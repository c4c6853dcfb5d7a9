"""The one traffic generator: a workload file and a configuration file in,
the designs and request parameters of a cell out.

A workload file (``workloads/<cell>.json``) holds only data:

  bits      width of the multiplier every request carries
  batch     identical copies tiled into one request (the paper's batch)
  verify    whether a request asks for the verdict
  session   route options handed to the session as they are (a memory
            budget, re-growth depth, packing capacity)

The configuration file names the design family (``families/<family>.py``),
the model, the training recipe and the session options it runs with.  The
design is the same for every seed.
"""
from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class Traffic:
    family: str
    bits: int
    batch: int
    verify: bool
    signed: bool
    design: dict             # the benchmark's own arrays of one copy
    session: dict            # SessionConfig fields: configuration, then workload

    @property
    def nodes(self) -> int:
        return self.batch * int(self.design["kind"].shape[0])

    @property
    def edges(self) -> int:
        """Two fanin edges per AND node and one per output, in every copy."""
        kind = self.design["kind"]
        return self.batch * int(2 * (kind == 1).sum() + (kind == 2).sum())

    def program_design(self):
        """The design as the system under test takes it."""
        from repro.core.aig import AIG

        d = self.design
        return AIG(name=d["name"], kind=d["kind"], fanin0=d["fanin0"],
                   fanin1=d["fanin1"], label=d["label"], n_pi=d["n_pi"], pos=d["pos"])


def family(name: str):
    return importlib.import_module(f"families.{name}")


def build(config: dict, workload: dict) -> Traffic:
    fam = family(config["family"])
    return Traffic(
        family=config["family"],
        bits=workload["bits"],
        batch=workload.get("batch", 1),
        verify=workload["verify"],
        signed=fam.SIGNED,
        design=fam.build(workload["bits"]),
        session={**config["session"], **workload.get("session", {}),
                 "batch": workload.get("batch", 1)},
    )
