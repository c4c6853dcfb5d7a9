#!/usr/bin/env python3
"""Readings for the limits of a cell's check, on the chip, at the cell's size.

    python3 benchmarks/chip/control.py --workload csa256.verify --seeds 1 2 3

For each seed, in one process: the weights are made as in a run, and the
timed path answers one request on each of two sides, read with the numbers
of ``checks.py`` against the float32 reference at highest precision that
``checks.References`` holds the request to (the full graph, or the re-grown
subgraphs of the request's partition):

  program  the system as the cell runs it (the lower readings)
  control  the system with its own bfloat16 edge-stream path switched on
           (``gnn.stream_dtype`` bfloat16), the step below the float32 the
           configuration states (the upper readings)

One JSON line per seed and side goes to standard output.  The benchmark's
own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def control_config(config: dict) -> dict:
    """The configuration with the program's bfloat16 edge streams on."""
    return {**config, "gnn": {**config["gnn"], "stream_dtype": "bfloat16"}}


def readings(name: str, seeds, *, require_chip: bool = True, shrink=None):
    """Yield one dict per seed and side."""
    import harness

    bench = harness.load_bench()
    cell, config, workload, _ = harness.find(bench, name)
    if shrink:
        workload = {**workload, **shrink}
    jax, _ = harness.start_jax(cell["chips"], require_chip, config["matmul_precision"])
    sys.path.insert(0, str(harness.ROOT / "src"))
    import numpy as np

    import checks
    import traffic as traffic_mod

    traffic = traffic_mod.build(config, workload)
    design = traffic.program_design()
    sides = {"program": config, "control": control_config(config)}
    sessions = {}
    for seed in seeds:
        params = jax.block_until_ready(harness.make_params(config, seed))
        refs = checks.References(config, traffic, params)
        for side, cfg in sides.items():
            if side not in sessions:
                sessions[side] = harness.make_session(traffic, cfg, params, False)
            else:
                sessions[side].set_params(params)
            t0 = time.perf_counter()
            res = harness.request(sessions[side], traffic, design)
            t_req = time.perf_counter() - t0
            t0 = time.perf_counter()
            ref = refs.for_result(res)
            row = {"cell": name, "seed": seed, "side": side, "route": res.routing.mode,
                   "k": res.routing.k, "ref_s": time.perf_counter() - t0,
                   "request_s": t_req, "status": getattr(res.verdict, "status", None),
                   "coverage": getattr(res.verdict, "coverage", None)}
            if ref is None:             # the check cannot judge this result
                yield {**row, "judged": False}
                continue
            top2 = np.sort(ref, axis=1)[:, -2:]
            gap, mismatch = checks.class_numbers(ref, res.predictions, traffic.batch)
            yield {**row, "judged": True, "logit_gap_max": gap,
                   "class_mismatch_share": mismatch,
                   "verdict_fields_off": checks.verdict_fields_off(config, traffic, res, {}),
                   "min_margin": float((top2[:, 1] - top2[:, 0]).min()),
                   "accuracy": float((ref.argmax(1) == traffic.design["label"]).mean())}
    for sess in sessions.values():
        sess.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for row in readings(args.workload, args.seeds):
        print(json.dumps(row), flush=True)
    print(json.dumps({"done_s": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
