"""The comparison that decides ``correct``.

Every request the window completed is compared, once the window has closed,
with the plain reference (``references/``), which imports nothing of the
system under test and takes only the benchmark's own weights and designs:

  logit_gap_max         over every node of every request, how far the
                        reference's logit of the predicted class lies below
                        the reference's best logit (0 where they agree)
  verdict_fields_off    fields of the verdicts (status, adder count, XOR and
                        MAJ counts, coverage, eliminated terms) that differ
                        from the reference verdict over the same predictions
  requests_failed       requests that raised, returned nothing to compare, or
                        could not be judged (below)

Which reference a request is held to follows its route (``routing.mode``):

  full                  the model over the whole design, computed once a run
                        (``reference_logits``)
  partitioned, streamed, sharded
                        the model over each partition's re-grown subgraph
                        (``references/sage_regrowth.py``), under the
                        partition the program chose for that request, with
                        ``regrow`` and ``regrow_hops`` as the cell's own
                        session options state them.  Computed once per
                        distinct partition, keyed by the sha256 of its ids.

The partition is the program's heuristic choice, and any valid one is a
correct answer; the predictions must then be those of that partition.  It is
taken from the result's ``partition`` (one id per node: the partition whose
core holds it).  A program whose results have no such field is asked once a
run, through its public ``Session.prepare`` for the same design and options,
for the cores of its subgraphs.  The partition must cover the design's nodes
once each with the ids 0..k-1, every id used, and k equal to the result's
``routing.k``.

A request counts in ``requests_failed`` where it cannot be judged: a
partitioned route with no partition, an invalid one, or a cell that does not
state ``regrow`` and ``regrow_hops``; a partitioned route with ``batch`` above
1 (not supported); a full route that reports a partition; any other route.

Each number is held to its limit in ``limits/<cell>.json``; a number is
correct at or below its limit.
"""
from __future__ import annotations

import functools
import hashlib
import importlib
from typing import Optional

import numpy as np

VERDICT_FIELDS = ("status", "n_adders", "n_xor_pred", "n_maj_pred", "coverage",
                  "nonlinear_terms_eliminated")
PARTITIONED_ROUTES = ("partitioned", "streamed", "sharded")


def reference_logits(config: dict, traffic, params) -> np.ndarray:
    """One copy's reference logits, float32 at highest precision."""
    ref = importlib.import_module(f"references.{config['reference']['model']}")
    return ref.logits(params, ref.features(traffic.design), ref.graph(traffic.design))


def regrowth_options(session: dict) -> Optional[tuple[bool, int]]:
    """(regrow, hops) as the cell states them, or None where it does not."""
    regrow, hops = session.get("regrow"), session.get("regrow_hops")
    if isinstance(regrow, bool) and type(hops) is int and hops >= 1:
        return regrow, hops
    return None


def valid_partition(part, n: int, k: int) -> Optional[np.ndarray]:
    """``part`` as int32 where it gives each of the ``n`` nodes one of the ids
    0..k-1 and uses every one; else None."""
    if part is None:
        return None
    part = np.asarray(part)
    if part.shape != (n,) or part.dtype.kind not in "iu":
        return None
    if part.min() < 0 or part.max() != k - 1 or len(np.unique(part)) != k:
        return None
    return part.astype(np.int32)


def partition_of_cores(subgraphs, n: int):
    """Partition ids from the cores of the program's subgraphs (the first
    ``num_core`` of each one's ``global_ids``), or None where the cores do
    not cover the ``n`` nodes once each."""
    if not subgraphs:
        return None
    cores = [np.asarray(sg.global_ids[: sg.num_core], np.int64) for sg in subgraphs]
    ids = np.concatenate(cores)
    if not np.array_equal(np.sort(ids), np.arange(n)):
        return None
    part = np.empty(n, np.int64)
    part[ids] = np.repeat(np.arange(len(cores)), [len(c) for c in cores])
    return part


def program_partition(config: dict, traffic):
    """The partition the program's own prepare step chooses for the cell's
    design and session options (for results that report none)."""
    import harness

    sess = harness.make_session(traffic, config, None, False)
    try:
        prep = sess.prepare(traffic.program_design(), dataset=traffic.family,
                            bits=traffic.bits)
    finally:
        sess.close()
    return partition_of_cores(prep.subgraphs, int(traffic.design["kind"].shape[0]))


class References:
    """The reference logits each result is judged against, each computed
    once a run."""

    def __init__(self, config: dict, traffic, params):
        self.config, self.traffic, self.params = config, traffic, params
        self.full: Optional[np.ndarray] = None
        self.by_partition: dict = {}

    def for_result(self, res) -> Optional[np.ndarray]:
        """The logits ``res`` is held to, or None where it cannot be judged."""
        mode = res.routing.mode
        if mode == "full":
            if getattr(res, "partition", None) is not None:
                return None
            if self.full is None:
                self.full = reference_logits(self.config, self.traffic, self.params)
            return self.full
        options = regrowth_options(self.traffic.session)
        if mode not in PARTITIONED_ROUTES or options is None or self.traffic.batch != 1:
            return None
        part = res.partition if hasattr(res, "partition") else self.program_partition
        part = valid_partition(part, int(self.traffic.design["kind"].shape[0]), res.routing.k)
        if part is None:
            return None
        key = hashlib.sha256(part.tobytes()).hexdigest()
        if key not in self.by_partition:
            self.by_partition[key] = self.partitioned_logits(part, *options)
        return self.by_partition[key]

    @functools.cached_property
    def program_partition(self):
        return program_partition(self.config, self.traffic)

    def partitioned_logits(self, part: np.ndarray, regrow: bool, hops: int) -> np.ndarray:
        from references import sage_regrowth

        ref = importlib.import_module(f"references.{self.config['reference']['model']}")
        d = self.traffic.design
        return sage_regrowth.logits(self.params, ref.features(d), ref.graph(d), part,
                                    regrow=regrow, hops=hops)


def class_numbers(logits: np.ndarray, pred: np.ndarray, batch: int) -> tuple[float, float]:
    """(logit gap max, mismatch share) of the predictions of ``batch``
    tiled copies against one copy's reference logits."""
    n, c = logits.shape
    pred = np.asarray(pred)
    if pred.shape != (batch * n,):
        return 1e9, 1.0
    pred = pred.reshape(batch, n).astype(np.int64)
    if pred.min() < 0 or pred.max() >= c:
        return 1e9, 1.0
    gap = logits.max(axis=1)[None, :] - logits[np.arange(n)[None, :], pred]
    mismatch = (pred != logits.argmax(axis=1)[None, :]).mean()
    return float(gap.max()), float(mismatch)


def verdict_fields_off(config: dict, traffic, result, cache: dict) -> int:
    if not traffic.verify:
        return 0
    got = result.verdict
    if got is None:
        return len(VERDICT_FIELDS)
    pred = np.asarray(result.predictions)[: traffic.design["kind"].shape[0]]
    key = hashlib.sha256(pred.astype(np.int64).tobytes()).hexdigest()
    if key not in cache:
        ref = importlib.import_module(f"references.{config['reference']['verdict']}")
        cache[key] = ref.verdict(traffic.design, pred, bits=traffic.bits, signed=traffic.signed)
    want = cache[key]
    return sum(getattr(got, f) != want[f] for f in VERDICT_FIELDS)


def evaluate(config: dict, traffic, params, requests, limits: dict) -> dict:
    refs = References(config, traffic, params)
    gap = 0.0
    off = failed = 0
    verdicts: dict = {}
    for r in requests:
        res = r.result
        if res is None or getattr(res, "predictions", None) is None:
            failed += 1
            continue
        logits = refs.for_result(res)
        if logits is None:
            failed += 1
            continue
        gap = max(gap, class_numbers(logits, res.predictions, traffic.batch)[0])
        off += verdict_fields_off(config, traffic, res, verdicts)
    if not any(r.result is not None for r in requests):
        failed = max(failed, 1)
    found = {"logit_gap_max": gap, "verdict_fields_off": off, "requests_failed": failed}
    return {k: {"value": v, "limit": limits[k], "ok": v <= limits[k]} for k, v in found.items()}
