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
  requests_failed       requests that raised or returned nothing to compare

Each number is held to its limit in ``limits/<cell>.json``; a number is
correct at or below its limit.
"""
from __future__ import annotations

import hashlib
import importlib

import numpy as np

VERDICT_FIELDS = ("status", "n_adders", "n_xor_pred", "n_maj_pred", "coverage",
                  "nonlinear_terms_eliminated")


def reference_logits(config: dict, traffic, params) -> np.ndarray:
    """One copy's reference logits, float32 at highest precision."""
    ref = importlib.import_module(f"references.{config['reference']['model']}")
    return ref.logits(params, ref.features(traffic.design), ref.graph(traffic.design))


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
    logits = reference_logits(config, traffic, params)
    gap = 0.0
    off = failed = 0
    verdicts: dict = {}
    for r in requests:
        res = r.result
        if res is None or getattr(res, "predictions", None) is None:
            failed += 1
            continue
        gap = max(gap, class_numbers(logits, res.predictions, traffic.batch)[0])
        off += verdict_fields_off(config, traffic, res, verdicts)
    if not any(r.result is not None for r in requests):
        failed = max(failed, 1)
    found = {"logit_gap_max": gap, "verdict_fields_off": off, "requests_failed": failed}
    return {k: {"value": v, "limit": limits[k], "ok": v <= limits[k]} for k, v in found.items()}
