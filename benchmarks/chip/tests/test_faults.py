"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the system under test (its inference or verdict
stage), the rest of a run is driven as on the chip at a tiny width on the
CPU, and ``correct`` must read false.  The faults a cell of this benchmark
can have: an answer altered where it is produced (one node's class, or the
verdict), and half of a batch left out.  (No cell trains or spans chips, so
a step that returns its state unchanged and a missing exchange between chips
do not apply.)
"""
import dataclasses

import numpy as np
import pytest

from repro.core import pipeline
from test_rehearsal import BENCH, rehearse

VERIFY_CELL = next(w["name"] for w in BENCH["workloads"] if "verify" in w["name"])
BATCH_CELL = next(w["name"] for w in BENCH["workloads"] if "x16" in w["name"])


def break_infer(monkeypatch, alter):
    real = pipeline.infer

    def infer(params, prep, **kw):
        pred = np.array(real(params, prep, **kw))
        alter(pred)
        return pred

    monkeypatch.setattr(pipeline, "infer", infer)


def one_class_altered(pred):
    pred[0] = 1 if pred[0] != 1 else 2           # node 0 is a primary input


def half_the_batch_left_out(pred):
    pred[len(pred) // 2:] = 0


@pytest.mark.parametrize("name,alter", [
    (VERIFY_CELL, one_class_altered),
    (BATCH_CELL, one_class_altered),
    (BATCH_CELL, half_the_batch_left_out),
])
def test_altered_predictions_are_caught(monkeypatch, name, alter):
    break_infer(monkeypatch, alter)
    line = rehearse(name, False)
    assert line["correct"] is False
    assert line["checks"]["logit_gap_max"]["value"] > line["checks"]["logit_gap_max"]["limit"]


def test_altered_verdict_is_caught(monkeypatch):
    real = pipeline.verify_prepared

    def verify_prepared(prep, pred, **kw):
        v = real(prep, pred, **kw)
        return dataclasses.replace(v, n_adders=v.n_adders + 1)

    monkeypatch.setattr(pipeline, "verify_prepared", verify_prepared)
    line = rehearse(VERIFY_CELL, False)
    assert line["correct"] is False
    assert line["checks"]["verdict_fields_off"]["value"] > 0
