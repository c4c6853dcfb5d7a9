"""The control, in the program's place, comes out not correct.

The control is the system's own bfloat16 edge-stream path
(``control.control_config``: ``gnn.stream_dtype`` bfloat16), the step below
the float32 the configuration states.  Here it runs a whole cell on the CPU
at a width a test can hold; on the chip ``control.py`` reads it at the
cell's own size.  The CPU trains a different model from the same key than
the chip does (its float sums round otherwise over 300 epochs), so the test
serves the model of ``CPU_TRAIN_KEY``, on which the CPU's bfloat16 streams
change classes at this width, in place of the configuration's key.
"""
import control
import harness
from test_rehearsal import BENCH, rehearse

VERIFY_CELL = next(w["name"] for w in BENCH["workloads"] if "verify" in w["name"])
CONTROL_BITS = 32
CPU_TRAIN_KEY = 18


def test_bf16_stream_control_is_not_correct(monkeypatch):
    real = harness.find

    def find(bench, name):
        cell, config, workload, limits = real(bench, name)
        config = {**config, "train": {**config["train"], "key": CPU_TRAIN_KEY}}
        return cell, control.control_config(config), workload, limits

    monkeypatch.setattr(harness, "find", find)
    line = rehearse(VERIFY_CELL, False, bits=CONTROL_BITS)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]
