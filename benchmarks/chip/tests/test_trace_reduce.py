"""The trace reduction against hand counts and a small recorded trace.

The recorded trace (``data/small_trace.json``) is the event list that
``trace_reduce.load`` read from a profiler trace of three csa:8 requests on a
TPU v5e.  Its busy time is checked against an independent count: every
elementary segment between two event boundaries is busy when any operation
covers its midpoint.
"""
import json
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def ev(plane, name, start, dur, detail=""):
    return tr.Event(plane, tr.OPS_LINE, name, float(start), float(dur), detail)


def test_busy_idle_and_kernels_by_hand():
    events = [
        ev(DEV0, "fusion.1", 0, 30),
        ev(DEV0, "custom-call.2", 20, 30, "_ld_kernel_grouped"),  # overlaps: busy 0-50
        ev(DEV0, "fusion.1", 70, 10),                              # busy 70-80
        ev(DEV0, "copy", 95, 20),                                  # clipped to 95-100
        ev(DEV1, "fusion.1", 10, 40),                              # busy 10-50
        ev("/host:CPU", "bench.window", 0, 100),                   # not a device op
    ]
    spans = [("execute", 0, 65), ("verdict", 65, 100), ("parse", 85, 90)]
    red = tr.reduce(events, 0, 100, spans=spans, kernel_patterns=["_ld_kernel"])
    assert red["devices"] == 2
    # device 0 busy 50 + 10 + 5 = 65, device 1 busy 40: mean 52.5 ns
    assert red["busy_s"] == pytest.approx(52.5e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["idle_share"] == pytest.approx(1 - 52.5 / 100)
    assert red["kernel_s"] == pytest.approx(30e-9 / 2)
    # device 0 gaps 50-70 (execute), 80-95 (parse, innermost at 87.5);
    # device 1 gaps 0-10 (execute), 50-100 (verdict)
    got = sorted((k, round(v * 1e9, 6)) for k, v in red["idle_gaps"])
    assert got == [("execute", 10.0), ("execute", 20.0), ("parse", 15.0), ("verdict", 50.0)]
    assert red["idle_by_label"]["verdict"] == pytest.approx(25e-9)
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx((30 + 10 + 40) / 2 * 1e-9)
    assert ops["copy"] == pytest.approx(5 / 2 * 1e-9)


def test_gap_outside_every_span_is_unattributed():
    red = tr.reduce([ev(DEV0, "op", 10, 10)], 0, 30, spans=[("plan", 0, 8)])
    assert sorted(k for k, _ in red["idle_gaps"]) == ["plan", "unattributed"]


def _busy_by_segments(events, lo, hi) -> float:
    """Independent count: elementary segments covered by any operation."""
    ops = [(e.start, e.end) for e in events if e.plane.startswith(tr.DEVICE_PREFIX)]
    points = sorted({lo, hi} | {min(max(t, lo), hi) for s, e in ops for t in (s, e)})
    busy = 0.0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in ops):
            busy += b - a
    return busy


def test_recorded_trace():
    events = tr.load_json(DATA / "small_trace.json")
    (win,) = [e for e in events if e.name == "bench.window"]
    assert sum(e.name == "bench.request" for e in events) == 3
    patterns = json.loads((Path(tr.__file__).parent / "agg_kernels.json").read_text())["patterns"]
    red = tr.reduce(events, win.start, win.end, kernel_patterns=patterns)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(_busy_by_segments(events, win.start, win.end) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] == pytest.approx(win.dur * 1e-9)
    # the aggregation kernels ran, and are part of the device time
    assert 0 < red["kernel_s"] <= sum(v for _, v in red["device_ops"]) + 1e-12 or \
        0 < red["kernel_s"] <= red["busy_s"] * 1.0000001
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["idle_by_label"].values()) == pytest.approx(idle)


def test_merged_and_gaps():
    assert tr.merged([(5, 9), (0, 2), (1, 3), (8, 12)], 0, 10) == [(0, 3), (5, 10)]
    assert tr.gaps([(0, 3), (5, 10)], 0, 12) == [(3, 5), (10, 12)]
