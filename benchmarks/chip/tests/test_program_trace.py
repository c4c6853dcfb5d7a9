"""The program's side of a trace: device time by group, idle time by the
program's innermost span, the host metrics read from the program's spans,
and the detail tool, against hand counts, the recorded trace and a CPU
rehearsal.  None of it changes what ``trace_reduce.reduce`` reports: its
outputs on the recorded trace are pinned here.
"""
import json
import time
from pathlib import Path

import pytest

import detail
import harness
import program_trace as pt
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, name, start, dur, detail=""):
    line = tr.OPS_LINE if plane.startswith(tr.DEVICE_PREFIX) else "python"
    return tr.Event(plane, line, name, float(start), float(dur), detail)


GROUPS = json.loads((harness.HERE / "program_groups.json").read_text())["groups"]


def test_device_seconds_by_group_by_hand():
    events = [
        ev(DEV0, "%groot_ld_fused_grouped.3 = custom-call", 0, 30,
           "jit(_predict)/groot_ld_fused_grouped/pallas_call:"),
        ev(DEV0, "%fusion.7 = fusion", 30, 20, "jit(_predict)/gnn.degree/scatter-add:"),
        # names a kernel's output as its operand: not kernel time
        ev(DEV0, "%copy.4 = copy(f32[8,128] %groot_ld_fused_grouped.3)", 50, 5),
        ev(DEV0, "%groot_hd_grouped.1 = custom-call", 90, 20,
           "jit(_predict)/groot_hd_grouped/pallas_call:"),       # clipped to 90-100
        ev(DEV1, "%groot_ld_grouped.2 = custom-call", 10, 10,
           "jit(_predict)/groot_ld_grouped/pallas_call:"),
        ev(DEV1, "%fusion.9 = fusion", 20, 10, "jit(_predict)/gnn.gather/jit(_take)/gather:"),
        ev(HOST, "repro.execute.run", 0, 100),                   # not a device op
    ]
    got = pt.device_s_by_group(events, 0, 100, GROUPS)
    assert got["ld_kernel"] == pytest.approx((30 + 10) / 2 * 1e-9)
    assert got["hd_kernel"] == pytest.approx(10 / 2 * 1e-9)
    assert got["degree"] == pytest.approx(20 / 2 * 1e-9)
    assert got["gather"] == pytest.approx(10 / 2 * 1e-9)
    assert got["stage"] == got["head"] == 0.0


def test_load_reads_each_operation_scope_from_the_trace_metadata(tmp_path):
    """A small XSpace: the device operation's detail is its metadata's
    tf_op; the program's annotations are kept beside the benchmark's."""
    pb = pt._xplane_pb2()
    space = pb.XSpace()
    dev = space.planes.add(id=1, name=DEV0)
    dev.stat_metadata[1].id, dev.stat_metadata[1].name = 1, "tf_op"
    md = dev.event_metadata[1]
    md.id, md.name = 1, "%fusion.37 = f32[8] fusion(f32[8] %p)"
    md.stats.add(metadata_id=1, str_value="jit(_predict)/gnn.degree/scatter-add:")
    line = dev.lines.add(id=1, name=tr.OPS_LINE, timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=2_000_000, duration_ps=5_000_000)
    host = space.planes.add(id=2, name=HOST)
    for i, name in enumerate(("bench.window", "repro.parse.hash", "other"), start=1):
        host.event_metadata[i].id, host.event_metadata[i].name = i, name
    hl = host.lines.add(id=1, name="python", timestamp_ns=1000)
    for i in (1, 2, 3):
        hl.events.add(metadata_id=i, offset_ps=0, duration_ps=10_000_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    events = pt.load(path)
    (op,) = [e for e in events if e.plane == DEV0]
    assert op.detail == "jit(_predict)/gnn.degree/scatter-add:"
    assert op.start == pytest.approx(1000 + 2000) and op.dur == pytest.approx(5000)
    assert pt.device_s_by_group(events, 0, 1e9, GROUPS)["degree"] == pytest.approx(5e-6)
    assert sorted(e.name for e in events if e.plane == HOST) == ["bench.window",
                                                                 "repro.parse.hash"]
    # the harness's own load of the same file is unchanged
    assert [e.detail for e in tr.load(path) if e.plane == DEV0] == [""]


def test_idle_by_span_splits_one_gap_across_two_spans():
    events = [
        ev(DEV0, "op", 0, 20),
        ev(DEV0, "op", 80, 20),              # one gap: 20-80
        ev(HOST, "repro.session.verify", 0, 100),
        ev(HOST, "repro.verdict", 10, 40),    # 10-50: innermost over 20-50
        ev(HOST, "repro.parse", 60, 30),      # 60-90: innermost over 60-80
        ev(HOST, "bench.request", 0, 100),    # the benchmark's, not the program's
    ]
    got = pt.idle_by_span(events, 0, 100)
    assert got["verdict"] == pytest.approx(30e-9)
    assert got["session.verify"] == pytest.approx(10e-9)
    assert got["parse"] == pytest.approx(20e-9)
    assert got["unattributed"] == 0.0
    assert sum(got.values()) == pytest.approx(60e-9)
    # outside every program span the gap is unattributed
    got = pt.idle_by_span([ev(DEV0, "op", 0, 10), ev(HOST, "repro.plan", 40, 20)], 0, 100)
    assert got == pytest.approx({"plan": 20e-9, "unattributed": 70e-9})


def test_innermost_prefers_the_latest_start():
    pieces = pt.innermost([("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("d", 35, 60)])
    assert pieces == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 35, "b"),
                      (35, 60, "d"), (60, 100, "a")]


def test_recorded_trace_outputs_unchanged():
    events = tr.load_json(DATA / "small_trace.json")
    (win,) = [e for e in events if e.name == "bench.window"]
    patterns = json.loads((Path(tr.__file__).parent / "agg_kernels.json").read_text())["patterns"]
    red = tr.reduce(events, win.start, win.end, kernel_patterns=patterns)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.001051849, rel=1e-12)
    assert red["window_s"] == pytest.approx(0.112397279, rel=1e-12)
    assert red["kernel_s"] == pytest.approx(0.000344777, rel=1e-12)
    assert red["idle_by_label"] == pytest.approx({"unattributed": 0.11134543}, rel=1e-9)
    assert red["idle_gaps"][0] == ["unattributed", pytest.approx(0.058654433, rel=1e-9)]
    # the same kernels through the group reader
    by_group = pt.device_s_by_group(events, win.start, win.end, {"agg": patterns})
    assert by_group["agg"] == pytest.approx(red["kernel_s"], rel=1e-12)
    # the recorded trace predates the program's annotations: all idle is unattributed
    idle = pt.idle_by_span(events, win.start, win.end)
    assert idle == pytest.approx({"unattributed": red["window_s"] - red["busy_s"]})


def test_detail_rehearsal_reports_the_program_side():
    """A traced CPU run through the detail tool: the result line carries the
    new host metrics, the detail the program's spans."""
    line, found = detail.run(
        "csa256.verify", 2**33 + 777, 0.5, t_start=time.perf_counter(),
        require_chip=False, peaks={"flops_per_s": 1e12, "bytes_per_s": 1e11},
        shrink={"bits": 8, "batch": 1},
    )
    assert line["correct"] is True
    m = line["metrics"]
    for name in ("plan_lookup_s", "h2d_bytes"):
        assert name in m and m[name]["value"] > 0, name
    # gc_s reads the collections in the window, if a tiny run made any
    spans = found["program_spans"]
    assert ("gc_s" in m) == ("gc" in spans)
    if "gc" in spans:
        assert m["gc_s"]["value"] == pytest.approx(spans["gc"]["s_per_request"])
    assert m["plan_lookup_s"]["unit"] == "s" and m["h2d_bytes"]["unit"] == "bytes"
    # features (4 float32 per node) and the four edge arrays of csa:8
    from repro.core.aig import csa_multiplier

    g = csa_multiplier(8).to_edge_graph()
    want = 16 * g.num_nodes + sum(a.nbytes for a in (g.edge_src, g.edge_dst,
                                                    g.edge_inv, g.edge_slot))
    assert m["h2d_bytes"]["value"] == pytest.approx(want)
    assert found["requests"] == line["attempted"]
    for name in ("parse.hash", "execute.plan", "execute.stage", "execute.run",
                 "execute.fetch", "verdict.extract"):
        assert spans[name]["count"] == found["requests"], name
    assert set(found["device_s_per_request"]) == set(GROUPS)
