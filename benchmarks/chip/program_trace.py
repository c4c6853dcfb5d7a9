"""The program's own spans in a run, on both clocks.

``program_spans`` reads the session's tracer (host ``perf_counter``
seconds), which the results of a traced run carry; the program's metric
readers use it.  The rest works on the event list of ``trace_reduce.load``
(nanoseconds on the profiler's clock): while its tracer is active the
program opens a profiler annotation named ``repro.<span>`` for every span,
so its spans sit on the trace's host plane beside the benchmark's
``bench.*`` annotations, on the device trace's own clock.

``device_s_by_group`` sums device time per group of operations, matched by
substrings of an operation's name and detail as ``trace_reduce.reduce``
matches its kernels; ``idle_by_span`` splits each idle gap of the device
across the program's innermost span at each instant.  Nothing here is
needed by, or changes, what ``trace_reduce.reduce`` reports.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import importlib.util
from pathlib import Path
from typing import Optional

import trace_reduce as tr

#: the prefix of the program's profiler annotations (``repro.obs.trace``)
ANNOTATION_PREFIX = "repro."


def program_spans(run) -> list[tuple[str, float, float, dict]]:
    """Every span of the session's tracer inside the window, as (name, t0,
    t1, attrs) in host seconds; empty when the run was not traced."""
    tracer = next((r.result.trace.tracer for r in run.requests
                   if getattr(getattr(r, "result", None), "trace", None) is not None),
                  None)
    if tracer is None or not run.requests:
        return []
    w0, w1 = run.requests[0].t0, run.requests[-1].t1
    return [(s.name, s.t0, s.t1, dict(s.attrs)) for s in tracer.spans()
            if w0 <= s.t0 and s.t1 <= w1]


def per_request(run, name: str, attr: Optional[str] = None) -> Optional[float]:
    """Spans ``name`` in the window, summed per completed request: their
    seconds, or their attribute ``attr``; None where the program records no
    such span."""
    found = [(t1 - t0) if attr is None else attrs[attr]
             for n, t0, t1, attrs in program_spans(run)
             if n == name and (attr is None or attr in attrs)]
    if not found or not run.done:
        return None
    return sum(found) / len(run.done)


def load(path) -> list[tr.Event]:
    """Device operations, the benchmark's annotations and the program's.

    A device operation's detail is the ``tf_op`` of its event metadata:
    the HLO op's ``op_name`` path, which holds the ``jax.named_scope``
    scopes it ran under and a Pallas kernel's name
    (``jit(_predict)/gnn.degree/scatter-add:``).  ``jax.profiler``'s reader
    does not show metadata stats, so they are read from the ``XSpace``
    proto itself."""
    ops = _tf_ops(path)
    return [dataclasses.replace(e, detail=ops.get((e.plane, e.name), e.detail))
            if e.plane.startswith(tr.DEVICE_PREFIX) else e
            for e in tr.load(path, host_names=("bench.", ANNOTATION_PREFIX))]


def _xplane_pb2():
    """The ``XSpace`` proto module shipped with TensorFlow's profiler,
    loaded from its file: importing the package would start TensorFlow."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ModuleNotFoundError("the XSpace proto (tensorflow/tsl) is not installed")
    path = Path(spec.submodule_search_locations[0]) / "tsl/profiler/protobuf/xplane_pb2.py"
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _tf_ops(path) -> dict:
    """(device plane, operation name) -> the ``tf_op`` of its metadata."""
    space = _xplane_pb2().XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        tf_op = next((k for k, m in plane.stat_metadata.items() if m.name == "tf_op"), None)
        for md in plane.event_metadata.values():
            for st in md.stats:
                if st.metadata_id == tf_op:
                    ref = plane.stat_metadata[st.ref_value].name if st.ref_value else ""
                    out[(plane.name, md.name)] = st.str_value or ref
    return out


def device_s_by_group(events, lo: float, hi: float, groups: dict) -> dict:
    """Device seconds in [lo, hi) of the operations each group's substrings
    match, per device (the mean over the devices that ran anything), each
    operation clipped to the window as ``kernel_s`` is."""
    ops = [e for e in events if e.plane.startswith(tr.DEVICE_PREFIX) and e.end > lo
           and e.start < hi]
    n = max(len({e.plane for e in ops}), 1)
    out = {}
    for name, patterns in groups.items():
        d = sum(min(e.end, hi) - max(e.start, lo) for e in ops if tr.matches(e, patterns))
        out[name] = d / n * 1e-9
    return out


def host_spans(events) -> list[tuple[str, float, float]]:
    """The program's spans from its annotations: (name, start, end) ns."""
    k = len(ANNOTATION_PREFIX)
    return [(e.name[k:], e.start, e.end) for e in events
            if not e.plane.startswith(tr.DEVICE_PREFIX)
            and e.name.startswith(ANNOTATION_PREFIX)]


def innermost(spans) -> list[tuple[float, float, str]]:
    """Sorted disjoint (start, end, name) pieces of time, each under the
    innermost span (the latest start) open there; time under no span is
    left out."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    open_, out, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(order) and spans[order[j]][1] <= a:
            i = order[j]
            heapq.heappush(open_, (-spans[i][1], i))
            j += 1
        while open_ and spans[open_[0][1]][2] <= a:
            heapq.heappop(open_)
        if open_:
            name = spans[open_[0][1]][0]
            if out and out[-1][1] == a and out[-1][2] == name:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def split_by_span(gaps, pieces) -> dict:
    """The seconds of sorted disjoint ``gaps`` under each piece's span
    (``innermost``'s output); the rest goes to ``unattributed``."""
    starts = [a for a, _, _ in pieces]
    out: dict[str, float] = {}
    for lo, hi in gaps:
        rest = hi - lo
        k = max(bisect.bisect_right(starts, lo) - 1, 0)
        while k < len(pieces) and pieces[k][0] < hi:
            a, b, name = pieces[k]
            d = min(b, hi) - max(a, lo)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                rest -= d
            k += 1
        out["unattributed"] = out.get("unattributed", 0.0) + rest
    return out


def idle_by_span(events, lo: float, hi: float, top: int = 10) -> dict:
    """The device's idle seconds in [lo, hi), per device, by the program's
    innermost span open at each instant: the ``top`` spans with the most,
    then ``unattributed`` (idle under no program span)."""
    pieces = innermost(host_spans(events))
    ops = [e for e in events if e.plane.startswith(tr.DEVICE_PREFIX) and e.end > lo
           and e.start < hi]
    planes = sorted({e.plane for e in ops})
    total: dict[str, float] = {}
    for plane in planes:
        busy = tr.merged(((e.start, e.end) for e in ops if e.plane == plane), lo, hi)
        for k, v in split_by_span(tr.gaps(busy, lo, hi), pieces).items():
            total[k] = total.get(k, 0.0) + v
    n = max(len(planes), 1)
    unattributed = total.pop("unattributed", 0.0)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    out = {k: v / n * 1e-9 for k, v in ranked}
    out["unattributed"] = unattributed / n * 1e-9
    return out
