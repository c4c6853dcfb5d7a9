"""From a JAX profiler trace to device busy time, idle share, kernel time by
name and idle gaps attributed to what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a flat
list of events; ``reduce`` works on that list alone, so it can be checked on
a small recorded trace without a chip.  Times are nanoseconds on the trace's
own clock.  Device planes are the ``/device:TPU:<n>`` planes; an operation is
an event on their ``XLA Ops`` line.  Busy time is the union of operation
intervals inside the window, averaged over the devices that ran anything.
"""
from __future__ import annotations

import dataclasses
import glob
import json
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: stats of a device event that can carry the name of the HLO op or kernel
NAME_STATS = ("hlo_op", "long_name", "tf_op", "name", "kernel_details")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float
    dur: float
    detail: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


def find_xplane(trace_dir) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" / "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def load(path, host_names: tuple = ("bench.",)) -> list[Event]:
    """Device operations, and the host events whose names start with one of
    ``host_names`` (the benchmark's own annotations)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(host_names):
                    continue
                detail = ""
                if device:
                    detail = " ".join(str(v) for k, v in ev.stats
                                      if k in NAME_STATS and isinstance(v, str))
                out.append(Event(plane.name, line.name, name, float(ev.start_ns),
                                 float(ev.duration_ns), detail))
    return out


def load_json(path) -> list[Event]:
    return [Event(**e) for e in json.loads(Path(path).read_text())]


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Sorted disjoint union of intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of disjoint sorted intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(t: float, spans) -> str:
    """The innermost span (latest start) open at time t, else unattributed."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s > best[1]):
            best = (name, s)
    return best[0] if best else "unattributed"


def matches(ev: Event, patterns) -> bool:
    text = f"{ev.name} {ev.detail}"
    return any(p in text for p in patterns)


def reduce(events: list[Event], lo: float, hi: float, *, spans=(),
           kernel_patterns=(), top: int = 10) -> dict:
    """Reduce the events of one window [lo, hi).

    ``spans``: (name, start, end) host spans on the trace's clock, which
    label the idle gaps.  ``kernel_patterns``: substrings that mark an
    operation as one of the kernels whose time ``kernel_s`` sums.
    """
    ops = [e for e in events if e.plane.startswith(DEVICE_PREFIX) and e.end > lo and e.start < hi]
    planes = sorted({e.plane for e in ops})
    window = hi - lo
    busy_total, idle = 0.0, []
    for plane in planes:
        busy = merged(((e.start, e.end) for e in ops if e.plane == plane), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        idle += [(label_at((s + e) / 2, spans), e - s) for s, e in gaps(busy, lo, hi)]
    n = max(len(planes), 1)
    by_name: dict[str, float] = {}
    kernel = 0.0
    for e in ops:
        d = min(e.end, hi) - max(e.start, lo)
        by_name[e.name] = by_name.get(e.name, 0.0) + d
        if matches(e, kernel_patterns):
            kernel += d
    busy_s = busy_total / n * 1e-9
    return {
        "devices": len(planes),
        "window_s": window * 1e-9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window * 1e-9) if window > 0 else None,
        "kernel_s": kernel / n * 1e-9,
        "device_ops": [[k, v / n * 1e-9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * 1e-9] for k, v in sorted(idle, key=lambda kv: -kv[1])[:top]],
        "idle_by_label": _sum_by_label(idle, n),
    }


def _sum_by_label(idle, n: int) -> dict:
    out: dict[str, float] = {}
    for k, v in idle:
        out[k] = out.get(k, 0.0) + v / n * 1e-9
    return out
