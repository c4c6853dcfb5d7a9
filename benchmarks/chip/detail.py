#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, with the
program's side of the trace beside the result line.

    python3 benchmarks/chip/detail.py --workload csa256.verify --seed 7 \
        --seconds 30 [--keep DIR]

Prints the result line, then one JSON object: device seconds per request
in each group of ``program_groups.json`` (the forward's named scopes and the
Pallas kernels' names) beside the device's busy seconds per request; the
window's idle seconds split across the program's innermost spans
(``program_trace.idle_by_span``) and the share of idle time under a named
span; and, per span name, the count and host seconds per request of the
program's spans in the window.  ``--keep DIR`` copies the profiler's
``.xplane.pb`` there.  The result line is the one ``run.py`` prints: the
harness runs unchanged, and the detail is read from the same trace before
the harness deletes it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def span_summary(spans, requests: int) -> dict:
    out: dict[str, dict] = {}
    for name, t0, t1, _ in spans:
        row = out.setdefault(name, {"count": 0, "s_per_request": 0.0})
        row["count"] += 1
        row["s_per_request"] += (t1 - t0) / requests
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["s_per_request"]))


def run(name: str, seed: int, seconds: float, *, t_start: float, keep=None,
        **cell_options) -> tuple[dict, dict]:
    """The result line and the detail of one traced run of ``name``."""
    import harness
    import program_trace as pt
    import trace_reduce as tr

    groups = json.loads((harness.HERE / "program_groups.json").read_text())["groups"]
    detail: dict = {}
    plain = harness.reduce_trace

    def reduce_trace(trace_dir, run_):
        reduced = plain(trace_dir, run_)
        path = tr.find_xplane(trace_dir)
        if keep is not None:
            Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, Path(keep) / f"{name}-{seed}.xplane.pb")
        events = pt.load(path)
        win = next(e for e in events if e.name == "bench.window")
        n = max(len(run_.done), 1)
        by_group = pt.device_s_by_group(events, win.start, win.end, groups)
        idle = pt.idle_by_span(events, win.start, win.end)
        idle_s = reduced["window_s"] - reduced["busy_s"]
        detail.update(
            requests=len(run_.done),
            busy_s_per_request=reduced["busy_s"] / n,
            device_s_per_request={k: v / n for k, v in by_group.items()},
            idle_s=idle_s,
            idle_by_span=idle,
            idle_named_share=1.0 - idle["unattributed"] / idle_s if idle_s > 0 else None,
            program_spans=span_summary(pt.program_spans(run_), n),
        )
        return reduced

    harness.reduce_trace = reduce_trace
    try:
        line = harness.run_cell(name, seed, seconds, True, t_start=t_start, **cell_options)
    finally:
        harness.reduce_trace = plain
    return line, detail


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell's name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seeds the weights")
    ap.add_argument("--seconds", type=float, required=True, help="length of the window")
    ap.add_argument("--keep", help="copy the profiler trace into this directory")
    args = ap.parse_args(argv)

    import harness

    try:
        line, detail = run(args.workload, args.seed, args.seconds, t_start=T_START,
                           keep=args.keep)
    except harness.NoChip as e:
        print(f"detail.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    print(json.dumps({"detail": detail}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
