"""One run of one cell: set-up, a closed-loop window, the reference check,
and the result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its traffic in ``workloads/<cell>.json``, its configuration in the file
``BENCHMARK.json`` names, the limits of its check in ``limits/<cell>.json``,
and each metric's reader in ``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = HERE / ".jax_cache"
SCRATCH = HERE / ".scratch"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PROGRAM_SPANS = ("parse", "plan", "execute", "verdict")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class CompileMeter:
    """XLA compiles, from JAX's monitoring events."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class Request:
    t0: float
    t1: float
    result: object = None
    error: Optional[str] = None


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: dict
    config: dict
    traffic: object
    setup_s: float
    requests: list
    compiles_in_window: int
    memory_peak_bytes: Optional[int]
    peak: Optional[dict]
    spans: list = dataclasses.field(default_factory=list)     # (name, t0, t1) perf s
    reduced: Optional[dict] = None                             # trace_reduce.reduce

    @property
    def done(self) -> list:
        return [r for r in self.requests if r.error is None]

    @property
    def window_s(self) -> float:
        return self.requests[-1].t1 - self.requests[0].t0

    def span_mean(self, name: str) -> Optional[float]:
        d = [t1 - t0 for n, t0, t1 in self.spans if n == name]
        return sum(d) / len(self.done) if d and self.done else None


def load_bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(bench: dict, name: str) -> tuple[dict, dict, dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (cell, json.loads((ROOT / config["file"]).read_text()),
            json.loads((HERE / "workloads" / f"{name}.json").read_text()),
            json.loads((HERE / "limits" / f"{name}.json").read_text()))


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, run: Run):
    return importlib.import_module(f"metrics.{name}").read(run)


def start_jax(chips: int, require_chip: bool, matmul_precision: str):
    """Import JAX with the compile cache inside the checkout and the
    configuration's matmul precision; check the chip."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # libtpu would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    # the precision every XLA matmul without one of its own runs at (the
    # TPU's default is one bfloat16 pass over float32 inputs)
    jax.config.update("jax_default_matmul_precision", matmul_precision)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return jax, devices


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def make_session(traffic, config: dict, params, trace: bool):
    from repro.api import Session, SessionConfig
    from repro.core.gnn import GNNConfig

    return Session(params, SessionConfig(gnn=GNNConfig(**config["gnn"]), trace=trace,
                                         **traffic.session))


def request(sess, traffic, design):
    return sess.verify(design, dataset=traffic.family, bits=traffic.bits,
                       verify=traffic.verify, signed=traffic.signed,
                       use_cache=False, return_predictions=True)


def make_params(config: dict, seed: int):
    """The cell's weights, on the device: the configuration's trained model,
    its hidden units relabelled from the seed."""
    import jax
    import numpy as np

    from traffic import family

    ref = importlib.import_module(f"references.{config['reference']['model']}")
    recipe = config["train"]
    # any whole number, wider than 32 bits too, folds to one PRNG key
    key = jax.random.key(int(np.random.SeedSequence(seed).generate_state(1)[0]))
    return ref.weights(config["gnn"], recipe, family(recipe["family"]).build(recipe["bits"]), key)


def window(sess, traffic, design, seconds: float, meter, trace_dir=None):
    """Closed loop, one request at a time, issuing until ``seconds`` have
    passed; the request in flight then finishes and counts."""
    import jax

    requests = []
    c0 = meter.count
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            w0 = time.perf_counter()
            while not requests or time.perf_counter() - w0 < seconds:
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.request"):
                    try:
                        res, err = request(sess, traffic, design), None
                    except Exception as e:  # a failed request is counted, not fatal
                        res, err = None, f"{type(e).__name__}: {e}"
                requests.append(Request(t0, time.perf_counter(), res, err))
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return requests, meter.count - c0


def reduce_trace(trace_dir, run: Run) -> dict:
    """The window's trace, with the program's spans moved onto its clock by
    the offset between the benchmark's request annotations and their
    host-clock starts."""
    import trace_reduce as tr

    events = tr.load(tr.find_xplane(trace_dir))
    marks = sorted(e.start for e in events if e.name == "bench.request")
    win = [e for e in events if e.name == "bench.window"]
    if not win or len(marks) != len(run.requests):
        raise RuntimeError(f"trace holds {len(win)} window and {len(marks)} request "
                           f"annotations for {len(run.requests)} requests")
    offs = sorted(m - r.t0 * 1e9 for m, r in zip(marks, run.requests))
    off = offs[len(offs) // 2]
    spans = [(n, t0 * 1e9 + off, t1 * 1e9 + off) for n, t0, t1 in run.spans]
    patterns = json.loads((HERE / "agg_kernels.json").read_text())["patterns"]
    return tr.reduce(events, win[0].start, win[0].end, spans=spans, kernel_patterns=patterns)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True, peaks: Optional[dict] = None,
             shrink: Optional[dict] = None) -> dict:
    """One run; returns the result line as a dict (``checks`` last).

    ``shrink`` (tests only) replaces workload fields: a tiny width for a
    CPU rehearsal.
    """
    bench = load_bench()
    cell, config, workload, limits = find(bench, name)
    if shrink:
        workload = {**workload, **shrink,
                    "session": {**workload.get("session", {}), **shrink.get("session", {})}}
    jax, devices = start_jax(cell["chips"], require_chip, config["matmul_precision"])
    sys.path.insert(0, str(ROOT / "src"))
    import checks
    import traffic as traffic_mod

    dev = devices[0]
    peak = peaks if peaks is not None else peaks_for(dev.device_kind)
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter)

    params = jax.block_until_ready(make_params(config, seed))
    traffic = traffic_mod.build(config, workload)
    design = traffic.program_design()
    sess = make_session(traffic, config, params, trace)
    request(sess, traffic, design)
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if trace:
        trace_dir = SCRATCH / f"trace-{os.getpid()}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    requests, compiles = window(sess, traffic, design, seconds, meter, trace_dir)
    stats = dev.memory_stats() or {}
    run = Run(cell=cell, config=config, traffic=traffic, setup_s=setup_s,
              requests=requests, compiles_in_window=compiles,
              memory_peak_bytes=stats.get("peak_bytes_in_use"), peak=peak)
    if trace:
        w0, w1 = requests[0].t0, requests[-1].t1
        run.spans = [(s.name, s.t0, s.t1) for s in sess.obs.tracer.spans()
                     if s.name in PROGRAM_SPANS and w0 <= s.t0 and s.t1 <= w1]
        try:
            run.reduced = reduce_trace(trace_dir, run)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    sess.close()
    del sess

    found = checks.evaluate(config, traffic, params, requests, limits)
    metrics = {}
    for m in metrics_for(bench, name, trace):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices[:cell["chips"]]),
              "memory_peak_bytes": run.memory_peak_bytes or 0}
    line = {"correct": all(c["ok"] for c in found.values()),
            "attempted": len(requests),
            "failed": sum(r.error is not None for r in requests),
            "metrics": metrics, "device": device}
    if run.reduced is not None:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        line["breakdown"] = {"device_ops": run.reduced["device_ops"],
                             "idle_gaps": run.reduced["idle_gaps"]}
        line["idle_by_label"] = run.reduced["idle_by_label"]
    routed = next((r.result.routing for r in requests if r.result is not None), None)
    if routed is not None:
        line["route"] = {"mode": routed.mode, "k": routed.k, "buckets": routed.num_buckets}
    errors = [r.error for r in requests if r.error]
    if errors:
        line["first_error"] = errors[0][:500]
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in found.items()}
    return line
