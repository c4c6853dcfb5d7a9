"""Benchmark orchestrator: one suite per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--json]

Default is the quick pass (CI-sized); --full reproduces the wider grids.
``--json`` additionally writes one ``BENCH_<suite>.json`` per suite, both
under ``experiments/bench/`` and at the repo root — suite runtime, every
table the suite saved (rows carry the peak-memory model / compile-count
columns), and an embedded ``repro.obs`` report (per-stage wall times from
a suite-scoped tracer, the process-counter delta, plan-cache hit rate) —
so the bench trajectory accumulates machine-readable points run over run.

Every suite runs in this one process, which holds every visible device:
no suite starts a child that would need a chip this process holds.  The
sharded suite streams over the powers of two up to
``jax.local_device_count()``; to fake a mesh on a CPU, set ``XLA_FLAGS``
for the whole run (``XLA_FLAGS=--xla_force_host_platform_device_count=8
python -m benchmarks.run --suites sharded``).  The timings are those of
whatever backend the process runs on: a CPU run times XLA's CPU backend
and the Pallas interpreter, not a chip.

The multi-pod dry-run + roofline tables are separate entry points
(python -m repro.launch.dryrun / python -m repro.roofline.report) since
they re-initialise jax with 512 host devices.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="write experiments/bench/BENCH_<suite>.json per suite")
    ap.add_argument("--suites", nargs="+", default=None,
                    help="run only the named suites (default: all)")
    args = ap.parse_args(argv)
    quick = [] if args.full else ["--quick"]

    from benchmarks import (
        bench_accuracy,
        bench_chaos,
        bench_features,
        bench_grouped,
        bench_memory,
        bench_partitioned,
        bench_service,
        bench_sharded,
        bench_spmm,
        bench_verification,
    )
    from benchmarks import common
    from repro.compile_cache import enable_compile_cache
    from repro.kernels.plan_cache import PLAN_CACHE
    from repro.obs import REGISTRY, Sampler, Tracer
    from repro.obs.flight import DUMP_DIR_ENV
    from repro.obs.regress import SCHEMA_VERSION, host_info

    if args.json:
        # failed tickets' flight records land next to the BENCH JSONs, so
        # CI's artifact upload carries the forensic trail too
        common.ART.mkdir(parents=True, exist_ok=True)
        os.environ.setdefault(DUMP_DIR_ENV, str(common.ART))

    enable_compile_cache()
    t0 = time.time()
    suites = [
        ("accuracy", "accuracy (Fig. 6/7)", bench_accuracy.main),
        ("memory", "memory (Fig. 8 / Table II)", bench_memory.main),
        ("spmm", "spmm kernels (Fig. 9)", bench_spmm.main),
        ("grouped", "grouped multi-polarity spmm (PR 2)", bench_grouped.main),
        ("verification", "verification runtime (Fig. 10)", bench_verification.main),
        ("features", "feature ablation (§III-B)", bench_features.main),
        ("service", "verification service (repro.service)", bench_service.main),
        ("partitioned", "partitioned streaming executor (repro.exec)",
         bench_partitioned.main),
        ("chaos", "failure-domain chaos gates (repro.faults)",
         bench_chaos.main),
        ("sharded", "sharded mesh streaming (repro.mesh)",
         bench_sharded.main),
    ]
    if args.suites:
        known = {k for k, _, _ in suites}
        unknown = set(args.suites) - known
        if unknown:
            ap.error(f"unknown suites {sorted(unknown)} (known: {sorted(known)})")
        suites = [s for s in suites if s[0] in args.suites]
    failed = []
    for key, name, fn in suites:
        print(f"\n#### {name} ####", flush=True)
        common.drain_tables()
        pc0 = PLAN_CACHE.snapshot()
        reg0 = REGISTRY.snapshot()
        tracer = Tracer()
        # per-suite JSONL time series over the process registry (queue
        # depth, executor gauges, stage latencies) — uploaded by CI next
        # to the BENCH JSONs
        sampler = (
            Sampler(common.ART / f"SAMPLER_{key}.jsonl", REGISTRY,
                    interval_s=0.5)
            if args.json else None
        )
        t_suite = time.time()
        err = None
        try:
            # every Session the suite builds (trace=False) emits its spans
            # into this suite-scoped tracer via the active-tracer fallback
            with tracer.activate():
                if sampler is not None:
                    sampler.start()
                fn(quick)
        except Exception as e:  # noqa: BLE001
            err = repr(e)
            failed.append((name, err))
            print(f"[FAIL] {name}: {e}")
        finally:
            if sampler is not None:
                sampler.stop()
        if args.json:
            pc1 = PLAN_CACHE.snapshot()
            lookups = (pc1.hits - pc0.hits) + (pc1.builds - pc0.builds)
            common.ART.mkdir(parents=True, exist_ok=True)
            payload = {
                "schema": SCHEMA_VERSION,
                "host": host_info(),
                "suite": key,
                "title": name,
                "ok": err is None,
                "error": err,
                "runtime_s": time.time() - t_suite,
                "quick": bool(quick),
                "plan_cache": {
                    "builds": pc1.builds - pc0.builds,
                    "hits": pc1.hits - pc0.hits,
                },
                "report": {
                    "stages": tracer.summary(),
                    "counters": REGISTRY.delta(reg0),
                    # null, not 0.0, when the suite never touched the plan
                    # cache — "0% hit rate" and "idle cache" are different
                    # dashboard facts
                    "plan_cache_hit_rate": (
                        (pc1.hits - pc0.hits) / lookups if lookups else None
                    ),
                },
                "tables": common.drain_tables(),
            }
            for path in (common.ART / f"BENCH_{key}.json",
                         REPO_ROOT / f"BENCH_{key}.json"):
                path.write_text(json.dumps(payload, indent=1))
                print(f"[json] wrote {path}")
    print(f"\nbenchmarks done in {time.time()-t0:.1f}s")
    if failed:
        for name, err in failed:
            print(f"FAILED: {name}: {err}")
        sys.exit(1)


if __name__ == "__main__":
    main()
