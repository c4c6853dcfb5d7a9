"""The spans inside a traced verify, garbage collection as spans, and the
program's spans in a JAX profiler trace.

A traced csa:32 (and csa:16) verify records the stage spans' children where the work
happens (``parse.hash``, ``execute.plan/stage/run/fetch``,
``verdict.extract``/``verdict.simulate``), and they cover their stages.  A
collection under an active tracer is a ``gc`` span; an untraced verify
leaves ``gc.callbacks`` alone and opens no profiler annotation.  A CPU
profiler trace carries every span as a ``repro.<name>`` annotation on its
host plane, which the benchmark's ``trace_reduce.load`` reads.
"""
from __future__ import annotations

import gc
import sys
from pathlib import Path

import jax
import pytest

from repro.api import Session, SessionConfig
from repro.core import aig as A
from repro.core import pipeline as P
from repro.obs import Tracer, span, span_coverage
from repro.obs import trace as obs_trace

CHIP_BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


@pytest.fixture(scope="module")
def trained_params():
    params, _ = P.train_model("csa", 8, epochs=200)
    return params


@pytest.fixture(scope="module")
def csa32():
    return A.csa_multiplier(32)


def _traced_verify(params, design, backend="groot"):
    sess = Session(params, SessionConfig(trace=True, backend=backend))
    sess.verify(design, use_cache=False)            # compile + plan build
    return sess.verify(design, use_cache=False)


@pytest.mark.parametrize("bits", [32, 16])
def test_traced_verify_records_work_spans_under_their_stages(trained_params, bits):
    design = A.csa_multiplier(bits)
    r = _traced_verify(trained_params, design)
    spans = r.trace.spans()
    by_id = {s.span_id: s for s in spans}
    parent = lambda s: by_id[s.parent_id].name if s.parent_id in by_id else None
    named = {}
    for s in spans:
        named.setdefault(s.name, []).append(s)
    want = {
        "parse.hash": "parse",
        "execute.plan": "execute",
        "execute.stage": "execute",
        "execute.run": "execute",
        "execute.fetch": "execute",
        "verdict.extract": "verdict",
    }
    for name, stage in want.items():
        assert [parent(s) for s in named[name]] == [stage], name
    # csa:16 extracts the whole adder tree, so its verdict simulates too
    assert (r.status == "verified") == (bits == 16)
    if bits == 16:
        assert [parent(s) for s in named["verdict.simulate"]] == ["verdict"]
    else:
        assert "verdict.simulate" not in named
    # the layer spans these replace are gone
    assert "gnn.predict" not in named and "pipeline.verify_prepared" not in named

    hashed = named["parse.hash"][0].attrs
    assert hashed["bytes"] > design.num_nodes     # the binary AIGER encoding
    assert named["execute.plan"][0].attrs["hit"] is True   # second verify
    g = design.to_edge_graph()
    staged = 4 * 4 * g.num_nodes + sum(
        a.nbytes for a in (g.edge_src, g.edge_dst, g.edge_inv, g.edge_slot)
    )
    assert named["execute.stage"][0].attrs["h2d_bytes"] == staged
    for stage in ("execute", "verdict"):
        (s,) = named[stage]
        assert span_coverage(spans, s.span_id) >= 0.95, stage


def test_forced_collection_is_a_gc_span_under_the_open_span():
    tr = Tracer()
    with tr.activate():
        assert obs_trace._on_gc in gc.callbacks
        with span("work") as work:
            gc.collect()
    assert obs_trace._on_gc not in gc.callbacks
    (g,) = [s for s in tr.spans() if s.name == "gc"]
    assert g.parent_id == work.span_id
    assert g.attrs["generation"] == 2 and g.attrs["collected"] >= 0
    assert g.t1 >= g.t0


def test_nested_activations_hook_gc_once():
    t1, t2 = Tracer(), Tracer()
    before = list(gc.callbacks)
    with t1.activate():
        with t2.activate():
            assert gc.callbacks.count(obs_trace._on_gc) == 1
            gc.collect()
        assert obs_trace._on_gc in gc.callbacks
    assert gc.callbacks == before
    # the collection went to the innermost active tracer only
    assert [s.name for s in t2.spans()] == ["gc"] and t1.spans() == []


def test_untraced_verify_touches_neither_gc_nor_the_profiler(monkeypatch, csa32):
    from repro.core import gnn

    entered = []

    class Counting:
        def __init__(self, name, **_):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(obs_trace, "_annotation_cls", Counting)
    hooked = []
    real_hook = obs_trace._gc_hook

    def counting_hook(add):
        hooked.append(add)
        real_hook(add)

    monkeypatch.setattr(obs_trace, "_gc_hook", counting_hook)
    params = gnn.init_params(gnn.GNNConfig(), jax.random.key(0))
    before = list(gc.callbacks)

    Session(params, SessionConfig(trace=False, backend="ref")).verify(
        csa32, use_cache=False
    )
    assert hooked == [] and entered == [] and gc.callbacks == before

    # the same patches see a traced verify hook gc and annotate every span
    Session(params, SessionConfig(trace=True, backend="ref")).verify(
        csa32, use_cache=False
    )
    assert hooked == [True, False]
    assert "repro.session.verify" in entered and "repro.parse.hash" in entered
    assert gc.callbacks == before


def test_program_spans_on_the_profiler_host_plane(tmp_path, csa32):
    from repro.core import gnn

    sys.path.insert(0, str(CHIP_BENCH))
    try:
        import trace_reduce as tr
    finally:
        sys.path.remove(str(CHIP_BENCH))

    params = gnn.init_params(gnn.GNNConfig(), jax.random.key(0))
    sess = Session(params, SessionConfig(trace=True, backend="ref"))
    sess.verify(csa32, use_cache=False)              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.request"):
            sess.verify(csa32, use_cache=False)
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)

    events = tr.load(path, host_names=("bench.", obs_trace.ANNOTATION_PREFIX))
    names = {e.name for e in events}
    for stage in ("session.verify", "parse", "parse.hash", "plan", "execute",
                  "execute.plan", "execute.stage", "execute.run", "execute.fetch",
                  "verdict", "verdict.extract"):
        assert obs_trace.ANNOTATION_PREFIX + stage in names, stage
    (req,) = [e for e in events if e.name == "bench.request"]
    (root,) = [e for e in events if e.name == "repro.session.verify"]
    assert req.start <= root.start and root.end <= req.end     # one clock
    # the benchmark's own filter still sees only its annotations
    assert {e.name for e in tr.load(path)} == {"bench.request"}


def test_gc_hook_under_concurrent_activations():
    """Threads activate their own tracers and collect at once: the hook is
    installed exactly while any is active, and each collection lands on
    the collecting thread's tracer."""
    import threading

    before = list(gc.callbacks)
    tracers = [Tracer() for _ in range(8)]
    errors = []

    def work(tr):
        try:
            for _ in range(20):
                with tr.activate():
                    with span("unit"):
                        gc.collect(0)
        except Exception as e:  # noqa: BLE001 — surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in tracers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and errors == []
    assert gc.callbacks == before
    # a collect() while another thread collects returns without one, so
    # only the total is bounded below
    assert sum(s.name == "gc" for tr in tracers for s in tr.spans()) >= 1
    for tr in tracers:
        units = [s for s in tr.spans() if s.name == "unit"]
        gcs = [s for s in tr.spans() if s.name == "gc"]
        assert len(units) == 20 and len(gcs) <= 20
        assert {s.tid for s in gcs} <= {s.tid for s in units}
        assert all(s.parent_id in {u.span_id for u in units} for s in gcs)
