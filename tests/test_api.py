"""`repro.api.Session` façade: the execution router, the flattened
config (and its legacy projections), the deprecated shims, and the
committed API-surface snapshot.

Fast lane: router decisions + parity on small random-param designs, the
config alias/override semantics, shim DeprecationWarnings, the
plan/compile probe gates, and the ``__all__`` manifest check.  Slow
lane: trained-model golden parity across routes and the csa-256 routing
acceptance criterion.
"""
from __future__ import annotations

import dataclasses
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.api as api
from repro.api import Session, SessionConfig
from repro.core import gnn
from repro.core import pipeline as P
from repro.kernels.plan_cache import PLAN_CACHE

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def rand_params():
    return gnn.init_params(gnn.GNNConfig(), jax.random.key(0))


# ---------------------------------------------------------------------------
# API-surface snapshot (accidental public-surface changes fail the build)
# ---------------------------------------------------------------------------

def test_api_surface_matches_committed_manifest():
    manifest = sorted(
        line.strip()
        for line in (DATA / "api_surface.txt").read_text().splitlines()
        if line.strip()
    )
    assert sorted(api.__all__) == manifest, (
        "repro.api public surface changed — if intentional, update "
        "tests/data/api_surface.txt in the same PR"
    )
    for name in manifest:
        assert getattr(api, name) is not None


# ---------------------------------------------------------------------------
# Config unification: backend= everywhere, aggregate= as deprecated alias
# ---------------------------------------------------------------------------

def test_pipeline_config_backend_alias():
    assert P.PipelineConfig().backend == "ref"
    with pytest.warns(DeprecationWarning, match="aggregate"):
        cfg = P.PipelineConfig(aggregate="groot")
    assert cfg.backend == "groot"
    assert cfg.aggregate is None          # write-only alias, consumed
    # the alias being consumed is what keeps replace(backend=...) safe
    assert dataclasses.replace(cfg, backend="groot_fused").backend == "groot_fused"
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="disagree"):
            P.PipelineConfig(backend="ref", aggregate="groot")


def test_session_config_alias_and_projections():
    with pytest.warns(DeprecationWarning, match="aggregate"):
        cfg = SessionConfig(aggregate="groot_mxu")
    assert cfg.backend == "groot_mxu" and cfg.aggregate is None
    assert cfg.replace(backend="ref").backend == "ref"
    svc = SessionConfig(backend="groot", stream_dtype="bfloat16").service_config()
    assert svc.backend == "groot" and svc.stream_dtype == "bfloat16"
    # stream_dtype changes numerics, so it must key the service cache
    assert "bfloat16" in svc.cache_key_part()


def test_pipeline_config_roundtrip_is_exact():
    pcfg = P.PipelineConfig(
        dataset="booth", bits=12, batch=2, num_partitions=4, regrow=False,
        regrow_hops=3, partitioner="multilevel", backend="groot_fused",
        seed=7, memory_budget_bytes=12345, stream_capacity=3,
        stream_prefetch=2, stream_dtype="bfloat16",
    )
    lifted = SessionConfig.from_pipeline(pcfg)
    assert lifted.pipeline_config() == pcfg


def test_service_overrides_apply_on_top_of_config(rand_params):
    """Both ``config`` and ``**overrides`` given: overrides win (via
    dataclasses.replace), untouched fields come from the config."""
    from repro.service.server import ServiceConfig, VerificationService

    base = ServiceConfig(backend="ref", capacity=2, num_partitions=1)
    with pytest.warns(DeprecationWarning, match="Session"):
        svc = VerificationService(
            rand_params, base, num_partitions=3, capacity=4
        )
    try:
        assert svc.config.num_partitions == 3
        assert svc.config.capacity == 4
        assert svc.config.backend == "ref"       # inherited from base
        assert base.num_partitions == 1          # base config untouched
    finally:
        svc.close(timeout=30.0)


def test_session_overrides_apply_on_top_of_config(rand_params):
    base = SessionConfig(backend="ref", num_partitions=1)
    sess = Session(rand_params, base, num_partitions=4, bits=8)
    assert sess.config.num_partitions == 4
    assert sess.config.bits == 8
    assert sess.config.backend == "ref"


# ---------------------------------------------------------------------------
# The execution router
# ---------------------------------------------------------------------------

def test_router_full_route_and_full_parity(rand_params):
    sess = Session(rand_params, SessionConfig(dataset="csa", bits=8))
    d = sess.explain()
    assert d.mode == "full" and d.k == 1
    assert d.modeled_peak_bytes == d.modeled_full_bytes
    r = sess.verify(verify=False, return_predictions=True, use_cache=False)
    assert r.routing == d                  # explain() matches the route taken
    assert r.exec_stats == {}
    prep = sess.prepare()
    np.testing.assert_array_equal(
        r.predictions, gnn.predict(rand_params, prep.graph, prep.feats, "ref")
    )


def test_router_streamed_and_partitioned_routes_agree(rand_params):
    sess = Session(rand_params, SessionConfig(dataset="csa", bits=8,
                                              num_partitions=4))
    d = sess.explain()
    assert d.mode == "streamed" and d.k == 4 and d.num_buckets >= 1
    assert d.buckets and d.modeled_peak_bytes > 0
    r = sess.verify(verify=False, return_predictions=True, use_cache=False)
    assert r.routing == d
    assert r.exec_stats["num_buckets"] == d.num_buckets
    assert r.exec_stats["launches"] >= 1

    loop = sess.options(streaming=False)
    dl = loop.explain()
    assert dl.mode == "partitioned" and dl.k == 4 and dl.num_buckets == 0
    rl = loop.verify(verify=False, return_predictions=True, use_cache=False)
    assert rl.routing == dl and rl.exec_stats == {}
    # streamed and sequential routes are bit-exact on every row
    np.testing.assert_array_equal(r.predictions, rl.predictions)


def test_router_memory_budget_streams_to_fit(rand_params):
    sess = Session(rand_params, SessionConfig(dataset="csa", bits=16))
    full = sess.explain().modeled_full_bytes
    tight = sess.options(memory_budget_bytes=full // 3)
    d = tight.explain()
    assert d.mode == "streamed" and d.k > 1
    assert "choose_k" in d.reason
    r = tight.verify(verify=False, use_cache=False)
    assert r.routing == d
    assert r.exec_stats["chosen_k"] == d.k
    assert r.exec_stats["peak_packed_memory_bytes"] == d.modeled_peak_bytes


def test_unmeetable_budget_stops_refining(rand_params):
    """Re-grown by num_layers hops, every partition of a CSA multiplier
    keeps most of the design, so no cut meets half the full-graph model.
    The re-split search must stop once a finer cut stops shrinking the
    packed peak; it used to double k up to one partition per node,
    re-cutting at every step, which never returns at csa:512."""
    sess = Session(rand_params, SessionConfig(
        dataset="csa", bits=32, regrow_hops=4
    ))
    full = sess.explain().modeled_full_bytes
    tight = sess.options(memory_budget_bytes=full // 2)
    d = tight.explain()
    assert d.mode == "streamed" and 1 < d.k <= 64
    assert d.modeled_peak_bytes > full // 2       # streams at the best cut


def test_repeated_verify_builds_zero_plans_zero_compiles(rand_params):
    """Same-structure designs through a session: the second run touches
    neither the structural plan cache (0 builds) nor jit (0 compiles)."""
    sess = Session(rand_params, SessionConfig(
        dataset="csa", bits=8, num_partitions=2, backend="groot"
    ))
    sess.verify(verify=False, use_cache=False)
    ex = sess._stream_executor()
    compiles_before = ex.runner.compile_count
    pc_before = PLAN_CACHE.snapshot()
    r2 = sess.verify(verify=False, use_cache=False)
    assert r2.plan_cache["builds"] == 0
    assert r2.plan_cache["hits"] >= 1
    assert PLAN_CACHE.snapshot().builds == pc_before.builds
    assert ex.runner.compile_count == compiles_before
    # and with the result LRU on, the third call skips execution entirely
    r3 = sess.verify(verify=False)
    assert r3.cached
    assert r3.accuracy == r2.accuracy
    # mutating a returned result must not corrupt the cached copy
    r3.exec_stats["launches"] = -1
    r3.plan_cache["builds"] = 999
    r4 = sess.verify(verify=False)
    assert r4.cached and r4.exec_stats.get("launches") != -1
    assert r4.plan_cache["builds"] == 0
    # asking for predictions cannot be served from the predictions-free
    # cache: it falls through to a real run
    r5 = sess.verify(verify=False, return_predictions=True)
    assert not r5.cached and r5.predictions is not None


def test_explain_needs_no_params_but_verify_does():
    sess = Session(config=SessionConfig(dataset="csa", bits=6))
    assert sess.explain().mode == "full"          # host-side only
    with pytest.raises(RuntimeError, match="params"):
        sess.verify()


def test_train_invalidates_params_derived_state(rand_params):
    """New params must never serve results cached under the old ones —
    the LRU key carries no params fingerprint, so train()/set_params()
    invalidate it (and drop the stale service engine) wholesale."""
    sess = Session(rand_params, SessionConfig(dataset="csa", bits=6))
    r1 = sess.verify(verify=False)
    assert not r1.cached and sess.verify(verify=False).cached
    sess.train("csa", 6, epochs=40)
    r2 = sess.verify(verify=False)
    assert not r2.cached                 # the old cache entry is gone, so
    assert sess._service is None         # the run used the NEW params
    assert sess.verify(verify=False).cached   # and re-caches under them


def test_closed_session_rejects_async_but_not_sync(rand_params):
    sess = Session(rand_params, SessionConfig(dataset="csa", bits=6))
    sess.close()
    # a resurrected engine would leak threads and not know old tickets
    with pytest.raises(RuntimeError, match="closed"):
        sess.poll(0)
    with pytest.raises(RuntimeError, match="closed"):
        sess.submit()
    assert sess.verify(verify=False, use_cache=False).routing.mode == "full"


# ---------------------------------------------------------------------------
# Deprecated entry points: still correct, now warning
# ---------------------------------------------------------------------------

def test_run_pipeline_shim_warns_and_matches_session(rand_params):
    sess = Session(rand_params, SessionConfig(dataset="csa", bits=8,
                                              num_partitions=2))
    r_new = sess.verify(verify=False, use_cache=False)
    with pytest.warns(DeprecationWarning, match="run_pipeline"):
        r_old = P.run_pipeline(
            P.PipelineConfig(dataset="csa", bits=8, num_partitions=2),
            rand_params,
        )
    assert r_old.accuracy == r_new.accuracy
    assert r_old.num_nodes == r_new.num_nodes
    assert r_old.peak_memory_bytes == r_new.peak_memory_bytes
    assert r_old.exec_stats["num_buckets"] == r_new.exec_stats["num_buckets"]


def test_predict_partitioned_shim_warns_and_is_bit_exact(rand_params):
    from repro.exec.stream import stream_predict_partitioned

    prep = P.prepare(P.PipelineConfig(dataset="csa", bits=8, num_partitions=3))
    with pytest.warns(DeprecationWarning, match="predict_partitioned"):
        old = gnn.predict_partitioned(
            rand_params, prep.subgraphs, prep.feats, prep.num_nodes, "ref"
        )
    new = stream_predict_partitioned(
        rand_params, prep.subgraphs, prep.feats, prep.num_nodes, "ref"
    )
    np.testing.assert_array_equal(old, new)


# ---------------------------------------------------------------------------
# Slow lane: trained-model golden parity + csa-256 routing acceptance
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained_params_8b():
    params, _ = P.train_model("csa", 8, epochs=200)
    return params


@pytest.mark.slow
def test_session_golden_parity_across_routes(trained_params_8b):
    """``regrow_hops >= num_layers`` completes the receptive field, so all
    three sync routes must be BIT-EXACT — and every groot backend must
    agree with ref on the verdict."""
    base = Session(trained_params_8b, SessionConfig(
        dataset="csa", bits=10, regrow_hops=4
    ))
    full = base.verify(return_predictions=True, use_cache=False)
    assert full.verdict is not None
    routes = {
        "streamed": base.options(num_partitions=4),
        "partitioned": base.options(num_partitions=4, streaming=False),
    }
    for name, sess in routes.items():
        r = sess.verify(return_predictions=True, use_cache=False)
        assert r.routing.mode == name
        np.testing.assert_array_equal(r.predictions, full.predictions,
                                      err_msg=name)
        assert r.verdict.status == full.verdict.status
    for backend in ("groot", "groot_fused"):
        r = base.options(backend=backend, num_partitions=4).verify(
            use_cache=False
        )
        assert r.verdict.status == full.verdict.status, backend
        assert r.accuracy == pytest.approx(full.accuracy, abs=1e-12), backend


@pytest.mark.slow
def test_csa256_routes_streamed_under_budget_full_without(rand_params):
    """Acceptance: the same csa-256 design goes to the streaming executor
    under a tight memory budget and to full-graph execution without one,
    with matching accuracy."""
    sess = Session(rand_params, SessionConfig(dataset="csa", bits=256))
    d_full = sess.explain()
    assert d_full.mode == "full"
    r_full = sess.verify(verify=False, use_cache=False)
    assert r_full.routing.mode == "full"

    budget = d_full.modeled_full_bytes // 2
    tight = sess.options(memory_budget_bytes=budget)
    d = tight.explain()
    assert d.mode == "streamed" and d.k > 1
    assert d.modeled_peak_bytes <= budget       # prepare() validated the fit
    r = tight.verify(verify=False, use_cache=False)
    assert r.routing == d
    assert r.exec_stats["launches"] >= 1
    assert r.exec_stats["peak_packed_memory_bytes"] <= budget
    assert abs(r.accuracy - r_full.accuracy) < 0.005


@pytest.mark.slow
def test_session_async_path_matches_sync(trained_params_8b):
    """submit()/poll()/result() (the service-batched route) agrees with
    the sync router on the same design."""
    with Session(trained_params_8b, SessionConfig(
        dataset="csa", bits=12, num_partitions=2
    )) as sess:
        r_sync = sess.verify(use_cache=False)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            ticket = sess.submit()       # façade path must NOT warn
        r_async = sess.result(ticket, timeout=300)
    assert r_async.status == r_sync.status
    assert r_async.accuracy == pytest.approx(r_sync.accuracy, abs=1e-12)
    assert r_async.num_nodes == r_sync.num_nodes
