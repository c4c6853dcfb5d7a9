"""Streaming executor (repro.exec): plans, packing, bit-exactness, probes.

Fast lane (every push): a small ref-backend grid pins down

  * choose_k budget monotonicity and plan-cache identity,
  * schedule completeness (every partition packed exactly once),
  * bit-exact parity with the sequential ``predict_partitioned_loop``,
  * the compile-count probe: <= num_buckets jit compiles for ANY k,
  * scheduler auto-routing of oversized items.

Slow lane: the Pallas ``groot`` backend parity and a 256-bit CSA
(~530k nodes) streamed end to end under the memory model.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax

from repro.core import aig as A
from repro.core import gnn
from repro.core import pipeline as P
from repro.core.features import groot_features
from repro.core.partition import PARTITIONERS
from repro.core.regrowth import extract_partitions
from repro.exec import (
    StreamingExecutor,
    build_partition_plan,
    choose_k,
    choose_k_for_caps,
    plan_from_subgraphs,
)
from repro.exec.plan import _estimated_batch_bytes


@pytest.fixture(scope="module")
def rand_params():
    return gnn.init_params(gnn.GNNConfig(), jax.random.key(0))


@pytest.fixture(scope="module")
def csa12():
    d = A.csa_multiplier(12)
    return d.to_edge_graph(), groot_features(d)


def _subgraphs(g, k, partitioner="multilevel", regrow=True, seed=0):
    part = PARTITIONERS[partitioner](g, k, seed=seed)
    return extract_partitions(g, part, regrow=regrow)


# ---------------------------------------------------------------------------
# PartitionPlan / choose_k
# ---------------------------------------------------------------------------

def test_choose_k_monotone_and_fits_budget():
    cfg = gnn.GNNConfig()
    n, e = 100_000, 200_000
    full = P.memory_model_bytes(n, e, cfg)
    ks = [choose_k(n, e, cfg, budget) for budget in (full, full // 2, full // 8)]
    assert ks == sorted(ks)                       # tighter budget -> more parts
    for budget, k in zip((full, full // 2, full // 8), ks):
        if k < n:                                 # not capped
            assert _estimated_batch_bytes(
                n, e, k, cfg, 2, halo_frac=0.15, min_nodes=64, min_edges=128
            ) <= budget
    assert choose_k(0, 0, cfg, 1) == 1            # empty design


def test_choose_k_for_caps_respects_bucket_ceiling():
    k = choose_k_for_caps(100_000, 200_000, max_bucket_nodes=16384)
    assert k > 1
    n_part = int(np.ceil(100_000 / k * 1.15))
    from repro.kernels import ops

    n_pad, _ = ops.padded_shape(n_part, 1, min_nodes=64, min_edges=128)
    assert n_pad <= 16384


def test_build_partition_plan_is_content_cached(csa12):
    from repro.exec.plan import EXEC_PLAN_CACHE

    g, _ = csa12
    before = EXEC_PLAN_CACHE.snapshot()
    p1 = build_partition_plan(g, 4, seed=0)
    p2 = build_partition_plan(g, 4, seed=0)
    after = EXEC_PLAN_CACHE.snapshot()
    assert p1 is p2                               # same object, jit-friendly
    assert after.builds - before.builds <= 1      # built at most once
    p3 = build_partition_plan(g, 4, seed=1)       # different knobs -> new plan
    assert p3 is not p1


def test_plan_cache_distinguishes_edge_annotations(csa12):
    """Same connectivity, different inverter placement -> different plan.
    (graph_key hashes endpoints only; the exec-plan key must also cover
    edge_inv/edge_slot because Subgraphs embed their slices.)"""
    from repro.core.graph import EdgeGraph

    g, _ = csa12
    inv_a = np.zeros(g.num_edges, bool)
    inv_b = np.ones(g.num_edges, bool)
    ga = EdgeGraph(g.num_nodes, g.edge_src, g.edge_dst, inv_a, g.edge_slot)
    gb = EdgeGraph(g.num_nodes, g.edge_src, g.edge_dst, inv_b, g.edge_slot)
    pa = build_partition_plan(ga, 4, seed=0)
    pb = build_partition_plan(gb, 4, seed=0)
    assert pa is not pb
    assert pa.subgraphs[0].edge_inv is not None
    assert not pa.subgraphs[0].edge_inv.any()
    assert pb.subgraphs[0].edge_inv.all()


def test_empty_graph_pipeline_partitioned_request(rand_params):
    """A 0-node design with num_partitions > 1 must not crash the
    partitioned/streaming path (falls back to unpartitioned)."""
    from repro.core import aig as A

    design = A.AIG(
        name="empty",
        kind=np.zeros(0, np.int8),
        fanin0=np.zeros(0, np.int64),
        fanin1=np.zeros(0, np.int64),
        label=np.zeros(0, np.int8),
        n_pi=0,
        pos=np.zeros(0, np.int64),
    )
    cfg = P.PipelineConfig(dataset="csa", bits=4, num_partitions=4)
    prep = P.prepare(cfg, design)
    assert prep.subgraphs is None
    pred = P.infer(rand_params, prep)
    assert pred.shape == (0,)


def test_plan_schedule_covers_every_partition_once(csa12):
    g, _ = csa12
    plan = build_partition_plan(g, 8, seed=0)
    for capacity in (1, 2, 4):
        sched = plan.schedule(capacity)
        seen = [i for _, idxs in sched for i in idxs]
        assert sorted(seen) == list(range(plan.num_parts))
        for shape, idxs in sched:
            assert 0 < len(idxs) <= capacity
            for i in idxs:                        # same-bucket packing only
                assert plan.buckets[plan.bucket_of[i]] == shape


def test_plan_peak_batch_memory_scales_with_capacity(csa12):
    g, _ = csa12
    plan = build_partition_plan(g, 4, seed=0)
    cfg = gnn.GNNConfig()
    m1 = plan.peak_batch_memory_bytes(cfg, 1)
    m4 = plan.peak_batch_memory_bytes(cfg, 4)
    assert 0 < m1 < m4


# ---------------------------------------------------------------------------
# StreamingExecutor: parity + probes (ref backend, fast)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 4, 8])
def test_stream_matches_sequential_loop_bit_exact(rand_params, csa12, k):
    g, feats = csa12
    subs = _subgraphs(g, k)
    loop = gnn.predict_partitioned_loop(rand_params, subs, feats, g.num_nodes, "ref")
    ex = StreamingExecutor(rand_params, "ref", capacity=2, prefetch=1)
    out = ex.run_subgraphs(subs, feats, g.num_nodes)
    np.testing.assert_array_equal(out, loop)


@pytest.mark.parametrize("k", [2, 4, 8, 16])
def test_compile_probe_at_most_num_buckets_for_any_k(rand_params, csa12, k):
    """The acceptance criterion: one fresh executor, any partition count,
    shape-stable backend -> compiles <= number of distinct buckets."""
    g, feats = csa12
    plan = build_partition_plan(g, k, seed=0)
    ex = StreamingExecutor(rand_params, "ref", capacity=2)
    ex.run_plan(plan, feats)
    assert 0 < ex.stats.compiles <= plan.num_buckets
    assert ex.stats.partitions == plan.num_parts
    # re-running the same plan compiles nothing new
    before = ex.stats.compiles
    ex.run_plan(plan, feats)
    assert ex.stats.compiles == before


def test_shared_executor_across_k_grid_compiles_by_bucket(rand_params, csa12):
    g, feats = csa12
    ex = StreamingExecutor(rand_params, "ref", capacity=2)
    for k in (2, 4, 8):
        ex.run_plan(build_partition_plan(g, k, seed=0), feats)
    assert ex.stats.compiles <= len(ex.buckets_seen)


def test_prefetch_depths_agree(rand_params, csa12):
    g, feats = csa12
    subs = _subgraphs(g, 8)
    outs = []
    for prefetch in (0, 1, 3):
        ex = StreamingExecutor(rand_params, "ref", capacity=2, prefetch=prefetch)
        outs.append(ex.run_subgraphs(subs, feats, g.num_nodes))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_stream_stats_probe_counters(rand_params, csa12):
    g, feats = csa12
    plan = build_partition_plan(g, 4, seed=0)
    ex = StreamingExecutor(rand_params, "ref", capacity=2, prefetch=2)
    ex.run_plan(plan, feats)
    s = ex.stats
    assert s.runs == 1
    assert s.batches == s.launches == len(plan.schedule(2))
    assert s.partitions == plan.num_parts
    assert s.core_rows == g.num_nodes             # scatter is complete
    assert s.bytes_h2d > 0 and s.pack_s >= 0.0 and s.launch_s > 0.0


def test_prefetch_thread_error_propagates(rand_params, csa12):
    g, _ = csa12
    plan = build_partition_plan(g, 4, seed=0)
    bad_feats = np.zeros((3, 4), np.float32)      # too few rows: pack must fail
    ex = StreamingExecutor(rand_params, "ref", capacity=2, prefetch=1)
    with pytest.raises(Exception):
        ex.run_plan(plan, bad_feats)


def test_pipeline_budget_mode_partitions_to_fit(rand_params):
    full = P.memory_model_bytes(2110, 4124, gnn.GNNConfig())
    cfg = P.PipelineConfig(dataset="csa", bits=16, memory_budget_bytes=full // 3)
    r = P.run_pipeline(cfg, rand_params)
    assert r.exec_stats["chosen_k"] > 1            # the budget forced a cut
    # packed launches are strictly smaller than the full-graph figure (at
    # this tiny scale halo + pow-2 padding eat most of the 1/k win; the
    # 256-bit slow test asserts the real <50% criterion)
    assert r.exec_stats["peak_packed_memory_bytes"] < full
    assert r.exec_stats["compiles"] <= r.exec_stats["num_buckets"]


# ---------------------------------------------------------------------------
# Scheduler auto-routing (oversized items stream instead of rejecting)
# ---------------------------------------------------------------------------

def test_scheduler_streams_oversized_item_bit_exact(rand_params):
    from repro.service.bucketing import items_from_prepared
    from repro.service.scheduler import ShapeBucketScheduler

    prep = P.prepare(P.PipelineConfig(dataset="csa", bits=16))  # 2110 nodes
    items = items_from_prepared(7, prep)
    sched = ShapeBucketScheduler(rand_params, max_bucket_nodes=1024)
    out = sched.run_items(items)
    stats = sched.stats()
    assert stats.streamed_items == 1
    assert stats.compile_count <= len(stats.buckets)

    # replicate the scheduler's internal plan -> bit-exact oracle
    k = choose_k_for_caps(prep.num_nodes, prep.num_edges, 1024)
    assert k > 1
    subs = _subgraphs(prep.graph, k)
    ref = gnn.predict_partitioned_loop(
        rand_params, subs, prep.feats, prep.num_nodes, "ref"
    )
    np.testing.assert_array_equal(out[(7, 0)], ref)

    # small items keep taking the packed-bucket path
    small = items_from_prepared(8, P.prepare(P.PipelineConfig(dataset="csa", bits=6)))
    out2 = sched.run_items(small)
    assert sched.stats().streamed_items == 1      # unchanged
    assert out2[(8, 0)].shape[0] == small[0].num_nodes


# ---------------------------------------------------------------------------
# Slow lane: groot parity + large-design streaming
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_stream_matches_loop_bit_exact_groot(rand_params):
    d = A.csa_multiplier(8)
    g, feats = d.to_edge_graph(), groot_features(d)
    subs = _subgraphs(g, 2)
    loop = gnn.predict_partitioned_loop(rand_params, subs, feats, g.num_nodes, "groot")
    ex = StreamingExecutor(rand_params, "groot", capacity=2)
    out = ex.run_subgraphs(subs, feats, g.num_nodes)
    np.testing.assert_array_equal(out, loop)


@pytest.mark.slow
def test_large_design_streams_under_memory_model(rand_params):
    """256-bit CSA (~530k nodes) through the executor: scatter complete,
    compile probe bounded, peak packed launch < 50% of the full-graph
    memory model."""
    d = A.csa_multiplier(256)
    g, feats = d.to_edge_graph(), groot_features(d)
    plan = build_partition_plan(g, 16, partitioner="multilevel", seed=0)
    ex = StreamingExecutor(rand_params, "ref", capacity=2, prefetch=1)
    out = ex.run_plan(plan, feats)
    assert ex.stats.core_rows == g.num_nodes
    assert ex.stats.compiles <= plan.num_buckets
    cfg = gnn.GNNConfig()
    full = P.memory_model_bytes(g.num_nodes, g.num_edges, cfg)
    peak = plan.peak_batch_memory_bytes(cfg, ex.capacity)
    assert peak < 0.5 * full
    assert out.shape == (g.num_nodes,)
