"""Forward-invariant hoisting: ForwardPlan staging, assembly, and probes.

Four contracts of the hoisted hot path:

  * **staging is a permutation** — the weight streams the ForwardPlan
    gathers once into kernel (ELL / HD-chunk) layout carry exactly the
    per-layer gathered values: every real edge id appears exactly once
    across the concatenated streams, pad slots read the zero weight row,
    and each bucket's staged slab equals ``wg[b.eids]``;
  * **scatter-free assembly** — ``asm_index`` is an inverse count-sort
    permutation: gathering the concatenated bucket/HD reductions
    reproduces the scatter-based assembly bit for bit (and no row is
    both LD and HD);
  * **model parity** — hoisted == pre-hoist bit-exact in f32 through full
    forwards (grouped, fused, across ``num_layers`` in {1, 2, 4}), ref
    parity within fp32 tolerance, bf16 streams within a pinned bound;
  * **probe gate** (CI fast lane) — per forward: ``weight_gathers == 2``
    (was ``2 * num_layers``) and ``output_scatters <= 2`` (was
    ``num_segments`` per aggregation) on every groot backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gnn
from repro.kernels import ops
from repro.kernels.forward_plan import build_forward_plan
from repro.kernels.groot_spmm import (
    PROBE,
    apply_plan,
    apply_plan_grouped,
    build_plan,
    plan_cat_eids,
    reset_probe,
    stage_group_weights,
)
from tests.test_plan_properties import graph_from_degrees

GROOT_BACKENDS = ("groot", "groot_mxu", "groot_fused")

# Fig.-4-style mixture degree distributions (n, e_t, hd_frac, scale, seed)
MIXTURES = [
    (60, 512, 0.0, 1, 0),        # LD only
    (150, 64, 0.05, 1, 1),       # HD rows past a small threshold
    (90, 512, 0.03, 20, 2),      # deep LD buckets + HD rows
    (40, 16, 0.4, 1, 3),         # HD-heavy
]


def _mixture(case):
    n, e_t, hd_frac, scale, seed = case
    rng = np.random.default_rng(seed)
    src, dst = graph_from_degrees(rng, n, e_t, hd_frac, scale)
    return src, dst, n, e_t


# ---------------------------------------------------------------------------
# Staged weights are a permutation of the per-layer gathered weights
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", MIXTURES)
def test_staged_weights_are_permutation_of_per_layer_gather(case):
    src, dst, n, e_t = _mixture(case)
    e = len(src)
    plan = build_plan(src, dst, n, e_t=e_t)
    cat = plan_cat_eids(plan)
    # every real edge id exactly once; pad slots point at the zero row E
    real = np.sort(cat[cat < e])
    np.testing.assert_array_equal(real, np.arange(e))
    assert (cat[cat >= e] == e).all()

    rng = np.random.default_rng(7)
    wg = jnp.asarray(rng.standard_normal((e, 4)), jnp.float32)
    staged = stage_group_weights(plan, wg)
    wg_pad = np.concatenate([np.asarray(wg), np.zeros((1, 4), np.float32)])
    for b, slab in zip(plan.buckets, staged.buckets):
        np.testing.assert_array_equal(
            np.asarray(slab), wg_pad[np.minimum(b.eids, e)]
        )
    if plan.hd is not None:
        np.testing.assert_array_equal(
            np.asarray(staged.hd), wg_pad[np.minimum(plan.hd.eids, e)]
        )


@pytest.mark.parametrize("case", MIXTURES)
def test_assembly_index_is_inverse_count_sort(case):
    src, dst, n, e_t = _mixture(case)
    plan = build_plan(src, dst, n, e_t=e_t)
    assert plan.asm_index is not None and plan.asm_index.dtype == np.int32
    asm = plan.asm_index
    deg = np.bincount(dst, minlength=n)
    # simulate assembly of a concat whose row i holds value i; every
    # degree>0 row must land on its own unique concat slot, degree-0 rows
    # on the trailing zero row
    off = 0
    owner = np.full(plan.asm_rows, -1, dtype=np.int64)
    for b in plan.buckets:
        live = b.rows >= 0
        owner[off : off + int(live.sum())] = b.rows[live]
        off += b.rows.shape[0]
    if plan.hd is not None:
        owner[off : off + len(plan.hd.rows)] = plan.hd.rows
    for r in range(n):
        if deg[r] > 0:
            assert owner[asm[r]] == r
        else:
            assert asm[r] == plan.asm_rows - 1
    # LD and HD row sets are disjoint (the "no add needed" guarantee)
    if plan.hd is not None and plan.buckets:
        ld = np.concatenate([b.rows[b.rows >= 0] for b in plan.buckets])
        assert np.intersect1d(ld, plan.hd.rows).size == 0


@pytest.mark.parametrize("case", MIXTURES[:2])
def test_scatter_free_assembly_matches_scatter(case):
    """The grouped walk's gather assembly == the ungrouped walk's, to
    within f32 rounding of the same sums."""
    src, dst, n, e_t = _mixture(case)
    e = len(src)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(e), jnp.float32)
    plan = build_plan(src, dst, n, e_t=e_t)
    got = np.asarray(apply_plan(plan, x, w, interpret=True))
    wg = jnp.stack([w, 2.0 * w], axis=1)
    grouped = np.asarray(apply_plan_grouped(plan, x, wg, interpret=True))
    if plan.hd is None:
        # assembly is pure data movement, but the ungrouped walk weights
        # the messages before the LD kernel and the grouped one inside
        # it, so the compiler may contract multiply and add into an FMA
        # in one form and not the other (jax 0.9's CPU backend does):
        # the same sums then differ in the last bits.  A few f32 ulps of
        # the output scale bound that; an assembly fault misplaces whole
        # rows, orders of magnitude above it.
        ulps = 8 * np.finfo(np.float32).eps * np.abs(got).max()
        np.testing.assert_allclose(grouped[0], got, rtol=0, atol=ulps)
    else:
        # the grouped HD kernel reduces via matmul (different reduction
        # order than the ungrouped sum) — tolerance, not bits
        np.testing.assert_allclose(grouped[0], got, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Model-level: hoisted vs pre-hoist vs ref, f32 and bf16 streams
# ---------------------------------------------------------------------------

def _forward(params, x, s, d, inv, slot, n, agg, stream_dtype=None):
    return np.asarray(
        gnn.forward(
            params, x, s, d, inv, slot, num_nodes=n, agg=agg,
            stream_dtype=stream_dtype,
        )
    )


@pytest.mark.parametrize("num_layers", [1, 2, 4])
@pytest.mark.parametrize("backend", GROOT_BACKENDS)
def test_hoisted_parity_across_depths(backend, num_layers):
    src, dst, n, e_t = _mixture(MIXTURES[2])
    assert e_t == 512  # full-size threshold: the real kernel config
    e = len(src)
    rng = np.random.default_rng(9)
    cfg = gnn.GNNConfig(in_features=4, hidden=16, num_layers=num_layers)
    params = gnn.init_params(cfg, jax.random.key(1))
    x = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    inv = jnp.asarray(rng.integers(0, 2, e).astype(bool))
    slot = jnp.asarray(rng.integers(0, 2, e).astype(np.uint8))
    s, d = jnp.asarray(src), jnp.asarray(dst)

    pair = ops.make_agg_pair(src, dst, n, backend)
    assert pair.fwd_plan is not None
    want = _forward(params, x, s, d, inv, slot, n, None)
    hoisted = _forward(params, x, s, d, inv, slot, n, pair)
    prehoist = _forward(params, x, s, d, inv, slot, n, ops.unhoisted(pair))
    pergroup = _forward(params, x, s, d, inv, slot, n, ops.ungrouped(pair))

    # f32 hoisting is pure data movement: bit-exact with the pre-hoist walk
    np.testing.assert_array_equal(hoisted, prehoist)
    np.testing.assert_allclose(hoisted, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(pergroup, want, rtol=1e-4, atol=1e-4)

    # bf16 streams: pinned tolerance (weights+messages at 8-bit mantissa,
    # f32 accumulation in-kernel)
    bf16 = _forward(params, x, s, d, inv, slot, n, pair, stream_dtype="bfloat16")
    scale = np.maximum(np.abs(want), 1.0)
    assert np.max(np.abs(bf16 - want) / scale) < 0.05 * num_layers


# ---------------------------------------------------------------------------
# Probe gate (CI fast lane): the hoisting acceptance criterion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", GROOT_BACKENDS)
def test_probe_gate_weight_gathers_and_scatters(backend):
    src, dst, n, e_t = _mixture(MIXTURES[2])
    e = len(src)
    num_layers = 3
    rng = np.random.default_rng(11)
    cfg = gnn.GNNConfig(in_features=4, hidden=8, num_layers=num_layers)
    params = gnn.init_params(cfg, jax.random.key(2))
    x = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    inv = jnp.asarray(rng.integers(0, 2, e).astype(bool))
    slot = jnp.asarray(rng.integers(0, 2, e).astype(np.uint8))
    s, d = jnp.asarray(src), jnp.asarray(dst)
    pair = ops.make_agg_pair(src, dst, n, backend)

    reset_probe()
    jaxpr = jax.make_jaxpr(
        lambda xx, ii, ss: gnn.forward(
            params, xx, s, d, ii, ss, num_nodes=n, agg=pair
        )
    )(x, inv, slot)
    probe = dict(PROBE)
    # hoisted: the weight streams are staged once per direction per FORWARD
    assert probe["weight_gathers"] == 2
    assert probe["output_scatters"] <= 2
    # the measured form of the scatter gate: count scatter-add primitives
    # in the traced forward.  The only ones allowed are the two degree
    # segment-sums of the norm fold (one per direction) — output assembly
    # must contribute ZERO (pre-hoist it emitted num_segments per
    # aggregation per layer).
    assert str(jaxpr).count("scatter-add") <= 2
    assert probe["edge_stream_gathers"] == 2 * num_layers
    assert probe["stream_bytes"] > 0

    reset_probe()
    gnn.forward(params, x, s, d, inv, slot, num_nodes=n, agg=ops.unhoisted(pair))
    # pre-hoist walk re-stages per layer: the reduction being asserted
    assert PROBE["weight_gathers"] == 2 * num_layers
    reset_probe()


def test_grouped_walks_handle_zero_edge_graph():
    """An inputs-only partition (nodes, no edges) must keep the group
    dimension: assembly cannot infer G from an empty part list."""
    n, g = 5, 4
    plan = build_plan(np.zeros(0, np.int64), np.zeros(0, np.int64), n)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((n, 3)), jnp.float32)
    wg = jnp.zeros((0, g), jnp.float32)
    out = apply_plan_grouped(plan, x, wg, interpret=True)
    assert out.shape == (g, n, 3)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


# ---------------------------------------------------------------------------
# Satellites: int32 narrowing
# ---------------------------------------------------------------------------

def test_plan_indices_are_int32():
    src, dst, n, e_t = _mixture(MIXTURES[1])
    plan = build_plan(src, dst, n, e_t=e_t)
    for b in plan.buckets:
        assert b.cols.dtype == np.int32 and b.eids.dtype == np.int32
    if plan.hd is not None:
        assert plan.hd.cols.dtype == np.int32 and plan.hd.eids.dtype == np.int32
    fp = build_forward_plan(plan, build_plan(dst, src, n, e_t=e_t))
    assert fp.in_cat_eids.dtype == np.int32
    assert fp.out_cat_eids.dtype == np.int32


def test_partitioned_predictions_int32_end_to_end():
    from repro.core import aig as A
    from repro.core.features import groot_features
    from repro.core.partition import PARTITIONERS
    from repro.core.regrowth import extract_partitions
    from repro.exec.stream import stream_predict_partitioned

    d = A.csa_multiplier(8)
    g = d.to_edge_graph()
    feats = groot_features(d)
    cfg = gnn.GNNConfig(in_features=feats.shape[1], hidden=8, num_layers=2)
    params = gnn.init_params(cfg, jax.random.key(0))
    part = PARTITIONERS["multilevel"](g, 2, seed=0)
    subs = extract_partitions(g, part, regrow=True, hops=2)
    loop = gnn.predict_partitioned_loop(params, subs, feats, g.num_nodes, "ref")
    stream = stream_predict_partitioned(params, subs, feats, g.num_nodes, "ref")
    assert loop.dtype == np.int32
    assert stream.dtype == np.int32
    np.testing.assert_array_equal(loop, stream)
