"""Per-kernel allclose validation: Pallas (interpret=True) vs pure-jnp ref.

Sweeps shapes/dtypes per the brief; hypothesis (when installed) drives
the structural invariants of the degree-bucketing plan (every edge
covered exactly once, pow-2 padding bound).  Without hypothesis the
same properties run over a fixed seed grid instead, so the tier-1 suite
collects and passes in a bare environment.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core import aig as A
from repro.kernels import ops, ref
from repro.kernels.fused_sage import fused_ld_matmul, fused_ref
from repro.kernels.groot_spmm import apply_plan, build_plan


def random_graph(rng, n, e, hd_rows=0, hd_deg=1500):
    """Random COO graph; optionally a few extreme-degree rows (paper's
    polarized distribution)."""
    src = rng.integers(0, n, e, dtype=np.int64)
    dst = rng.integers(0, n, e, dtype=np.int64)
    if hd_rows:
        hsrc = rng.integers(0, n, hd_rows * hd_deg, dtype=np.int64)
        hdst = np.repeat(rng.choice(n, hd_rows, replace=False), hd_deg)
        src = np.concatenate([src, hsrc])
        dst = np.concatenate([dst, hdst])
    return src.astype(np.int32), dst.astype(np.int32)


TOL = {jnp.float32: 1e-5, jnp.bfloat16: 8e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "n,e,f,hd_rows",
    [
        (64, 256, 8, 0),
        (128, 512, 32, 0),
        (257, 2000, 100, 0),     # non-pow2 everything
        (300, 1024, 128, 2),     # HD rows (degree 1500 > E_T=512)
        (1000, 4000, 64, 1),
        (32, 0, 16, 0),          # empty edge set
    ],
)
@pytest.mark.parametrize("backend", ["groot", "groot_mxu"])
def test_spmm_matches_ref(n, e, f, hd_rows, dtype, backend):
    rng = np.random.default_rng(42 + n + e)
    src, dst = random_graph(rng, n, e, hd_rows)
    x = jnp.asarray(rng.standard_normal((n, f)), dtype)
    w = jnp.asarray(rng.standard_normal(len(src)), dtype)
    pair = ops.make_agg_pair(src, dst, n, backend)
    # Oracle in f32 over the bf16-rounded inputs: the kernels accumulate in
    # f32 regardless of input dtype, so the only tolerated error is the
    # per-product input quantisation (sqrt(deg)-scaled for bf16).
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    want = ref.spmm_ref(xf, jnp.asarray(src), jnp.asarray(dst), n, wf)
    deg_max = max(int(np.bincount(dst, minlength=n).max()), 1)
    tol = TOL[dtype] * np.sqrt(deg_max)
    got = pair.in_agg(x, w)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol
    )
    # unweighted path + fanout direction
    got_out = pair.out_agg(x, None)
    want_out = ref.spmm_ref(xf, jnp.asarray(dst), jnp.asarray(src), n, None)
    deg_max_o = max(int(np.bincount(src, minlength=n).max()), 1)
    tol_o = TOL[dtype] * np.sqrt(deg_max_o)
    np.testing.assert_allclose(
        np.asarray(got_out, np.float32), np.asarray(want_out), rtol=tol_o, atol=tol_o
    )


@pytest.mark.parametrize("f,h", [(4, 32), (32, 32), (100, 60), (128, 256)])
def test_fused_agg_matmul_matches_ref(f, h):
    rng = np.random.default_rng(0)
    n, e = 200, 900
    src, dst = random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(e), jnp.float32)
    w_mat = jnp.asarray(rng.standard_normal((f, h)), jnp.float32)
    pair = ops.make_agg_pair(src, dst, n, "groot_fused")
    want = ref.spmm_ref(x, jnp.asarray(src), jnp.asarray(dst), n, w) @ w_mat
    got = pair.in_agg_mm(x, w, w_mat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_fused_kernel_body():
    rng = np.random.default_rng(1)
    deg, r, f, h = 4, 64, 128, 128
    msgs = jnp.asarray(rng.standard_normal((r * deg, f)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((f, h)), jnp.float32)
    got = fused_ld_matmul(msgs, w, deg, rows_per_tile=16, interpret=True)
    want = fused_ref(msgs, w, deg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_onehot_backend_matches_ref():
    rng = np.random.default_rng(3)
    n, e, f = 60, 200, 16
    src, dst = random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(e), jnp.float32)
    pair = ops.make_agg_pair(src, dst, n, "onehot")
    want = ref.spmm_ref(x, jnp.asarray(src), jnp.asarray(dst), n, w)
    np.testing.assert_allclose(
        np.asarray(pair.in_agg(x, w)), np.asarray(want), rtol=1e-4, atol=1e-4
    )


def test_ref_matches_dense_oracle():
    rng = np.random.default_rng(4)
    n, e, f = 40, 150, 8
    src, dst = random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(e), jnp.float32)
    a = ref.spmm_ref(x, jnp.asarray(src), jnp.asarray(dst), n, w)
    b = ref.spmm_dense_ref(x, jnp.asarray(src), jnp.asarray(dst), n, w)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_spmm_on_real_aig():
    """The actual workload: a multiplier AIG's fanout direction has the
    polarized degree distribution (PIs feed O(bits) partial products)."""
    aig = A.csa_multiplier(16)
    g = aig.to_edge_graph()
    deg_out = np.bincount(g.edge_src, minlength=g.num_nodes)
    assert deg_out.max() >= 16  # high-fanout PIs exist
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((g.num_nodes, 32)), jnp.float32)
    for direction in ("in", "out"):
        s, d = (g.edge_src, g.edge_dst) if direction == "in" else (g.edge_dst, g.edge_src)
        pair = ops.make_agg_pair(s, d, g.num_nodes, "groot")
        want = ref.spmm_ref(x, jnp.asarray(s), jnp.asarray(d), g.num_nodes, None)
        np.testing.assert_allclose(
            np.asarray(pair.in_agg(x, None)), np.asarray(want), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# Plan invariants (property-based)
# ---------------------------------------------------------------------------

def _check_plan_covers_every_edge_exactly_once(n, e, seed):
    rng = np.random.default_rng(seed)
    src, dst = random_graph(rng, n, e)
    plan = build_plan(src, dst, n)
    seen = np.concatenate(
        [b.eids for b in plan.buckets]
        + ([plan.hd.eids] if plan.hd is not None else [np.zeros(0, np.int32)])
    )
    real = seen[seen < e]
    assert sorted(real.tolist()) == list(range(e))
    # row sets are disjoint and complete over rows with degree >= 1
    rows = np.concatenate(
        [b.rows[b.rows >= 0] for b in plan.buckets]
        + ([plan.hd.rows] if plan.hd is not None else [np.zeros(0, np.int32)])
    )
    deg = np.bincount(dst, minlength=n)
    assert len(set(rows.tolist())) == len(rows)
    assert set(rows.tolist()) == set(np.where(deg > 0)[0].tolist())


def _check_spmm_property_random(n, e, f, seed):
    rng = np.random.default_rng(seed)
    src, dst = random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(e), jnp.float32)
    plan = build_plan(src, dst, n)
    got = apply_plan(plan, x, w, interpret=True)
    want = ref.spmm_ref(x, jnp.asarray(src), jnp.asarray(dst), n, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


if HAVE_HYPOTHESIS:

    @hypothesis.given(
        n=st.integers(2, 120),
        e=st.integers(0, 600),
        seed=st.integers(0, 2**31 - 1),
    )
    @hypothesis.settings(max_examples=40, deadline=None)
    def test_plan_covers_every_edge_exactly_once(n, e, seed):
        _check_plan_covers_every_edge_exactly_once(n, e, seed)

    @hypothesis.given(
        n=st.integers(4, 80),
        e=st.integers(1, 400),
        f=st.sampled_from([1, 3, 8, 33]),
        seed=st.integers(0, 2**31 - 1),
    )
    @hypothesis.settings(max_examples=25, deadline=None)
    def test_spmm_property_random(n, e, f, seed):
        _check_spmm_property_random(n, e, f, seed)

else:
    # fallback strategy: a fixed grid covering the same corners (empty edge
    # sets, e < n, e >> n, non-pow2 sizes) with varied seeds
    _PLAN_CASES = [
        (2, 0, 0), (5, 3, 1), (16, 64, 2), (33, 200, 3),
        (64, 600, 4), (97, 96, 5), (120, 377, 6), (50, 1, 7),
    ]
    _SPMM_CASES = [
        (4, 1, 1, 0), (17, 33, 3, 1), (40, 150, 8, 2), (80, 400, 33, 3),
        (64, 64, 8, 4), (33, 100, 1, 5), (79, 399, 3, 6),
    ]

    @pytest.mark.parametrize("n,e,seed", _PLAN_CASES)
    def test_plan_covers_every_edge_exactly_once(n, e, seed):
        _check_plan_covers_every_edge_exactly_once(n, e, seed)

    @pytest.mark.parametrize("n,e,f,seed", _SPMM_CASES)
    def test_spmm_property_random(n, e, f, seed):
        _check_spmm_property_random(n, e, f, seed)


def test_padding_overhead_bounded():
    """pow-2 bucketing pads <= 2x + tile rounding on the real workload."""
    aig = A.csa_multiplier(32)
    g = aig.to_edge_graph()
    plan = build_plan(g.edge_src, g.edge_dst, g.num_nodes)
    # AIG in-degrees are 1 or 2 -> buckets are nearly exact
    assert plan.padding_overhead() < 2.5
    plan_out = build_plan(g.edge_dst, g.edge_src, g.num_nodes)
    assert plan_out.padding_overhead() < 4.0  # fanout is more ragged
