"""Grouped multi-polarity SpMM: kernel- and model-level parity.

Two gaps this file closes:

  * the grouped kernels (one gather, one plan walk, all G weight columns
    reduced per pass) must match the per-group kernels bit-for-bit in
    intent — within fp32 tolerance — on both the LD and the HD path;
  * backend parity through a FULL forward pass on graphs whose fanout
    rows exceed ``E_T = 512`` — the HD accumulation path — plus the
    paper's Fig. 4 polarized LD+HD mixture.  The pre-existing tests only
    drove HD through bare SpMM calls, never through the SAGE layer.

Also asserts the hot-path contract the refactor exists for: <= 2
edge-stream gathers and <= 2 bucket-kernel walks per layer grouped,
vs 6 on the per-group path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gnn
from repro.kernels import ops, ref
from repro.kernels.fused_sage import fused_grouped_ref, fused_ld_matmul_grouped
from repro.kernels.groot_spmm import (
    PROBE,
    apply_plan,
    apply_plan_grouped,
    build_plan,
    reset_probe,
)

GROOT_BACKENDS = ("groot", "groot_mxu", "groot_fused")


def polarized_graph(rng, n, e_ld, hd_rows, hd_deg):
    """Fig. 4 shape: a sea of low-degree rows + a few extreme-fanout rows."""
    src = rng.integers(0, n, e_ld, dtype=np.int64)
    dst = rng.integers(0, n, e_ld, dtype=np.int64)
    if hd_rows:
        hsrc = rng.integers(0, n, hd_rows * hd_deg, dtype=np.int64)
        hdst = np.repeat(rng.choice(n, hd_rows, replace=False), hd_deg)
        src = np.concatenate([src, hsrc])
        dst = np.concatenate([dst, hdst])
    return src.astype(np.int32), dst.astype(np.int32)


# ---------------------------------------------------------------------------
# Kernel level: grouped == stacked per-group
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mxu", [False, True])
@pytest.mark.parametrize(
    "n,e_ld,hd_rows,f,g",
    [
        (64, 256, 0, 8, 4),          # LD only
        (120, 500, 0, 33, 2),        # non-pow2 F, G=2 (fanout polarity)
        (300, 900, 2, 17, 4),        # HD rows (deg 600 > E_T)
        (32, 0, 1, 16, 4),           # HD only, no LD edges
    ],
)
def test_apply_plan_grouped_matches_per_group(n, e_ld, hd_rows, f, g, mxu):
    rng = np.random.default_rng(7 + n)
    src, dst = polarized_graph(rng, n, e_ld, hd_rows, hd_deg=600)
    e = len(src)
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((e, g)), jnp.float32)
    plan = build_plan(src, dst, n)
    got = apply_plan_grouped(plan, x, wg, interpret=True, mxu=mxu)
    assert got.shape == (g, n, f) and got.dtype == x.dtype
    for k in range(g):
        want = apply_plan(plan, x, wg[:, k], interpret=True, mxu=mxu)
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want), rtol=1e-4, atol=1e-4
        )


def test_apply_plan_grouped_bf16_accumulates_f32():
    rng = np.random.default_rng(11)
    src, dst = polarized_graph(rng, 200, 800, 1, 600)
    x = jnp.asarray(rng.standard_normal((200, 32)), jnp.bfloat16)
    wg = jnp.asarray(rng.standard_normal((len(src), 4)), jnp.float32)
    plan = build_plan(src, dst, 200)
    got = apply_plan_grouped(plan, x, wg, interpret=True)
    assert got.dtype == jnp.bfloat16
    xf = x.astype(jnp.float32)
    deg_max = int(np.bincount(dst, minlength=200).max())
    tol = 8e-2 * np.sqrt(deg_max)
    for k in range(4):
        want = ref.spmm_ref(xf, jnp.asarray(src), jnp.asarray(dst), 200, wg[:, k])
        np.testing.assert_allclose(
            np.asarray(got[k], np.float32), np.asarray(want), rtol=tol, atol=tol
        )


def test_fused_grouped_kernel_matches_ref():
    rng = np.random.default_rng(2)
    deg, r, f, h, g = 4, 64, 128, 128, 4
    msgs = jnp.asarray(rng.standard_normal((r * deg, f)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((r * deg, g)), jnp.float32)
    w_stack = jnp.asarray(rng.standard_normal((g, f, h)), jnp.float32)
    got = fused_ld_matmul_grouped(
        msgs, wg, w_stack, deg, rows_per_tile=16, interpret=True
    )
    want = fused_grouped_ref(msgs, wg, w_stack, deg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Model level: every backend, grouped and per-group, through graphs that
# force the HD accumulation path inside a full forward pass
# ---------------------------------------------------------------------------

def _forward_all_backends(n, src, dst, seed=0, num_layers=2, hidden=16,
                          per_group=False):
    rng = np.random.default_rng(seed)
    e = len(src)
    cfg = gnn.GNNConfig(in_features=4, hidden=hidden, num_layers=num_layers)
    params = gnn.init_params(cfg, jax.random.key(seed))
    x = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    inv = jnp.asarray(rng.integers(0, 2, e).astype(bool))
    slot = jnp.asarray(rng.integers(0, 2, e).astype(np.uint8))
    s, d = jnp.asarray(src), jnp.asarray(dst)

    outs = {"ref": gnn.forward(params, x, s, d, inv, slot, num_nodes=n, agg=None)}
    outs["onehot"] = gnn.forward(
        params, x, s, d, inv, slot, num_nodes=n,
        agg=ops.make_agg_pair(src, dst, n, "onehot"),
    )
    for backend in GROOT_BACKENDS:
        pair = ops.make_agg_pair(src, dst, n, backend)
        assert pair.in_agg_grouped is not None
        outs[backend] = gnn.forward(
            params, x, s, d, inv, slot, num_nodes=n, agg=pair
        )
        if per_group:
            outs[backend + "/per-group"] = gnn.forward(
                params, x, s, d, inv, slot, num_nodes=n, agg=ops.ungrouped(pair)
            )
    return outs


def _assert_parity(outs, tol=1e-4):
    want = np.asarray(outs["ref"])
    for name, got in outs.items():
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=tol, atol=tol,
            err_msg=f"backend {name} diverges from ref",
        )


def test_forward_parity_hd_fanout():
    """Rows with fanout degree > E_T — the HD path — inside the layer."""
    rng = np.random.default_rng(3)
    src, dst = polarized_graph(rng, 300, 800, hd_rows=2, hd_deg=600)
    # the fanout direction aggregates over edge_src: HD rows live there too
    _assert_parity(_forward_all_backends(300, src, dst))


def test_forward_parity_polarized_mixture():
    """Fig. 4 mixture: deep LD buckets AND multiple HD rows at once."""
    rng = np.random.default_rng(4)
    src, dst = polarized_graph(rng, 400, 1500, hd_rows=2, hd_deg=530)
    # sprinkle mid-degree rows so several LD buckets are populated
    mid_dst = np.repeat(rng.choice(400, 6, replace=False), 40).astype(np.int32)
    mid_src = rng.integers(0, 400, mid_dst.size).astype(np.int32)
    src = np.concatenate([src, mid_src])
    dst = np.concatenate([dst, mid_dst])
    # per-group variants included here: grouped == per-group == ref through
    # the full layer stack on the richest degree mixture
    _assert_parity(_forward_all_backends(400, src, dst, seed=5, per_group=True))


def test_forward_parity_no_polarity_annotations():
    """edge_inv/edge_slot = None collapses groups; grouped must agree."""
    rng = np.random.default_rng(6)
    src, dst = polarized_graph(rng, 128, 512, 1, 600)
    n, e = 128, len(src)
    cfg = gnn.GNNConfig(in_features=4, hidden=8, num_layers=2)
    params = gnn.init_params(cfg, jax.random.key(1))
    x = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    s, d = jnp.asarray(src), jnp.asarray(dst)
    want = gnn.forward(params, x, s, d, None, None, num_nodes=n, agg=None)
    for backend in GROOT_BACKENDS:
        pair = ops.make_agg_pair(src, dst, n, backend)
        got = gnn.forward(params, x, s, d, None, None, num_nodes=n, agg=pair)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )


# ---------------------------------------------------------------------------
# Hot-path probe: the 6 -> 2 contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", GROOT_BACKENDS)
def test_grouped_hot_path_probe(backend):
    rng = np.random.default_rng(8)
    n, num_layers = 200, 2
    src, dst = polarized_graph(rng, n, 400, 1, 600)
    e = len(src)
    cfg = gnn.GNNConfig(in_features=4, hidden=8, num_layers=num_layers)
    params = gnn.init_params(cfg, jax.random.key(0))
    x = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    inv = jnp.asarray(rng.integers(0, 2, e).astype(bool))
    slot = jnp.asarray(rng.integers(0, 2, e).astype(np.uint8))
    s, d = jnp.asarray(src), jnp.asarray(dst)
    pair = ops.make_agg_pair(src, dst, n, backend)

    reset_probe()
    gnn.forward(params, x, s, d, inv, slot, num_nodes=n, agg=pair)
    assert PROBE["edge_stream_gathers"] == 2 * num_layers
    assert PROBE["kernel_walks"] == 2 * num_layers

    reset_probe()
    gnn.forward(params, x, s, d, inv, slot, num_nodes=n, agg=ops.ungrouped(pair))
    assert PROBE["edge_stream_gathers"] == 6 * num_layers
    assert PROBE["kernel_walks"] == 6 * num_layers
    reset_probe()
