"""Fused LD-aggregate + weight-matmul Pallas kernel (beyond-paper opt).

The GROOT paper stops at the SpMM; in GraphSAGE every aggregation is
immediately followed by a dense ``(N, F) @ (F, H)`` matmul.  Fusing the two
keeps the aggregated row block in VMEM and feeds it straight to the MXU —
the aggregated ``(R_t, F)`` tile is never written to HBM.  This removes
one full round-trip of the aggregate array per layer per group:

    unfused:  write (N,F) agg + read (N,F) agg  = 2*N*F*4 bytes per group
    fused:    0 bytes (lives in VMEM/VREG)

For the GNN's memory-bound regime (arithmetic intensity of the SpMM is
O(1) flops/byte) this is the dominant HBM-traffic term after the gather —
see EXPERIMENTS.md §Perf (GROOT kernel iterations).

Validated in interpret mode against ``ref.ell_block_reduce_ref @ W``;
compiled for a v5e by ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.groot_spmm import F_TILE, PROBE


def _fused_kernel(msgs_ref, w_ref, o_ref, *, rows: int, deg: int):
    """(R_t*d, F) tile + (F, H_t) weights -> (R_t, H_t) = rowsum @ W.

    Accumulation is always f32 (bf16 edge streams are widened in VREGs),
    matching the unfused LD kernel's numerics."""
    m = msgs_ref[...].astype(jnp.float32)
    agg = m.reshape(rows, deg, m.shape[-1]).sum(axis=1)
    o_ref[...] = jax.lax.dot(
        agg, w_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=o_ref.dtype,
    )


def fused_ld_matmul(
    msgs: jax.Array,
    w_mat: jax.Array,
    deg: int,
    rows_per_tile: int,
    *,
    interpret: bool,
    h_tile: int = F_TILE,
) -> jax.Array:
    """msgs: (R_pad * deg, F_pad); w_mat: (F_pad, H_pad) -> (R_pad, H_pad).

    Equivalent to ``ell_block_reduce(msgs) @ w_mat`` with the intermediate
    kept in VMEM.  F is carried whole per tile (GNN hidden <= 256 floats =
    1 KiB/row); H is tiled on the lane dim.
    """
    PROBE["pallas_calls"] += 1
    f_pad = msgs.shape[1]
    h_pad = w_mat.shape[1]
    r_pad = msgs.shape[0] // deg
    r_t = rows_per_tile
    h_t = min(h_tile, h_pad)
    grid = (r_pad // r_t, h_pad // h_t)
    return pl.pallas_call(
        functools.partial(_fused_kernel, rows=r_t, deg=deg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r_t * deg, f_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((f_pad, h_t), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((r_t, h_t), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r_pad, h_pad), jnp.float32),
        name="groot_ld_fused",
        interpret=interpret,
    )(msgs, w_mat)


def fused_ref(msgs: jax.Array, w_mat: jax.Array, deg: int) -> jax.Array:
    """Oracle: reshape-sum then matmul."""
    r = msgs.shape[0] // deg
    agg = msgs.reshape(r, deg, msgs.shape[1]).sum(axis=1)
    return agg @ w_mat


# ---------------------------------------------------------------------------
# Grouped fused kernel: all G slot x polarity groups of a SAGE layer in
# one pass.  The message tile is loaded once; per group it is weighted,
# segment-reduced, and matmul'd against that group's weight matrix, with
# the G partial (R_t, H_t) products summed in VREGs — the layer-level
# ``sum_g (agg_g @ W_g)`` never touches HBM between groups.
# ---------------------------------------------------------------------------

def _fused_kernel_grouped(msgs_ref, wg_ref, w_ref, o_ref, *, rows: int, deg: int,
                          groups: int):
    """(R_t*d, F) tile + (R_t*d, G) weights + (G, F, H_t) mats ->
    (R_t, H_t) = sum_g rowsum(wg[:, g] * msgs) @ W_g.

    Messages and weights may arrive as bf16 streams; the weighted
    reduction and the MXU products accumulate in f32."""
    m = msgs_ref[...].astype(jnp.float32)
    w = wg_ref[...].astype(jnp.float32)
    acc = None
    for g in range(groups):  # static, tiny (2 or 4): unrolls on the MXU
        agg = (m * w[:, g][:, None]).reshape(rows, deg, m.shape[-1]).sum(axis=1)
        part = jax.lax.dot(
            agg, w_ref[g],
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=o_ref.dtype,
        )
        acc = part if acc is None else acc + part
    o_ref[...] = acc


def fused_ld_matmul_grouped(
    msgs: jax.Array,
    wg: jax.Array,
    w_stack: jax.Array,
    deg: int,
    rows_per_tile: int,
    *,
    interpret: bool,
    h_tile: int = F_TILE,
) -> jax.Array:
    """msgs: (R_pad*deg, F_pad); wg: (R_pad*deg, G); w_stack: (G, F_pad, H_pad)
    -> (R_pad, H_pad) = sum_g ell_block_reduce(wg[:, g] * msgs) @ w_stack[g].
    """
    PROBE["pallas_calls"] += 1
    f_pad = msgs.shape[1]
    g, _, h_pad = w_stack.shape
    r_pad = msgs.shape[0] // deg
    r_t = rows_per_tile
    h_t = min(h_tile, h_pad)
    grid = (r_pad // r_t, h_pad // h_t)
    return pl.pallas_call(
        functools.partial(_fused_kernel_grouped, rows=r_t, deg=deg, groups=g),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r_t * deg, f_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((r_t * deg, g), lambda i, j: (i, 0)),
            pl.BlockSpec((g, f_pad, h_t), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((r_t, h_t), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r_pad, h_pad), jnp.float32),
        name="groot_ld_fused_grouped",
        interpret=interpret,
    )(msgs, wg.astype(msgs.dtype), w_stack.astype(jnp.float32))


def fused_grouped_ref(msgs: jax.Array, wg: jax.Array, w_stack: jax.Array,
                      deg: int) -> jax.Array:
    """Oracle: per-group weight, reshape-sum, matmul, sum over groups."""
    r = msgs.shape[0] // deg
    out = None
    for g in range(w_stack.shape[0]):
        agg = (msgs * wg[:, g][:, None]).reshape(r, deg, msgs.shape[1]).sum(axis=1)
        part = agg @ w_stack[g]
        out = part if out is None else out + part
    return out
