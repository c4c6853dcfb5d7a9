"""Pallas flash attention (TPU target) — the kernel form of the lax-flash
schedule in ``repro.zoo.models.attention``.

The roofline analysis (EXPERIMENTS.md §Roofline) shows the dominant
memory-term contributor for every attention arch is the score stream the
lax schedule materialises between loop steps (e.g. ~3.7 TB/device of the
qwen3-8b train traffic).  This kernel keeps scores, the running max and
the denominator in VMEM scratch across the kv-block grid dimension —
exactly the classic flash-attention tiling, expressed as:

    grid = (B*KV*G, n_q_blocks, n_kv_blocks)   (kv innermost)
    q block   (1, qc, hd)   revisited across kv blocks
    k/v block (1, kc, hd)
    scratch   acc (qc, hd) f32, m (qc, 1) f32, l (qc, 1) f32
    out block (1, qc, hd)   written on the last kv step

Causal/window masks are reconstructed from block indices (global
positions = block_id * block + iota), so no mask tensor ever exists.

Validated in interpret mode against the plain softmax reference for
causal / windowed / bidirectional cases (tests/test_kernels_flash.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr,
    *, scale: float, causal: bool, window: int, qc: int, kc: int,
    n_kv: int, softcap: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                                   # (qc, hd)
    k = k_ref[0]                                   # (kc, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                      # (qc, kc)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
    k_pos = ki * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
    ok = k_pos <= q_pos if causal else jnp.ones((qc, kc), jnp.bool_)
    if window:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]                            # (qc, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                         # (qc, kc)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    m_scr[...] = m_new
    pv = jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
    )
    acc[...] = acc[...] * alpha + pv

    @pl.when(ki == n_kv - 1)
    def _emit():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    softcap: float = 0.0,
    q_block: int = 256,
    kv_block: int = 256,
    interpret: bool,
) -> jax.Array:
    """q: (BH, S, hd); k/v: (BH, T, hd) — GQA callers flatten (B, KV, G)
    into BH and broadcast k/v per group.  Returns (BH, S, hd)."""
    bh, s, hd = q.shape
    t = k.shape[1]
    scale = hd**-0.5 if scale is None else scale
    qc, kc = min(q_block, s), min(kv_block, t)
    s_pad, t_pad = -s % qc, -t % kc
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0)))
    if t_pad:
        # padded keys land at positions > any query -> masked by causal;
        # for bidirectional we mask them via a window-free position test
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0)))
    nq, nk = (s + s_pad) // qc, (t + t_pad) // kc
    if not causal and t_pad:
        # bidirectional + padding needs an explicit key bound: fall back
        # to a window covering everything real (masks pads via k_pos).
        raise ValueError("bidirectional flash requires T % kv_block == 0")

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            qc=qc, kc=kc, n_kv=nk, softcap=softcap,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qc, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kc, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kc, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, qc, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s + s_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qc, hd), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :s]


def flash_ref(q, k, v, *, causal=True, window=0, scale=None, softcap=0.0):
    """Plain-softmax oracle, same signature."""
    bh, s, hd = q.shape
    t = k.shape[1]
    scale = hd**-0.5 if scale is None else scale
    sc = jnp.einsum("bsd,btd->bst", q, k).astype(jnp.float32) * scale
    if softcap:
        sc = softcap * jnp.tanh(sc / softcap)
    q_pos = np.arange(s)[:, None]
    k_pos = np.arange(t)[None, :]
    ok = k_pos <= q_pos if causal else np.ones((s, t), bool)
    if window:
        ok = ok & (k_pos > q_pos - window)
    sc = jnp.where(jnp.asarray(ok)[None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    return jnp.einsum("bst,btd->bsd", p, v)
