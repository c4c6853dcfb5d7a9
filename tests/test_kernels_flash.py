"""Pallas flash-attention kernel vs plain-softmax oracle (interpret mode)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_ref


def _mk(bh, s, t, hd, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(k1, (bh, s, hd), dtype)
    k = jax.random.normal(k2, (bh, t, hd), dtype)
    v = jax.random.normal(k3, (bh, t, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("s,t,qb,kb", [
    (256, 256, 128, 128),
    (300, 300, 128, 128),   # padding path
    (128, 512, 64, 128),    # cross-length (q short)
])
@pytest.mark.parametrize("window", [0, 100])
def test_flash_causal_matches_ref(s, t, qb, kb, window):
    q, k, v = _mk(4, s, t, 64)
    got = flash_attention(q, k, v, causal=True, window=window,
                          q_block=qb, kv_block=kb, interpret=True)
    want = flash_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_bidirectional():
    q, k, v = _mk(2, 256, 256, 64)
    got = flash_attention(q, k, v, causal=False, q_block=128, kv_block=128,
                          interpret=True)
    want = flash_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_softcap():
    q, k, v = _mk(2, 128, 128, 32, seed=3)
    got = flash_attention(q, k, v, softcap=20.0, q_block=64, kv_block=64,
                          interpret=True)
    want = flash_ref(q, k, v, softcap=20.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q, k, v = _mk(2, 256, 256, 64, seed=5, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, q_block=128, kv_block=128, interpret=True)
    want = flash_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_flash_matches_model_sdpa():
    """The kernel and the model's lax-flash schedule agree (same math the
    dry-run lowers; the kernel is the TPU deployment form)."""
    import repro.zoo.models.attention as A
    from repro.zoo.configs import get_config
    from repro.zoo.configs.base import materialize, param_tree

    cfg = get_config("qwen3-8b", smoke=True)
    p = materialize(param_tree(cfg)["layers"][0]["attn"], jax.random.key(7),
                    jnp.float32)
    x = jax.random.normal(jax.random.key(8), (2, 256, cfg.d_model), jnp.float32)
    out_model, _ = A.attention(x, p, cfg)
    # run the kernel on the same projected q/k/v
    q, k, v = A._project_qkv(x, p, cfg)
    pos = jnp.arange(256, dtype=jnp.int32)
    q = A.rope(q, pos, cfg.rope_theta)
    k = A.rope(k, pos, cfg.rope_theta)
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qf = jnp.moveaxis(q.reshape(b, s, kv, g, hd), 1, 3).reshape(b * kv * g, s, hd)
    kf = jnp.repeat(jnp.moveaxis(k, 1, 2), g, axis=1).reshape(b * kv * g, s, hd)
    vf = jnp.repeat(jnp.moveaxis(v, 1, 2), g, axis=1).reshape(b * kv * g, s, hd)
    of = flash_attention(qf, kf, vf, causal=True, q_block=128, kv_block=128,
                         interpret=True)
    out_k = jnp.moveaxis(of.reshape(b, kv, g, s, hd), 3, 1).reshape(b, s, h, hd)
    out_kernel = jnp.einsum("bshk,hkd->bsd", out_k, p["wo"])
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               rtol=2e-4, atol=2e-4)
