"""The GROOT Pallas kernels compile for a TPU v5e (no chip needed).

Interpret mode runs on the CPU and never sees Mosaic's rules: block
shapes whose last two dims are not (8, 128)-aligned or whole, scalar
prefetch that overflows SMEM, more VMEM than a kernel may use.  These
cases compile every kernel of the verification path with
``interpret=False`` for a described ``v5e:2x2`` topology, at the lane
width the model pads to (F_pad = 128), the group counts of the two
aggregation directions (G = 4 fanin, 2 fanout), LD degrees across the
bucket range, and HD plans at 256 and 4,096 chunks (a ``csa:1024`` fanout
plan needs 4,096).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.  All cases live in this one file so one worker holds the library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_sage import fused_ld_matmul, fused_ld_matmul_grouped
from repro.kernels.groot_spmm import (
    E_T,
    F_TILE,
    LD_TILE_EDGES,
    SUBLANE,
    hd_apply,
    hd_grouped_apply,
    ld_bucket_apply,
    ld_grouped_apply,
)

F_PAD = F_TILE
DEGREES = (1, 2, 64, 512)
TILES = 4          # row tiles per LD slab


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _rows_per_tile(deg: int) -> int:
    return max(SUBLANE, (LD_TILE_EDGES // deg) // SUBLANE * SUBLANE)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _ld(deg, groups, mxu):
    r_t = _rows_per_tile(deg)
    slots = TILES * r_t * deg
    if groups is None:
        fn = lambda m: ld_bucket_apply(m, deg, r_t, interpret=False, mxu=mxu)
        return fn, [((slots, F_PAD), jnp.float32)]
    fn = lambda m, wg: ld_grouped_apply(m, wg, deg, r_t, interpret=False, mxu=mxu)
    return fn, [((slots, F_PAD), jnp.float32), ((slots, groups), jnp.float32)]


def _fused(deg, groups):
    r_t = _rows_per_tile(deg)
    slots = TILES * r_t * deg
    if groups is None:
        fn = lambda m, w: fused_ld_matmul(m, w, deg, r_t, interpret=False)
        return fn, [((slots, F_PAD), jnp.float32), ((F_PAD, F_PAD), jnp.float32)]
    fn = lambda m, wg, ws: fused_ld_matmul_grouped(
        m, wg, ws, deg, r_t, interpret=False
    )
    return fn, [
        ((slots, F_PAD), jnp.float32),
        ((slots, groups), jnp.float32),
        ((groups, F_PAD, F_PAD), jnp.float32),
    ]


def _hd(chunks, groups):
    # two chunks per HD row, as a row of degree (E_T, 2 * E_T] gets
    rows = chunks // 2
    meta = np.stack(
        [np.arange(chunks) // 2, (np.arange(chunks) % 2 == 0)], axis=1
    ).astype(np.int32)
    slots = chunks * E_T
    if groups is None:
        fn = lambda m: hd_apply(m, meta, rows, E_T, interpret=False)
        return fn, [((slots, F_PAD), jnp.float32)]
    fn = lambda m, wg: hd_grouped_apply(m, wg, meta, rows, E_T, interpret=False)
    return fn, [((slots, F_PAD), jnp.float32), ((slots, groups), jnp.float32)]


CASES = (
    [("ld", None, mxu) for mxu in (False, True)]
    + [("ld_grouped", g, mxu) for g in (2, 4) for mxu in (False, True)]
    + [("fused", None, None)]
    + [("fused_grouped", g, None) for g in (2, 4)]
    + [("hd", None, chunks) for chunks in (256, 4096)]
    + [("hd_grouped", g, chunks) for g in (2, 4) for chunks in (256, 4096)]
)


@pytest.mark.parametrize(
    "kernel,groups,variant", CASES,
    ids=[f"{k}-G{g}-{v}" for k, g, v in CASES],
)
def test_kernel_compiles_for_v5e(one_chip, kernel, groups, variant):
    if kernel in ("ld", "ld_grouped"):
        builds = [_ld(deg, groups, variant) for deg in DEGREES]
    elif kernel in ("fused", "fused_grouped"):
        builds = [_fused(deg, groups) for deg in DEGREES]
    else:
        builds = [_hd(variant, groups)]
    for fn, shapes in builds:
        _compile(fn, shapes, one_chip)
