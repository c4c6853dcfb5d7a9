"""GROOT degree-bucketed SpMM as Pallas TPU kernels (paper §IV, TPU-adapted).

The paper's insight: EDA graph degree distributions are *polarized* — a few
extreme high-degree (HD >= 512) rows (high-fanout nets) and millions of
low-degree (LD <= 12) rows (AND gates: in-degree 2, fanout 2-4).  One
schedule cannot serve both.  The CUDA design assigns 32 warps to one HD row
and packs many LD rows per warp after a degree count-sort.

TPU adaptation (see DESIGN.md §2): no warps — the unit of work is a VMEM
tile feeding the VPU/MXU.

  * **count-sort** (host, O(E)) buckets rows by next-pow2(degree); within a
    bucket every row has the same padded degree ``d``, so the bucket is an
    ELL slab: its gathered edge messages form a dense ``(R_b * d, F)``
    array where each destination row owns ``d`` consecutive message rows —
    the TPU equivalent of "rows with the same degree are assembled into the
    same blocks" (paper Fig. 5).
  * **LD kernel**: grid tile ``(R_t * d, F_t)`` -> output tile ``(R_t,
    F_t)``; the segment reduction is a reshape-sum (VPU) or a one-hot
    block-diagonal matmul (MXU) — contiguous loads, coalesced stores, no
    atomics: the same "aggregate many whole small rows per work unit"
    economics as packing ``6m/3m/2m`` rows per warp.
  * **HD kernel**: a row's edge stream is split into fixed ``E_t``-edge
    chunks; the grid walks chunks of the same row consecutively and
    accumulates partial sums into the row's output block *in VMEM*
    (initialised on the row's first chunk via scalar-prefetched metadata)
    — the analogue of splitting one row across 32 warps, with the shuffle
    reduction replaced by output-block revisiting.
  * the neighbour gather itself (``x[src]``) is done by XLA outside the
    kernel: TPUs have no efficient in-kernel random HBM gather, so the
    TPU-native formulation is gather -> dense edge stream -> systolic
    reduce (DESIGN.md §2, "hardware adaptation").

Thresholds mirror the paper: ``E_T = 512`` — rows with degree > 512 take
the HD path, everything else lands in an LD power-of-2 bucket (1..512).

Every entry point takes ``interpret`` explicitly; the kernel backends
choose it in one place (:func:`repro.kernels.ops.pallas_interpret`): the
Pallas interpreter on a CPU backend, compiled Mosaic kernels on the TPU.
The interpret-mode tests validate against ``kernels/ref.py``;
``tests/test_tpu_compile.py`` compiles every kernel for a v5e.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Paper §IV thresholds: HD rows have degree >= 512; LD buckets are the
# power-of-two degrees up to E_T.
E_T = 512
F_TILE = 128           # lane dimension tile (TPU lane width)
LD_TILE_EDGES = 2048   # target edges per LD VMEM tile (R_t * d)
SUBLANE = 8            # f32 sublane quantum
# Row tile of the MXU LD reduction.  Its one-hot (R, R * d) matrix grows
# with R^2 * d: at the VPU tile's R = 1024 rows and d = 2 it is 8 MiB,
# which double-buffered overflows the 16 MiB of scoped VMEM on a v5e.
# One MXU pass of rows bounds it to a few hundred KiB; every LD row tile
# is a power of two, so this one divides it.
MXU_ROWS = 128


# ---------------------------------------------------------------------------
# Hot-path probe.  Counters increment at *trace* time (or per eager call),
# so tracing one forward pass measures exactly how many times the edge
# stream is gathered and how many times the bucket-kernel schedule is
# walked — the quantities the grouped-SpMM refactor reduces from 6 to 2
# per layer.  ``pallas_calls`` counts individual kernel launches.
#
# The forward-invariant hoisting counters:
#   ``weight_gathers``   passes over the per-edge weight arrays (one
#                        ``jnp.take`` of the (E, G) stream).  Pre-hoist the
#                        grouped forward paid 2 per layer; the ForwardPlan
#                        stages the streams once -> 2 per FORWARD.
#   ``output_scatters``  ``out.at[rows].add`` ops issued.  The historical
#                        walks scattered once per bucket (+1 for HD,
#                        ``plan.num_segments`` per aggregation); since the
#                        scatter-free rewrite EVERY walk assembles via the
#                        inverse count-sort permutation, so the counter
#                        reads 0 — it exists as a regression tripwire: any
#                        reintroduced output scatter must bump it (the CI
#                        fast lane gates <= 2 per forward).
#   ``stream_bytes``     modeled HBM bytes of gathered edge streams
#                        (messages + staged weights), accumulated at trace
#                        time from static shapes/dtypes.
# ---------------------------------------------------------------------------

# Since the repro.obs spine landed, PROBE is a dict-shaped *view* over
# the process-wide metrics registry (counters ``kernels.spmm.<key>``):
# the historic ``PROBE["k"] += 1`` / ``dict(PROBE)`` idiom keeps working
# while every increment is visible to Session.report() and benchmarks.
from repro.obs.metrics import REGISTRY, CounterGroup

PROBE = CounterGroup(
    REGISTRY,
    "kernels.spmm",
    (
        "edge_stream_gathers",
        "kernel_walks",
        "pallas_calls",
        "weight_gathers",
        "output_scatters",
        "stream_bytes",
    ),
)


def reset_probe() -> None:
    for k in PROBE:
        PROBE[k] = 0


def probe_snapshot() -> dict:
    return dict(PROBE)


# ---------------------------------------------------------------------------
# Host-side plan (the count-sort / row-assembly of paper Fig. 5, step B)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LdBucket:
    """All rows whose (padded) degree is ``deg``: an ELL slab."""

    deg: int
    rows: np.ndarray        # (R_pad,) int32 destination row ids (pad = -1)
    cols: np.ndarray        # (R_pad * deg,) int32 source node ids (pad = N)
    eids: np.ndarray        # (R_pad * deg,) int32 edge ids (pad = E)
    rows_per_tile: int      # R_t

    @property
    def num_rows(self) -> int:
        return int(self.rows.shape[0])


@dataclasses.dataclass(frozen=True)
class HdPlan:
    """Rows with degree > E_T, chunked into E_t-edge pieces."""

    rows: np.ndarray        # (n_hd,) int32 destination row ids
    cols: np.ndarray        # (n_chunks * E_t,) int32 source ids (pad = N)
    eids: np.ndarray        # (n_chunks * E_t,) int32 edge ids (pad = E)
    chunk_meta: np.ndarray  # (n_chunks, 2) int32: [output row slot, is_first]
                            # (prefetched flattened by the HD kernels)

    @property
    def num_chunks(self) -> int:
        return int(self.chunk_meta.shape[0])


@dataclasses.dataclass(frozen=True)
class SpmmPlan:
    num_nodes: int
    num_edges: int
    buckets: tuple          # tuple[LdBucket, ...]
    hd: Optional[HdPlan]
    e_t: int = E_T
    # Inverse count-sort permutation for scatter-free output assembly:
    # bucket (then HD) reductions concatenated row-major form a
    # (asm_rows, F) array whose LAST row is zero; ``asm_index[r]`` is the
    # concat position of destination row r (degree-0 rows point at the
    # zero row).  A row appears in exactly one LD bucket OR the HD plan —
    # never both — so one gather (no adds) assembles the (N, F) output.
    asm_index: Optional[np.ndarray] = None   # (N,) int32
    asm_rows: int = 0

    def padding_overhead(self) -> float:
        """Padded-slot fraction — the cost of ELL bucketing (tests assert
        the pow-2 bound: <= ~2x + tile-rounding)."""
        slots = sum(b.eids.size for b in self.buckets)
        slots += self.hd.eids.size if self.hd else 0
        return slots / max(self.num_edges, 1)

    @property
    def num_slots(self) -> int:
        """Gathered edge-stream rows per walk (real edges + ELL padding)."""
        return sum(b.eids.size for b in self.buckets) + (
            self.hd.eids.size if self.hd else 0
        )

    @property
    def num_segments(self) -> int:
        """Output segments one aggregation produces (LD buckets + HD) —
        the per-walk scatter count of the pre-hoist assembly."""
        return len(self.buckets) + (1 if self.hd is not None else 0)


def build_plan(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    num_nodes: int,
    *,
    e_t: int = E_T,
    ld_tile_edges: int = LD_TILE_EDGES,
) -> SpmmPlan:
    """Degree count-sort + row assembly (paper Fig. 5 step B, host, O(E)).

    ``eids`` index the *edge array*, so one plan serves any (x, w) pair on
    the same graph (all six slot/polarity groups of the GNN reuse it).
    """
    edge_src = np.asarray(edge_src, dtype=np.int64)
    edge_dst = np.asarray(edge_dst, dtype=np.int64)
    n, e = int(num_nodes), int(edge_dst.shape[0])
    # indices are staged as int32 (halves the index bytes per launch);
    # partitioned subgraphs guarantee device-sized N and E
    assert n < 2**31 and e < 2**31, (
        f"graph too large for int32 plan indices ({n} nodes, {e} edges)"
    )
    deg = np.bincount(edge_dst, minlength=n).astype(np.int64)

    # CSR-style row starts after a stable count-sort of edges by dest row.
    order = np.argsort(edge_dst, kind="stable").astype(np.int64)
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=starts[1:])

    buckets: list[LdBucket] = []
    d = 1
    while d <= e_t:
        lo = 1 if d == 1 else d // 2 + 1
        rows = np.where((deg >= lo) & (deg <= d))[0]
        if rows.size:
            r_t = max(SUBLANE, (ld_tile_edges // d) // SUBLANE * SUBLANE)
            r_pad = -rows.size % r_t
            eids = np.full((rows.size + r_pad, d), e, dtype=np.int64)
            for slot in range(d):  # d slots; loop count <= 512, host-only
                take = deg[rows] > slot
                eids[: rows.size][take, slot] = order[starts[rows[take]] + slot]
            rows_p = np.concatenate(
                [rows, np.full(r_pad, -1, dtype=np.int64)]
            ).astype(np.int32)
            flat = eids.reshape(-1)
            cols = np.where(flat < e, edge_src[np.minimum(flat, e - 1)], n)
            buckets.append(
                LdBucket(
                    deg=d,
                    rows=rows_p,
                    cols=cols.astype(np.int32),
                    eids=flat.astype(np.int32),
                    rows_per_tile=r_t,
                )
            )
        d *= 2

    hd_rows = np.where(deg > e_t)[0]
    hd = None
    if hd_rows.size:
        n_chunks_per = -(-deg[hd_rows] // e_t)
        total_chunks = int(n_chunks_per.sum())
        eids = np.full((total_chunks, e_t), e, dtype=np.int64)
        meta = np.zeros((total_chunks, 2), dtype=np.int32)
        c = 0
        for slot_i, r in enumerate(hd_rows):
            row_edges = order[starts[r] : starts[r + 1]]
            for k in range(int(n_chunks_per[slot_i])):
                chunk = row_edges[k * e_t : (k + 1) * e_t]
                eids[c, : chunk.size] = chunk
                meta[c] = (slot_i, 1 if k == 0 else 0)
                c += 1
        flat = eids.reshape(-1)
        cols = np.where(flat < e, edge_src[np.minimum(flat, e - 1)], n)
        hd = HdPlan(
            rows=hd_rows.astype(np.int32),
            cols=cols.astype(np.int32),
            eids=flat.astype(np.int32),
            chunk_meta=meta,
        )

    asm_index, asm_rows = _assembly_index(n, buckets, hd)
    return SpmmPlan(
        num_nodes=n, num_edges=e, buckets=tuple(buckets), hd=hd, e_t=e_t,
        asm_index=asm_index, asm_rows=asm_rows,
    )


def _assembly_index(
    n: int, buckets: list[LdBucket], hd: Optional[HdPlan]
) -> tuple[np.ndarray, int]:
    """Inverse count-sort permutation (scatter-free output assembly).

    Concatenating every bucket's padded reduction and the HD rows
    row-major, followed by one zero row, gives an (asm_rows, F) array
    where ``take(cat, asm_index)`` is exactly what the per-bucket
    ``out.at[rows].add`` passes used to build — a destination row belongs
    to exactly one LD bucket or the HD plan, so no adds are needed.
    """
    asm = np.full(n, -1, dtype=np.int64)
    off = 0
    for b in buckets:
        live = b.rows >= 0
        rows_live = b.rows[live].astype(np.int64)
        assert (asm[rows_live] < 0).all(), "row in two LD buckets"
        asm[rows_live] = off + np.nonzero(live)[0]
        off += b.rows.shape[0]
    if hd is not None:
        hd_rows = hd.rows.astype(np.int64)
        # a row receiving both an LD and an HD contribution would need an
        # add on top of the gather; the degree partition makes it
        # impossible within one plan — assert it
        assert (asm[hd_rows] < 0).all(), "row is both LD and HD"
        asm[hd_rows] = off + np.arange(hd.rows.shape[0])
        off += hd.rows.shape[0]
    zero_row = off
    asm[asm < 0] = zero_row           # degree-0 rows read the zero row
    asm_rows = off + 1
    assert asm_rows < 2**31
    return asm.astype(np.int32), asm_rows


# ---------------------------------------------------------------------------
# LD kernel
# ---------------------------------------------------------------------------

def _ld_kernel(msgs_ref, o_ref, *, rows: int, deg: int):
    """(R_t * d, F_t) edge-message tile -> (R_t, F_t) row sums (VPU path).

    Accumulation is always f32 (bf16 inputs are widened in VREGs — free on
    the VPU, and required for deep-degree numerical sanity)."""
    m = msgs_ref[...].astype(jnp.float32)
    o_ref[...] = m.reshape(rows, deg, m.shape[-1]).sum(axis=1)


def _ld_kernel_mxu(red_ref, msgs_ref, o_ref):
    """MXU path: one-hot block-diagonal reduction matrix @ message tile.

    ``red`` is (R_t, R_t*d) with red[r, r*d:(r+1)*d] = 1 — the segment sum
    becomes a systolic matmul (DESIGN.md §2, "one-hot MXU matmul").
    """
    o_ref[...] = jax.lax.dot(
        red_ref[...], msgs_ref[...],
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=o_ref.dtype,
    )


def ld_bucket_apply(
    msgs: jax.Array, deg: int, rows_per_tile: int, *, interpret: bool, mxu: bool
) -> jax.Array:
    """Run the LD kernel over one ELL slab.  msgs: (R_pad * deg, F_pad)."""
    PROBE["pallas_calls"] += 1
    f_pad = msgs.shape[1]
    r_pad = msgs.shape[0] // deg
    mxu = mxu and deg > 1
    r_t = min(rows_per_tile, MXU_ROWS) if mxu else rows_per_tile
    grid = (r_pad // r_t, f_pad // F_TILE)
    out_shape = jax.ShapeDtypeStruct((r_pad, f_pad), jnp.float32)
    if mxu:
        red = np.zeros((r_t, r_t * deg), dtype=np.float32)
        for r in range(r_t):
            red[r, r * deg : (r + 1) * deg] = 1.0
        return pl.pallas_call(
            _ld_kernel_mxu,
            grid=grid,
            in_specs=[
                pl.BlockSpec((r_t, r_t * deg), lambda i, j: (0, 0)),
                pl.BlockSpec((r_t * deg, F_TILE), lambda i, j: (i, j)),
            ],
            out_specs=pl.BlockSpec((r_t, F_TILE), lambda i, j: (i, j)),
            out_shape=out_shape,
            interpret=interpret,
        )(jnp.asarray(red, msgs.dtype), msgs)
    return pl.pallas_call(
        functools.partial(_ld_kernel, rows=r_t, deg=deg),
        grid=grid,
        in_specs=[pl.BlockSpec((r_t * deg, F_TILE), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((r_t, F_TILE), lambda i, j: (i, j)),
        out_shape=out_shape,
        interpret=interpret,
    )(msgs)


# ---------------------------------------------------------------------------
# HD kernel
# ---------------------------------------------------------------------------

def _hd_kernel(meta_ref, msgs_ref, o_ref):
    """One E_t-edge chunk -> partial sum accumulated into the row's output.

    Chunks of the same row are consecutive in the (inner) chunk grid dim,
    so the output block stays resident in VMEM across the row's chunks —
    the TPU version of the 32-warp row split + shuffle reduce.  ``meta``
    is the flattened ``chunk_meta``: ``meta[2c]`` is chunk c's output row
    slot, ``meta[2c + 1]`` is 1 on the row's first chunk.
    """
    c = pl.program_id(1)
    part = msgs_ref[...].astype(jnp.float32).sum(axis=0, keepdims=True)

    @pl.when(meta_ref[2 * c + 1] == 1)
    def _init():
        o_ref[...] = part

    @pl.when(meta_ref[2 * c + 1] == 0)
    def _acc():
        o_ref[...] += part


def hd_apply(
    msgs: jax.Array,
    chunk_meta: np.ndarray,
    n_hd_rows: int,
    e_t: int,
    *,
    interpret: bool,
) -> jax.Array:
    """msgs: (n_chunks * e_t, F_pad) -> (n_hd_rows, F_pad).

    Grid is (F-tiles, chunks): the chunk dim is innermost so same-row
    chunks revisit the same output block back-to-back (required for the
    VMEM accumulation pattern).  The output is laid out (n_hd, 1, F_pad)
    so the block's last two dims, (1, F_TILE), are legal Mosaic tiles (a
    (1, F_TILE) block over (n_hd, F_pad) is not); ``chunk_meta`` is
    prefetched flat, since a 2-D SMEM operand pads its minor dim to 128
    words and overflows SMEM at a few thousand chunks.
    """
    PROBE["pallas_calls"] += 1
    f_pad = msgs.shape[1]
    n_chunks = msgs.shape[0] // e_t
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(f_pad // F_TILE, n_chunks),
        in_specs=[pl.BlockSpec((e_t, F_TILE), lambda j, c, meta: (c, j))],
        out_specs=pl.BlockSpec(
            (pl.Squeezed(), 1, F_TILE), lambda j, c, meta: (meta[2 * c], 0, j)
        ),
    )
    out = pl.pallas_call(
        _hd_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_hd_rows, 1, f_pad), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(chunk_meta.reshape(-1)), msgs)
    return out.reshape(n_hd_rows, f_pad)


# ---------------------------------------------------------------------------
# Full SpMM: gather (XLA) -> per-bucket kernels -> permutation assembly (XLA)
# ---------------------------------------------------------------------------

def pad_features(x: jax.Array) -> jax.Array:
    """Feature staging for the bucket walks: one zero row appended (the
    gather pad target) and lanes padded to the F_TILE quantum.  Hoisted
    callers (the ForwardPlan forward) pad once per layer and share the
    result across both direction walks."""
    f = x.shape[1]
    return jnp.pad(x, ((0, 1), (0, -f % F_TILE)))


def assemble_rows(plan: SpmmPlan, parts: list, f_pad: int) -> jax.Array:
    """Scatter-free output assembly via the inverse count-sort permutation.

    ``parts`` are the per-bucket (R_pad, F_pad) reductions (then HD) in
    plan order; one concatenate + one gather replaces the pre-hoist
    ``num_segments`` ``out.at[rows].add`` passes over the (N, F) output.
    """
    parts = list(parts) + [jnp.zeros((1, f_pad), jnp.float32)]
    cat = jnp.concatenate(parts, axis=0)
    return jnp.take(cat, jnp.asarray(plan.asm_index), axis=0)


def assemble_rows_grouped(
    plan: SpmmPlan, parts: list, groups: int, f_pad: int
) -> jax.Array:
    """Grouped variant: parts are (G, R_pad, F_pad); concat/gather on axis 1.

    ``groups`` is passed explicitly — a zero-edge graph has no parts to
    infer it from but must still return (G, N, F_pad)."""
    parts = list(parts) + [jnp.zeros((groups, 1, f_pad), jnp.float32)]
    cat = jnp.concatenate(parts, axis=1)
    return jnp.take(cat, jnp.asarray(plan.asm_index), axis=1)


def apply_plan(
    plan: SpmmPlan,
    x: jax.Array,
    w: Optional[jax.Array] = None,
    *,
    interpret: bool,
    mxu: bool = False,
) -> jax.Array:
    """Compute ``out[r] = sum_{e: dst[e]=r} w[e] * x[src[e]]`` via the
    degree-bucketed kernels.  ``plan`` is static (host numpy); ``x``/``w``
    are traced.  Matches :func:`repro.kernels.ref.spmm_ref`.
    """
    PROBE["edge_stream_gathers"] += 1
    PROBE["kernel_walks"] += 1
    if w is not None:
        PROBE["weight_gathers"] += 1
        PROBE["stream_bytes"] += plan.num_slots * x.dtype.itemsize
    n, f = x.shape
    f_pad = f + (-f % F_TILE)
    x_p = pad_features(x)
    w_p = None if w is None else jnp.pad(w.astype(x.dtype), (0, 1))
    PROBE["stream_bytes"] += plan.num_slots * f_pad * x.dtype.itemsize

    def gather(cols: np.ndarray, eids: np.ndarray) -> jax.Array:
        g = jnp.take(x_p, jnp.asarray(cols), axis=0)
        if w_p is not None:
            g = g * jnp.take(w_p, jnp.asarray(eids), axis=0)[:, None]
        return g

    parts = []
    for b in plan.buckets:
        msgs = gather(b.cols, b.eids)
        parts.append(
            ld_bucket_apply(msgs, b.deg, b.rows_per_tile, interpret=interpret, mxu=mxu)
        )
    if plan.hd is not None:
        msgs = gather(plan.hd.cols, plan.hd.eids)
        parts.append(
            hd_apply(
                msgs, plan.hd.chunk_meta, len(plan.hd.rows), plan.e_t,
                interpret=interpret,
            )
        )
    out = assemble_rows(plan, parts, f_pad)
    return out[:, :f].astype(x.dtype)


# ---------------------------------------------------------------------------
# Grouped multi-polarity SpMM.  The SAGE layer's six slot x polarity
# aggregations share one plan and identical gather columns — only the
# per-edge weights differ.  The grouped kernels take a (slots, G) weight
# matrix, gather ``x[src]`` ONCE, broadcast-multiply by the G weight
# columns inside the tile, and reduce every group in the same pass:
# 6 gathers + 6 kernel walks per layer collapse to one per direction.
# Output layout is group-major (G, R, F) — per-group (N, F) planes the
# layer contracts directly via ``einsum('gnf,gfh->nh')``.
# ---------------------------------------------------------------------------

def _ld_kernel_grouped(wg_ref, msgs_ref, o_ref, *, rows: int, deg: int):
    """(R_t*d, F_t) tile + (R_t*d, G) weights -> (G, R_t, F_t) row sums.

    One edge-message load serves every group; the per-group weighting is
    a VREG broadcast (f32 accumulation as in the ungrouped kernel)."""
    m = msgs_ref[...].astype(jnp.float32)
    w = wg_ref[...].astype(jnp.float32)
    prod = w.T[:, :, None] * m[None, :, :]            # (G, R_t*d, F_t)
    o_ref[...] = prod.reshape(w.shape[1], rows, deg, m.shape[-1]).sum(axis=2)


def _ld_kernel_grouped_mxu(red_ref, wg_ref, msgs_ref, o_ref, *, groups: int):
    """MXU path: per group, one-hot block-diag reduction @ weighted tile.

    ``groups`` is static and tiny (2 or 4), so the loop unrolls into G
    back-to-back systolic matmuls over the SAME resident message tile."""
    m = msgs_ref[...]
    w = wg_ref[...]
    red = red_ref[...]
    o_ref[...] = jnp.stack(
        [
            jax.lax.dot(
                red, m * w[:, g][:, None],
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=o_ref.dtype,
            )
            for g in range(groups)
        ],
        axis=0,
    )


def ld_grouped_apply(
    msgs: jax.Array,
    wg: jax.Array,
    deg: int,
    rows_per_tile: int,
    *,
    interpret: bool,
    mxu: bool,
) -> jax.Array:
    """Grouped LD reduction over one ELL slab.

    msgs: (R_pad * deg, F_pad); wg: (R_pad * deg, G) -> (G, R_pad, F_pad).
    """
    PROBE["pallas_calls"] += 1
    f_pad = msgs.shape[1]
    g = wg.shape[1]
    r_pad = msgs.shape[0] // deg
    mxu = mxu and deg > 1
    r_t = min(rows_per_tile, MXU_ROWS) if mxu else rows_per_tile
    grid = (r_pad // r_t, f_pad // F_TILE)
    out_shape = jax.ShapeDtypeStruct((g, r_pad, f_pad), jnp.float32)
    if mxu:
        red = np.zeros((r_t, r_t * deg), dtype=np.float32)
        for r in range(r_t):
            red[r, r * deg : (r + 1) * deg] = 1.0
        return pl.pallas_call(
            functools.partial(_ld_kernel_grouped_mxu, groups=g),
            grid=grid,
            in_specs=[
                pl.BlockSpec((r_t, r_t * deg), lambda i, j: (0, 0)),
                pl.BlockSpec((r_t * deg, g), lambda i, j: (i, 0)),
                pl.BlockSpec((r_t * deg, F_TILE), lambda i, j: (i, j)),
            ],
            out_specs=pl.BlockSpec((g, r_t, F_TILE), lambda i, j: (0, i, j)),
            out_shape=out_shape,
            interpret=interpret,
        )(jnp.asarray(red, msgs.dtype), wg.astype(msgs.dtype), msgs)
    return pl.pallas_call(
        functools.partial(_ld_kernel_grouped, rows=r_t, deg=deg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r_t * deg, g), lambda i, j: (i, 0)),
            pl.BlockSpec((r_t * deg, F_TILE), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((g, r_t, F_TILE), lambda i, j: (0, i, j)),
        out_shape=out_shape,
        interpret=interpret,
    )(wg, msgs)


def _hd_kernel_grouped(meta_ref, wg_ref, msgs_ref, o_ref):
    """One E_t-edge chunk -> per-group partial sums for the chunk's row.

    The weighted reduction is one (G, E_t) @ (E_t, F_t) systolic matmul;
    accumulation across a row's chunks revisits the same (G, F_t) output
    block in VMEM, exactly like the ungrouped HD kernel."""
    c = pl.program_id(1)
    m = msgs_ref[...].astype(jnp.float32)
    w = wg_ref[...].astype(jnp.float32)
    part = jax.lax.dot_general(
        w, m, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(meta_ref[2 * c + 1] == 1)
    def _init():
        o_ref[...] = part

    @pl.when(meta_ref[2 * c + 1] == 0)
    def _acc():
        o_ref[...] += part


def hd_grouped_apply(
    msgs: jax.Array,
    wg: jax.Array,
    chunk_meta: np.ndarray,
    n_hd_rows: int,
    e_t: int,
    *,
    interpret: bool,
) -> jax.Array:
    """msgs: (n_chunks * e_t, F_pad); wg: (n_chunks * e_t, G)
    -> (G, n_hd_rows, F_pad).

    The kernel writes row-major (n_hd, G, F_pad) — a (G, F_TILE) block is
    a legal tile where (G, 1, F_TILE) over (G, n_hd, F_pad) is not — and
    the group-major layout is restored outside (HD rows are few)."""
    PROBE["pallas_calls"] += 1
    f_pad = msgs.shape[1]
    g = wg.shape[1]
    n_chunks = msgs.shape[0] // e_t
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(f_pad // F_TILE, n_chunks),
        in_specs=[
            pl.BlockSpec((e_t, g), lambda j, c, meta: (c, 0)),
            pl.BlockSpec((e_t, F_TILE), lambda j, c, meta: (c, j)),
        ],
        out_specs=pl.BlockSpec(
            (pl.Squeezed(), g, F_TILE), lambda j, c, meta: (meta[2 * c], 0, j)
        ),
    )
    out = pl.pallas_call(
        _hd_kernel_grouped,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_hd_rows, g, f_pad), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(chunk_meta.reshape(-1)), wg, msgs)
    return jnp.transpose(out, (1, 0, 2))


# ---------------------------------------------------------------------------
# Forward-invariant weight staging.  The (E, G) group-weight matrices of a
# GNN forward are layer-invariant; pre-hoist every layer of every forward
# re-gathered them into each bucket's ELL layout (``jnp.take(wg_p,
# b.eids)`` per bucket per layer).  ``stage_group_weights`` performs ONE
# gather of the concatenated edge-id stream and slices the result into
# per-bucket (and HD-chunk) streams the staged walks consume directly —
# layers 2..L touch zero edge-weight bytes.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StagedWeights:
    """Edge-weight streams pre-gathered into kernel layout (traced arrays,
    aligned with ``plan.buckets`` order; ``hd`` in HD-chunk layout)."""

    buckets: tuple                 # per-bucket (R_pad * deg, G)
    hd: Optional[jax.Array]        # (n_chunks * e_t, G) or None
    groups: int


def plan_cat_eids(plan: SpmmPlan) -> np.ndarray:
    """Concatenated edge-id stream of every bucket + HD chunk (int32) —
    the single gather index of :func:`stage_group_weights`."""
    parts = [b.eids for b in plan.buckets]
    if plan.hd is not None:
        parts.append(plan.hd.eids)
    if not parts:
        return np.zeros(0, np.int32)
    return np.concatenate(parts).astype(np.int32)


def stage_group_weights(
    plan: SpmmPlan,
    wg: jax.Array,
    *,
    cat_eids: Optional[np.ndarray] = None,
    dtype=None,
) -> StagedWeights:
    """Gather the (E, G) group-weight matrix into every bucket's ELL
    layout and the HD chunk layout in ONE pass (``dtype`` casts the
    staged streams, e.g. bf16 — kernels accumulate in f32 regardless)."""
    PROBE["weight_gathers"] += 1
    g = wg.shape[1]
    if cat_eids is None:
        cat_eids = plan_cat_eids(plan)
    wg_p = jnp.pad(wg.astype(jnp.float32), ((0, 1), (0, 0)))  # row E = 0 weight
    cat = jnp.take(wg_p, jnp.asarray(cat_eids), axis=0)
    if dtype is not None:
        cat = cat.astype(dtype)
    PROBE["stream_bytes"] += int(cat_eids.size) * g * cat.dtype.itemsize
    chunks = []
    off = 0
    for b in plan.buckets:
        chunks.append(cat[off : off + b.eids.size])
        off += b.eids.size
    hd = None
    if plan.hd is not None:
        hd = cat[off : off + plan.hd.eids.size]
    return StagedWeights(buckets=tuple(chunks), hd=hd, groups=g)


def apply_plan_grouped_staged(
    plan: SpmmPlan,
    x_p: jax.Array,
    staged: StagedWeights,
    *,
    interpret: bool,
    mxu: bool = False,
) -> jax.Array:
    """Hoisted grouped walk: pre-padded features (see :func:`pad_features`)
    + pre-staged weight streams in, ``(G, N, F_pad)`` f32 out.  Touches no
    edge-weight bytes and issues no output scatters (permutation
    assembly)."""
    PROBE["edge_stream_gathers"] += 1
    PROBE["kernel_walks"] += 1
    f_pad = x_p.shape[1]
    PROBE["stream_bytes"] += plan.num_slots * f_pad * x_p.dtype.itemsize
    parts = []
    for b, wge in zip(plan.buckets, staged.buckets):
        msgs = jnp.take(x_p, jnp.asarray(b.cols), axis=0)
        parts.append(
            ld_grouped_apply(
                msgs, wge, b.deg, b.rows_per_tile, interpret=interpret, mxu=mxu
            )
        )
    if plan.hd is not None:
        msgs = jnp.take(x_p, jnp.asarray(plan.hd.cols), axis=0)
        parts.append(
            hd_grouped_apply(
                msgs, staged.hd, plan.hd.chunk_meta, len(plan.hd.rows), plan.e_t,
                interpret=interpret,
            )
        )
    return assemble_rows_grouped(plan, parts, staged.groups, f_pad)


def apply_plan_grouped(
    plan: SpmmPlan,
    x: jax.Array,
    wg: jax.Array,
    *,
    interpret: bool,
    mxu: bool = False,
) -> jax.Array:
    """All-groups SpMM: ``out[g, r] = sum_{e: dst[e]=r} wg[e, g] * x[src[e]]``.

    One walk of the bucket schedule and one gather of the edge stream
    serve every group — ``wg`` is ``(E, G)`` with one weight column per
    slot x polarity group.  Returns ``(G, N, F)`` in ``x.dtype``.
    Matches ``stack([apply_plan(plan, x, wg[:, g]) for g])``.

    Stages the weight streams per call; the hoisted forward
    (:mod:`repro.kernels.forward_plan`) stages once per forward instead.
    """
    f = x.shape[1]
    staged = stage_group_weights(plan, wg)
    out = apply_plan_grouped_staged(
        plan, pad_features(x), staged, interpret=interpret, mxu=mxu
    )
    return out[:, :, :f].astype(x.dtype)
