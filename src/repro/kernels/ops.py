"""Backend dispatch + jit wrappers for graph aggregation.

An *aggregation pair* is ``(in_agg, out_agg)`` — two callables ``(x, w) ->
(N, F)`` computing the weighted neighbour sums over fanin edges and fanout
edges respectively.  ``repro.core.gnn.forward`` consumes such pairs; this
module builds them for each backend:

  ``ref``         gather + segment_sum (row-parallel SpMM; the
                  GNNAdvisor-style baseline)
  ``onehot``      dense one-hot matmul formulation (cuSPARSE-dense
                  analogue; O(N*E) — small graphs/benchmarks only)
  ``groot``       the Pallas degree-bucketed HD/LD kernels (VPU reduce);
                  compiled on the TPU, interpreted on a CPU backend
                  (:func:`pallas_interpret`)
  ``groot_mxu``   same, LD reduction as one-hot block-diag MXU matmul
  ``groot_fused`` ``groot`` aggregation whose LD slabs can additionally be
                  fused with the following weight matmul
                  (``agg_mm`` method; beyond-paper optimization)

Plans are built once per graph on host (numpy) and embedded as constants
in the jitted computation — exactly how a static EDA graph is deployed.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import plan_cache as pc
from repro.kernels import ref as kref
from repro.kernels.forward_plan import ForwardPlan, build_forward_plan
from repro.kernels.groot_spmm import (
    PROBE,
    SpmmPlan,
    StagedWeights,
    apply_plan,
    apply_plan_grouped,
    apply_plan_grouped_staged,
    assemble_rows,
    build_plan,
    hd_grouped_apply,
    pad_features,
    stage_group_weights,
)
from repro.kernels.fused_sage import fused_ld_matmul, fused_ld_matmul_grouped

BACKENDS = ("ref", "onehot", "groot", "groot_mxu", "groot_fused")


def onehot_spmm(x, edge_src, edge_dst, num_nodes: int, w=None):
    """Dense formulation: ``onehot(dst)^T @ (x[src] * w)``.

    This is what a "just use dense matmul" port of SpMM to the MXU looks
    like *without* the GROOT insight — the baseline the degree-bucketed
    kernels beat on memory (it materialises an (E, N) one-hot).
    """
    msgs = jnp.take(x, edge_src, axis=0)
    if w is not None:
        msgs = msgs * w[:, None].astype(msgs.dtype)
    oh = jax.nn.one_hot(edge_dst, num_nodes, dtype=x.dtype)  # (E, N)
    return oh.T @ msgs


@dataclasses.dataclass
class AggPair:
    """Aggregation callables for one graph (+ optional fused/grouped paths).

    The grouped entry points take a ``(E, G)`` weight matrix — one column
    per slot x polarity group — and compute every group's aggregation in
    a single plan walk with a single gather of the edge stream, returning
    group-major ``(G, N, F)``.  They are ``None`` for backends that have
    no shared plan to exploit (``ref``/``onehot``), where the model layer
    keeps its per-group loop.
    """

    in_agg: Callable      # (x, w) -> (N, F) over fanin edges
    out_agg: Callable     # (x, w) -> (N, F) over fanout edges
    backend: str
    # fused aggregate+matmul over fanin LD slabs; None when unsupported
    in_agg_mm: Optional[Callable] = None
    in_plan: Optional[SpmmPlan] = None
    out_plan: Optional[SpmmPlan] = None
    # grouped paths: (x, wg (E, G)) -> (G, N, F) in one plan walk
    in_agg_grouped: Optional[Callable] = None
    out_agg_grouped: Optional[Callable] = None
    # grouped fuse: (x, wg (E, G), w_stack (G, F, H)) -> (N, H)
    in_agg_mm_grouped: Optional[Callable] = None
    # forward-invariant hoisting (all groot* backends): the ForwardPlan
    # stages the weight streams once per forward; the *_staged entry
    # points consume pre-padded features + staged streams and return f32
    # padded-lane outputs — (G, N, F_pad), or (N, H_pad) for the fuse
    fwd_plan: Optional[ForwardPlan] = None
    in_agg_staged: Optional[Callable] = None     # (x_p, staged) -> (G, N, F_pad)
    out_agg_staged: Optional[Callable] = None
    in_agg_mm_staged: Optional[Callable] = None  # (x_p, staged, wm_p) -> (N, H_pad)

    def __hash__(self):  # jit static-arg friendliness
        return id(self)

    def __eq__(self, other):
        return self is other


def ungrouped(pair: AggPair) -> AggPair:
    """A copy of ``pair`` with the grouped entry points stripped — forces
    the model layer back onto the per-group loop (parity tests and the
    grouped-vs-per-group benchmark)."""
    return dataclasses.replace(
        pair,
        in_agg_grouped=None,
        out_agg_grouped=None,
        in_agg_mm_grouped=None,
        fwd_plan=None,
        in_agg_staged=None,
        out_agg_staged=None,
        in_agg_mm_staged=None,
    )


def unhoisted(pair: AggPair) -> AggPair:
    """A copy of ``pair`` without the ForwardPlan — keeps the grouped
    walks but re-stages the weight streams every layer (the pre-hoist
    walk; the hoisting bit-exactness tests and the before/after traffic
    benchmark route through it)."""
    return dataclasses.replace(
        pair,
        fwd_plan=None,
        in_agg_staged=None,
        out_agg_staged=None,
        in_agg_mm_staged=None,
    )


def _segment_pair(edge_src, edge_dst, num_nodes) -> AggPair:
    s = jnp.asarray(edge_src)
    d = jnp.asarray(edge_dst)
    return AggPair(
        in_agg=lambda x, w=None: kref.spmm_ref(x, s, d, num_nodes, w),
        out_agg=lambda x, w=None: kref.spmm_ref(x, d, s, num_nodes, w),
        backend="ref",
    )


def _onehot_pair(edge_src, edge_dst, num_nodes) -> AggPair:
    s = jnp.asarray(edge_src)
    d = jnp.asarray(edge_dst)
    return AggPair(
        in_agg=lambda x, w=None: onehot_spmm(x, s, d, num_nodes, w),
        out_agg=lambda x, w=None: onehot_spmm(x, d, s, num_nodes, w),
        backend="onehot",
    )


def pallas_interpret() -> bool:
    """Whether the GROOT Pallas kernels run in interpret mode — the one
    place that decides it.  Only a CPU backend interprets; on the TPU the
    kernels are compiled, and a kernel that fails to compile raises (there
    is no fallback to the interpreter or to ``ref``)."""
    return jax.default_backend() == "cpu"


def _groot_pair(
    edge_src,
    edge_dst,
    num_nodes,
    *,
    mxu: bool,
    fused: bool,
    interpret: bool,
    use_cache: bool = True,
) -> AggPair:
    src = np.asarray(edge_src)
    dst = np.asarray(edge_dst)
    if use_cache:
        in_plan = pc.cached_plan(src, dst, num_nodes)
        out_plan = pc.cached_plan(dst, src, num_nodes)
        fwd_plan = pc.cached_forward_plan(src, dst, num_nodes)
    else:
        in_plan = build_plan(src, dst, num_nodes)
        out_plan = build_plan(dst, src, num_nodes)
        fwd_plan = build_forward_plan(in_plan, out_plan)

    def in_agg(x, w=None):
        return apply_plan(in_plan, x, w, interpret=interpret, mxu=mxu)

    def out_agg(x, w=None):
        return apply_plan(out_plan, x, w, interpret=interpret, mxu=mxu)

    def in_agg_grouped(x, wg):
        return apply_plan_grouped(in_plan, x, wg, interpret=interpret, mxu=mxu)

    def out_agg_grouped(x, wg):
        return apply_plan_grouped(out_plan, x, wg, interpret=interpret, mxu=mxu)

    def in_agg_staged(x_p, staged):
        return apply_plan_grouped_staged(
            in_plan, x_p, staged, interpret=interpret, mxu=mxu
        )

    def out_agg_staged(x_p, staged):
        return apply_plan_grouped_staged(
            out_plan, x_p, staged, interpret=interpret, mxu=mxu
        )

    in_agg_mm = None
    in_agg_mm_grouped = None
    in_agg_mm_staged = None
    if fused:

        def in_agg_mm(x, w, w_mat):
            return _apply_plan_fused(in_plan, x, w, w_mat, interpret=interpret)

        def in_agg_mm_grouped(x, wg, w_stack):
            return _apply_plan_fused_grouped(
                in_plan, x, wg, w_stack, interpret=interpret
            )

        def in_agg_mm_staged(x_p, staged, wm_p):
            return _apply_plan_fused_grouped_staged(
                in_plan, x_p, staged, wm_p, interpret=interpret
            )

    return AggPair(
        in_agg=in_agg,
        out_agg=out_agg,
        backend="groot_fused" if fused else ("groot_mxu" if mxu else "groot"),
        in_agg_mm=in_agg_mm,
        in_plan=in_plan,
        out_plan=out_plan,
        in_agg_grouped=in_agg_grouped,
        out_agg_grouped=out_agg_grouped,
        in_agg_mm_grouped=in_agg_mm_grouped,
        fwd_plan=fwd_plan,
        in_agg_staged=in_agg_staged,
        out_agg_staged=out_agg_staged,
        in_agg_mm_staged=in_agg_mm_staged,
    )


def _apply_plan_fused(plan: SpmmPlan, x, w, w_mat, *, interpret: bool):
    """apply_plan with the LD reductions fused with ``@ w_mat``.

    Output is (N, H) = (sum_e w_e x[src_e] into rows) @ w_mat, with the
    aggregated (N, F) intermediate never materialised for LD rows.
    Assembly is scatter-free (inverse count-sort permutation).
    """
    from repro.kernels.groot_spmm import F_TILE, hd_apply

    PROBE["edge_stream_gathers"] += 1
    PROBE["kernel_walks"] += 1
    if w is not None:
        PROBE["weight_gathers"] += 1
    n, f = x.shape
    h = w_mat.shape[1]
    f_extra = -f % F_TILE
    h_extra = -h % F_TILE
    x_p = pad_features(x)
    w_p = None if w is None else jnp.pad(w.astype(x.dtype), (0, 1))
    wm_p = jnp.pad(w_mat.astype(jnp.float32), ((0, f_extra), (0, h_extra)))

    def gather(cols, eids):
        g = jnp.take(x_p, jnp.asarray(cols), axis=0)
        if w_p is not None:
            g = g * jnp.take(w_p, jnp.asarray(eids), axis=0)[:, None]
        return g

    parts = []
    for b in plan.buckets:
        msgs = gather(b.cols, b.eids)
        parts.append(
            fused_ld_matmul(msgs, wm_p, b.deg, b.rows_per_tile, interpret=interpret)
        )
    if plan.hd is not None:
        msgs = gather(plan.hd.cols, plan.hd.eids)
        red = hd_apply(
            msgs, plan.hd.chunk_meta, len(plan.hd.rows), plan.e_t, interpret=interpret
        )
        parts.append(red[:, :f] @ wm_p[:f, :])
    out = assemble_rows(plan, parts, h + h_extra)
    return out[:, :h].astype(x.dtype)


def _apply_plan_fused_grouped_staged(
    plan: SpmmPlan, x_p, staged: StagedWeights, wm_p, *, interpret: bool
):
    """Hoisted grouped fused walk: pre-padded features, pre-staged weight
    streams, and a pre-padded ``(G, F_pad, H_pad)`` weight stack in;
    ``(N, H_pad)`` f32 out.

    One gather of the edge stream and one walk of the bucket schedule
    serve all G groups; per LD slab the grouped fused kernel keeps every
    group's (R_t, F) aggregate in VMEM and sums the G MXU products before
    the single (R_t, H_t) store.  HD rows reduce through the grouped HD
    kernel and contract with the weight stack outside (HD rows are few).
    Output assembly is one permutation gather — no scatters.
    """
    PROBE["edge_stream_gathers"] += 1
    PROBE["kernel_walks"] += 1
    f_pad = x_p.shape[1]
    h_pad = wm_p.shape[2]
    PROBE["stream_bytes"] += plan.num_slots * f_pad * x_p.dtype.itemsize
    parts = []
    for b, wge in zip(plan.buckets, staged.buckets):
        msgs = jnp.take(x_p, jnp.asarray(b.cols), axis=0)
        parts.append(
            fused_ld_matmul_grouped(
                msgs, wge, wm_p, b.deg, b.rows_per_tile, interpret=interpret
            )
        )
    if plan.hd is not None:
        msgs = jnp.take(x_p, jnp.asarray(plan.hd.cols), axis=0)
        red = hd_grouped_apply(
            msgs, staged.hd, plan.hd.chunk_meta, len(plan.hd.rows), plan.e_t,
            interpret=interpret,
        )  # (G, n_hd, F_pad); pad lanes are zero, so the full-F_pad
        # contraction against the zero-padded stack is exact
        parts.append(jnp.einsum("gnf,gfh->nh", red, wm_p))
    return assemble_rows(plan, parts, h_pad)


def _apply_plan_fused_grouped(plan: SpmmPlan, x, wg, w_stack, *, interpret: bool):
    """Grouped fused path: ``sum_g (group-g aggregation) @ w_stack[g]``.

    Stages the weight streams and pads per call — the pre-hoist walk the
    hoisted forward replaces (kept for the per-call API and as the
    bit-exactness oracle of the hoisting refactor).
    """
    h = w_stack.shape[2]
    staged = stage_group_weights(plan, wg)
    out = _apply_plan_fused_grouped_staged(
        plan,
        pad_features(x),
        staged,
        ForwardPlan.pad_weight_stack(w_stack),
        interpret=interpret,
    )
    return out[:, :h].astype(x.dtype)


# ---------------------------------------------------------------------------
# Padded-shape entry points (the service scheduler's bucketing contract).
#
# jit specialises on array shapes: serving many differently-sized graphs
# through the same compiled GNN requires padding every graph to a small
# set of canonical (nodes, edges) shapes.  The contract that keeps padded
# inference *exact* for real rows:
#
#   * padded feature rows are zero and are never aggregated into real rows;
#   * padded edges are self-loops on a dummy node (>= num_real), so every
#     aggregation/degree a real node sees is identical to the unpadded run.
# ---------------------------------------------------------------------------

def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    return 1 if n <= 1 else 1 << int(n - 1).bit_length()


def padded_shape(
    num_nodes: int, num_edges: int, *, min_nodes: int = 16, min_edges: int = 16
) -> tuple[int, int]:
    """Power-of-two (nodes, edges) padding target.

    Nodes round up from ``num_nodes + 1``: at least one spare row is
    guaranteed, which is where padding edges park their endpoints.
    """
    n_pad = next_pow2(max(num_nodes + 1, min_nodes))
    e_pad = next_pow2(max(num_edges, min_edges, 1))
    return n_pad, e_pad


def pad_graph_arrays(
    edge_src,
    edge_dst,
    edge_inv,
    edge_slot,
    num_nodes: int,
    n_pad: int,
    e_pad: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad COO edge arrays to length ``e_pad`` for a ``n_pad``-row graph.

    Padding edges are self-loops on the dummy row ``n_pad - 1``; missing
    inv/slot annotations come back as zeros (dense arrays keep the jit
    signature uniform across designs that do / don't carry them).
    """
    e = len(edge_src)
    if n_pad <= num_nodes or e_pad < e:
        raise ValueError(
            f"padded shape ({n_pad}, {e_pad}) cannot hold graph "
            f"({num_nodes} nodes, {e} edges)"
        )
    dummy = n_pad - 1
    pad = e_pad - e
    src = np.concatenate([edge_src, np.full(pad, dummy)]).astype(np.int32)
    dst = np.concatenate([edge_dst, np.full(pad, dummy)]).astype(np.int32)
    inv = np.zeros(e_pad, dtype=bool)
    if edge_inv is not None:
        inv[:e] = edge_inv
    slot = np.zeros(e_pad, dtype=np.uint8)
    if edge_slot is not None:
        slot[:e] = edge_slot
    return src, dst, inv, slot


def _build_pair(edge_src, edge_dst, num_nodes: int, backend: str,
                use_cache: bool) -> AggPair:
    if backend == "ref":
        return _segment_pair(edge_src, edge_dst, num_nodes)
    if backend == "onehot":
        return _onehot_pair(edge_src, edge_dst, num_nodes)
    variants = {
        "groot": dict(mxu=False, fused=False),
        "groot_mxu": dict(mxu=True, fused=False),
        "groot_fused": dict(mxu=False, fused=True),
    }
    if backend not in variants:
        raise ValueError(f"unknown backend {backend!r} (want one of {BACKENDS})")
    return _groot_pair(
        edge_src, edge_dst, num_nodes, **variants[backend],
        interpret=pallas_interpret(), use_cache=use_cache,
    )


def make_agg_pair(
    edge_src, edge_dst, num_nodes: int, backend: str = "ref", *, use_cache: bool = True
) -> AggPair:
    """Build (or fetch) the aggregation pair for a graph under a backend.

    When the edge arrays are concrete host numpy, the pair comes from the
    process-wide structural :data:`~repro.kernels.plan_cache.PLAN_CACHE`:
    the same structure always yields the *same object*, so jit callers
    holding the pair as a static argument hit their compile cache instead
    of retracing (``predict_partitioned`` over recurring subgraphs, the
    service scheduler over recurring packed batches).  Traced inputs
    (e.g. the onehot backend built inside a jitted forward) bypass the
    cache — they cannot be content-hashed.
    """
    cacheable = (
        use_cache
        and isinstance(edge_src, np.ndarray)
        and isinstance(edge_dst, np.ndarray)
    )
    if not cacheable:
        return _build_pair(edge_src, edge_dst, num_nodes, backend, use_cache=False)
    key = ("pair", pc.graph_key(edge_src, edge_dst, num_nodes), backend)
    return pc.PLAN_CACHE.get_or_build(
        key,
        lambda: _build_pair(edge_src, edge_dst, num_nodes, backend, use_cache=True),
    )


def groot_spmm(
    x,
    edge_src,
    edge_dst,
    num_nodes: int,
    w=None,
    *,
    backend="groot",
    use_cache: bool = True,
):
    """One-shot SpMM through the GROOT kernels (for tests/benches;
    persistent users should hold an :class:`AggPair`).

    The plan comes from the process-wide structural
    :data:`~repro.kernels.plan_cache.PLAN_CACHE`: a recurring structure
    builds nothing.  Pass ``use_cache=False`` to force a cold plan build
    (benchmarks that time host-side plan construction).
    """
    pair = make_agg_pair(
        np.asarray(edge_src), np.asarray(edge_dst), num_nodes, backend,
        use_cache=use_cache,
    )
    return pair.in_agg(jnp.asarray(x), None if w is None else jnp.asarray(w))
