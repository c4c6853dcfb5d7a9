"""GraphSAGE for AIG node classification (paper §III-C/D), in JAX.

Direction- and polarity-separated SAGE: each layer aggregates three
neighbourhoods with separate weights — non-inverted fanin edges, inverted
fanin edges, and fanout edges.  (AIGs are DAGs: a node's function-root
pattern lives in its *fanin* cone, and the paper's core domain insight is
that the *polarity* of input connections identifies XOR/MAJ structures.)

    h'_u = act( W_s h_u + W_in+ mean_{v->u, pos} h_v
                        + W_in- mean_{v->u, inv} h_v
                        + W_out mean_{u->v} h_v + b )

Aggregation (the SpMM that dominates runtime, §IV) is pluggable:
``aggregate_fn(x, edge_src, edge_dst, num_nodes, w=None)`` — pure-jnp
segment ops (ref), the Pallas GROOT kernel, or the XLA one-hot
formulation.  Inference on partitioned graphs runs per-subgraph and reads
back core-node rows only.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aig as A
from repro.core.graph import EdgeGraph
from repro.core.regrowth import Subgraph
from repro.kernels.plan_cache import PLAN_CACHE
from repro.obs import REGISTRY, span
from repro.training import optimizer as opt


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    in_features: int = 4
    hidden: int = 32
    num_layers: int = 4
    num_classes: int = A.NUM_CLASSES
    dtype: str = "float32"
    # dtype of the staged edge streams (hoisted weight streams + gathered
    # messages) on the groot* backends: "float32" (default, bit-exact) or
    # "bfloat16" (halves the per-layer gather traffic; kernels accumulate
    # in f32, parity bounds pinned by tests/test_forward_plan.py).
    # Honored by the pipeline/service/executor paths, which read the
    # config; direct ``gnn.forward``/``gnn.predict`` callers pass the
    # explicit ``stream_dtype=`` kwarg instead (forward never sees a
    # GNNConfig).
    stream_dtype: str = "float32"


IN_GROUPS = ("w_in_l_pos", "w_in_l_neg", "w_in_r_pos", "w_in_r_neg")
OUT_GROUPS = ("w_out_pos", "w_out_neg")


def init_params(cfg: GNNConfig, key) -> dict:
    dims = [cfg.in_features] + [cfg.hidden] * cfg.num_layers
    params = {"layers": []}
    for i in range(cfg.num_layers):
        names = ("w_self",) + IN_GROUPS + OUT_GROUPS
        key, *keys = jax.random.split(key, 1 + len(names))
        s = 1.0 / np.sqrt(dims[i])
        layer = {
            nm: jax.random.uniform(kk, (dims[i], dims[i + 1]), jnp.float32, -s, s)
            for nm, kk in zip(names, keys)
        }
        layer["b"] = jnp.zeros((dims[i + 1],), jnp.float32)
        params["layers"].append(layer)
    key, kh = jax.random.split(key)
    s = 1.0 / np.sqrt(cfg.hidden)
    params["head"] = {
        "w": jax.random.uniform(kh, (cfg.hidden, cfg.num_classes), jnp.float32, -s, s),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return params


# ---------------------------------------------------------------------------
# Aggregation backends.  Signature:
#   agg(x, edge_src, edge_dst, num_nodes, w=None) ->
#       sum over incoming edges of w_e * x[src] per dst row.
# ---------------------------------------------------------------------------

def segment_sum_agg(x, edge_src, edge_dst, num_nodes, w=None):
    """Reference: gather + segment-sum (what PyG/GNNAdvisor-style row
    parallel SpMM computes)."""
    msgs = x[edge_src]
    if w is not None:
        msgs = msgs * w[:, None]
    return jax.ops.segment_sum(msgs, edge_dst, num_segments=num_nodes)


def forward(
    params,
    x,
    edge_src,
    edge_dst,
    edge_inv=None,
    edge_slot=None,
    *,
    num_nodes: int,
    agg=None,
    stream_dtype: Optional[str] = None,
):
    """Full forward pass -> logits (num_nodes, num_classes).

    In-edges are aggregated in four (slot x polarity) groups.  Each AIG node
    has at most one edge per group, so group aggregation is *exact* ordered
    message passing (no mean washout) while remaining an SpMM — the same
    kernel serves all groups (weights select the group) plus the fanout
    direction, which is where the HD/LD degree polarization lives.

    ``agg`` is an :class:`repro.kernels.ops.AggPair` (or None for the
    segment-sum reference).  Paths, most specific wins:

      * **hoisted grouped** (``fwd_plan`` present — all ``groot*``
        backends): the grouped path below, plus everything layer-invariant
        hoisted out of the layer loop via the
        :class:`~repro.kernels.forward_plan.ForwardPlan`: the group-weight
        streams are staged into kernel layout ONCE per forward (2 weight
        gathers total, not 2 per layer), activations are padded once per
        layer and shared by both directions, and output assembly is a
        single scatter-free permutation gather.  ``stream_dtype="bfloat16"``
        stages the edge streams narrow (f32 accumulation in-kernel).
      * **grouped** (``in_agg_grouped`` present): the four fanin and two
        fanout groups are *channels of one SpMM*.  The ``(E, 4)`` /
        ``(E, 2)`` group-weight matrices are
        built once, the mean norms are folded into them (exact — every
        edge's destination norm is known per edge), and each layer issues
        ONE grouped aggregation per direction: 6 -> 2 edge-stream gathers
        and 6 -> 2 bucket-kernel walks per layer.  The per-group ``@ W``
        collapses to one ``einsum('gnf,gfh->nh')`` contraction (or is
        fused into the grouped kernel when ``in_agg_mm_grouped`` exists).
      * **fused per-group** (``in_agg_mm``): per-group ``agg @ W`` inside
        the kernel, norm folded into edge weights (post-scaling would be
        wrong: the aggregated row is never materialised).
      * **per-group loop** (ref/onehot/None): aggregate per group, then
        post-scale by the per-destination norm ((N,1) elementwise — under
        SPMD a per-edge gather of the (N,) norm array forces a 0.7 GB
        all-gather per group, measured in §Perf).
    """
    # Executes at trace time only (the body of every jitted caller —
    # _predict, the runner's _fwd — runs once per compilation), so this is
    # the process-wide compile probe all three routes share.
    REGISTRY.counter("gnn.forward_traces").inc()
    one = jnp.ones_like(edge_dst, dtype=x.dtype)
    w_neg = edge_inv.astype(x.dtype) if edge_inv is not None else jnp.zeros_like(one)
    w_pos = 1.0 - w_neg
    w_r = edge_slot.astype(x.dtype) if edge_slot is not None else jnp.zeros_like(one)
    w_l = 1.0 - w_r
    group_w = {
        "w_in_l_pos": w_l * w_pos,
        "w_in_l_neg": w_l * w_neg,
        "w_in_r_pos": w_r * w_pos,
        "w_in_r_neg": w_r * w_neg,
    }
    out_w = {"w_out_pos": w_pos, "w_out_neg": w_neg}

    in_grouped = getattr(agg, "in_agg_grouped", None)
    out_grouped = getattr(agg, "out_agg_grouped", None)
    if in_grouped is not None and out_grouped is not None:
        return _forward_grouped(
            params, x, edge_src, edge_dst, group_w, out_w, num_nodes, agg,
            stream_dtype=stream_dtype,
        )

    deg = lambda idx, w: jax.ops.segment_sum(w, idx, num_segments=num_nodes)
    with jax.named_scope("gnn.degree"):
        norm_in = {
            nm: (1.0 / jnp.maximum(deg(edge_dst, w), 1.0))[:, None]
            for nm, w in group_w.items()
        }
        norm_out = {
            nm: (1.0 / jnp.maximum(deg(edge_src, w), 1.0))[:, None]
            for nm, w in out_w.items()
        }

    if agg is None:
        in_agg = lambda h, w: segment_sum_agg(h, edge_src, edge_dst, num_nodes, w)
        out_agg = lambda h, w: segment_sum_agg(h, edge_dst, edge_src, num_nodes, w)
        in_agg_mm = None
    else:
        in_agg, out_agg, in_agg_mm = agg.in_agg, agg.out_agg, agg.in_agg_mm

    if in_agg_mm is not None:  # fused path: fold norms into edge weights
        with jax.named_scope("gnn.degree"):
            group_w = {nm: w * norm_in[nm][:, 0][edge_dst] for nm, w in group_w.items()}

    h = x
    for layer in params["layers"]:
        acc = h @ layer["w_self"] + layer["b"]
        for nm in IN_GROUPS:
            if in_agg_mm is not None:
                acc = acc + in_agg_mm(h, group_w[nm], layer[nm])
            else:
                acc = acc + (in_agg(h, group_w[nm]) * norm_in[nm]) @ layer[nm]
        for nm in OUT_GROUPS:
            acc = acc + (out_agg(h, out_w[nm]) * norm_out[nm]) @ layer[nm]
        h = jax.nn.relu(acc)
    return _head(params, h)


def _head(params, h):
    """The classifier head, under the ``gnn.head`` scope."""
    with jax.named_scope("gnn.head"):
        return h @ params["head"]["w"] + params["head"]["b"]


def _forward_grouped(params, x, edge_src, edge_dst, group_w, out_w, num_nodes, agg,
                     *, stream_dtype: Optional[str] = None):
    """Grouped hot path: one aggregation per direction per layer.

    Group weights become ``(E, G)`` matrices (column order = IN_GROUPS /
    OUT_GROUPS) with the per-destination mean norm folded in, so the
    grouped SpMM's output planes are already normalised and the layer
    reduces to ``einsum('gnf,gfh->nh')`` over the stacked group weights.

    When the pair carries a :class:`~repro.kernels.forward_plan.ForwardPlan`
    the loop below is replaced by :func:`_forward_hoisted`; this body is
    the pre-hoist walk, kept as the bit-exactness oracle
    (``ops.unhoisted(pair)`` routes here).
    """
    wg_in = jnp.stack([group_w[nm] for nm in IN_GROUPS], axis=1)     # (E, 4)
    wg_out = jnp.stack([out_w[nm] for nm in OUT_GROUPS], axis=1)     # (E, 2)
    # per-group in/out degrees in ONE segment-sum per direction (the
    # per-group path needs six)
    with jax.named_scope("gnn.degree"):
        deg_in = jax.ops.segment_sum(wg_in, edge_dst, num_segments=num_nodes)
        deg_out = jax.ops.segment_sum(wg_out, edge_src, num_segments=num_nodes)
        wg_in = wg_in * (1.0 / jnp.maximum(deg_in, 1.0))[edge_dst]
        wg_out = wg_out * (1.0 / jnp.maximum(deg_out, 1.0))[edge_src]

    fp = getattr(agg, "fwd_plan", None)
    if fp is not None and agg.in_agg_staged is not None:
        return _forward_hoisted(params, x, wg_in, wg_out, agg, fp, stream_dtype)

    h = x
    for layer in params["layers"]:
        acc = h @ layer["w_self"] + layer["b"]
        w_in_stack = jnp.stack([layer[nm] for nm in IN_GROUPS], axis=0)
        w_out_stack = jnp.stack([layer[nm] for nm in OUT_GROUPS], axis=0)
        if agg.in_agg_mm_grouped is not None:
            acc = acc + agg.in_agg_mm_grouped(h, wg_in, w_in_stack)
        else:
            gin = agg.in_agg_grouped(h, wg_in)                       # (4, N, F)
            acc = acc + jnp.einsum("gnf,gfh->nh", gin.astype(acc.dtype), w_in_stack)
        gout = agg.out_agg_grouped(h, wg_out)                        # (2, N, F)
        acc = acc + jnp.einsum("gnf,gfh->nh", gout.astype(acc.dtype), w_out_stack)
        h = jax.nn.relu(acc)
    return _head(params, h)


def _forward_hoisted(params, x, wg_in, wg_out, agg, fp, stream_dtype):
    """Hoisted grouped hot path: everything layer-invariant leaves the loop.

    The :class:`~repro.kernels.forward_plan.ForwardPlan` contract:

      * the fanin/fanout group-weight streams are staged into each
        bucket's ELL layout (and the HD chunk layout) ONCE — 2 weight
        gathers per FORWARD, so layers 2..L touch zero edge-weight bytes;
      * activations are padded once per layer, shared by both direction
        walks (pre-hoist each aggregation padded its own copy);
      * the fused path's per-layer weight stacks are padded in a prologue;
      * output assembly inside the staged walks is one permutation gather
        — zero ``.at[].add`` scatters per forward.

    ``stream_dtype="bfloat16"`` narrows the staged weight streams and the
    gathered messages; kernels accumulate in f32.
    """
    sdt = None
    if stream_dtype is not None and jnp.dtype(stream_dtype) != jnp.float32:
        sdt = jnp.dtype(stream_dtype)
    with jax.named_scope("gnn.stage"):
        sw_in = fp.stage_in(wg_in, dtype=sdt)
        sw_out = fp.stage_out(wg_out, dtype=sdt)
    layers = params["layers"]
    fused = agg.in_agg_mm_staged is not None
    stacks_in = [jnp.stack([l[nm] for nm in IN_GROUPS], axis=0) for l in layers]
    stacks_out = [jnp.stack([l[nm] for nm in OUT_GROUPS], axis=0) for l in layers]
    if fused:
        stacks_in = [fp.pad_weight_stack(s) for s in stacks_in]

    h = x
    for layer, w_in_stack, w_out_stack in zip(layers, stacks_in, stacks_out):
        acc = h @ layer["w_self"] + layer["b"]
        f = h.shape[1]
        h_p = fp.pad_x(h)
        if sdt is not None:
            h_p = h_p.astype(sdt)
        if fused:
            acc = acc + agg.in_agg_mm_staged(h_p, sw_in, w_in_stack)[
                :, : acc.shape[1]
            ].astype(acc.dtype)
        else:
            gin = agg.in_agg_staged(h_p, sw_in)[:, :, :f]            # (4, N, F)
            acc = acc + jnp.einsum("gnf,gfh->nh", gin.astype(acc.dtype), w_in_stack)
        gout = agg.out_agg_staged(h_p, sw_out)[:, :, :f]             # (2, N, F)
        acc = acc + jnp.einsum("gnf,gfh->nh", gout.astype(acc.dtype), w_out_stack)
        h = jax.nn.relu(acc)
    return _head(params, h)


def loss_fn(params, batch):
    logits = forward(
        params,
        batch["x"],
        batch["edge_src"],
        batch["edge_dst"],
        batch.get("edge_inv"),
        batch.get("edge_slot"),
        num_nodes=batch["x"].shape[0],
    )
    logp = jax.nn.log_softmax(logits)
    ll = jnp.take_along_axis(logp, batch["labels"][:, None], axis=1)[:, 0]
    mask = batch.get("mask")
    if mask is not None:
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return -ll.mean()


@partial(jax.jit, static_argnames=("optimizer",))
def train_step(params, state, batch, optimizer: opt.AdamW):
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    updates, state = optimizer.update(grads, state, params)
    params = opt.apply_updates(params, updates)
    return params, state, loss


def make_batch(design, features: np.ndarray, labels: np.ndarray) -> dict:
    g = design.to_edge_graph() if hasattr(design, "to_edge_graph") else design
    batch = {
        "x": jnp.asarray(features),
        "edge_src": jnp.asarray(g.edge_src),
        "edge_dst": jnp.asarray(g.edge_dst),
        "labels": jnp.asarray(labels.astype(np.int32)),
    }
    if g.edge_inv is not None:
        batch["edge_inv"] = jnp.asarray(g.edge_inv)
    if g.edge_slot is not None:
        batch["edge_slot"] = jnp.asarray(g.edge_slot)
    return batch


def train(
    params,
    batch: dict,
    *,
    epochs: int = 200,
    lr: float = 5e-3,
    log_every: int = 0,
) -> tuple[dict, list]:
    optimizer = opt.AdamW(lr=lr, weight_decay=1e-4)
    state = optimizer.init(params)
    history = []
    for e in range(epochs):
        params, state, loss = train_step(params, state, batch, optimizer)
        if log_every and (e % log_every == 0 or e == epochs - 1):
            history.append((e, float(loss)))
    return params, history


@partial(jax.jit, static_argnames=("num_nodes", "agg", "stream_dtype"))
def _predict(params, x, edge_src, edge_dst, edge_inv, edge_slot, num_nodes, agg,
             stream_dtype=None):
    return jnp.argmax(
        forward(
            params, x, edge_src, edge_dst, edge_inv, edge_slot,
            num_nodes=num_nodes, agg=agg, stream_dtype=stream_dtype,
        ),
        axis=-1,
    ).astype(jnp.int32)


def _make_agg(g, backend: str):
    """Build the kernel-backend aggregation pair for a graph (None = ref)."""
    if backend in (None, "ref"):
        return None
    from repro.kernels import ops

    return ops.make_agg_pair(g.edge_src, g.edge_dst, g.num_nodes, backend)


def _stage(arrays):
    """Host -> device for ``arrays`` (None entries stay None), and the
    bytes handed to the device."""
    staged = jax.device_put(arrays)
    return staged, sum(int(a.nbytes) for a in staged if a is not None)


def predict(
    params, design, features, backend: str = "ref",
    *, stream_dtype: Optional[str] = None,
) -> np.ndarray:
    """Full-graph predictions, in four spans: ``execute.plan`` (the
    aggregation pair, a plan-cache hit or a build), ``execute.stage``
    (host -> device of the features and edge arrays), ``execute.run``
    (dispatch until the device is done) and ``execute.fetch``
    (device -> host of the predictions)."""
    g = design.to_edge_graph() if hasattr(design, "to_edge_graph") else design
    REGISTRY.counter("gnn.predicts").inc()
    with span("execute.plan", backend=backend, nodes=g.num_nodes) as sp:
        builds = PLAN_CACHE.stats.builds
        agg = _make_agg(g, backend)
        if agg is not None:
            sp.set(hit=PLAN_CACHE.stats.builds == builds)
    with span("execute.stage") as sp:
        staged, nbytes = _stage(
            (np.asarray(features), g.edge_src, g.edge_dst, g.edge_inv, g.edge_slot)
        )
        x, src, dst, inv, slot = jax.block_until_ready(staged)
        sp.set(h2d_bytes=nbytes)
    REGISTRY.counter("gnn.bytes_staged").inc(nbytes)
    with span("execute.run"):
        out = jax.block_until_ready(_predict(
            params, x, src, dst, inv, slot, g.num_nodes, agg, stream_dtype,
        ))
    with span("execute.fetch"):
        return np.asarray(out)


def predict_partitioned(
    params,
    subgraphs: list[Subgraph],
    features: np.ndarray,
    num_nodes: int,
    backend: str = "ref",
    *,
    streaming: bool = True,
    capacity: int = 2,
    prefetch: int = 1,
    stream_dtype: Optional[str] = None,
) -> np.ndarray:
    """DEPRECATED: per-partition inference; core-node predictions only.

    Use :class:`repro.api.Session` (whose router picks the streamed or
    sequential path) or call
    :func:`repro.exec.stream.stream_predict_partitioned` /
    :func:`predict_partitioned_loop` directly.  Kept as a
    behaviour-preserving shim: each subgraph is an independent
    device-sized problem, streamed through the ``repro.exec`` executor by
    default, or run through the sequential per-subgraph loop with
    ``streaming=False`` — bit-exact on core rows either way.
    """
    import warnings

    warnings.warn(
        "gnn.predict_partitioned is deprecated; use repro.api.Session "
        "(or stream_predict_partitioned / predict_partitioned_loop)",
        DeprecationWarning,
        stacklevel=2,
    )
    if streaming:
        from repro.exec.stream import stream_predict_partitioned

        return stream_predict_partitioned(
            params, subgraphs, features, num_nodes, backend,
            capacity=capacity, prefetch=prefetch, stream_dtype=stream_dtype,
        )
    return predict_partitioned_loop(
        params, subgraphs, features, num_nodes, backend,
        stream_dtype=stream_dtype,
    )


def predict_partitioned_loop(
    params,
    subgraphs: list[Subgraph],
    features: np.ndarray,
    num_nodes: int,
    backend: str = "ref",
    *,
    stream_dtype: Optional[str] = None,
) -> np.ndarray:
    """Sequential reference: one unpadded device call per subgraph.

    Kept as the bit-exactness oracle for the streaming executor and as the
    baseline ``benchmarks/bench_partitioned.py`` measures against (it
    recompiles per subgraph shape and staging never overlaps the device).
    Predictions are int32 end-to-end (``_predict`` emits int32 argmax),
    matching the streamed path — parity never rides on an implicit upcast.
    """
    out = np.zeros(num_nodes, dtype=np.int32)
    for sg in subgraphs:
        (feats, src, dst, inv, slot), nbytes = _stage(
            (features[sg.global_ids], sg.edge_src, sg.edge_dst, sg.edge_inv,
             sg.edge_slot)
        )
        REGISTRY.counter("gnn.loop_launches").inc()
        REGISTRY.counter("gnn.bytes_staged").inc(nbytes)
        pred = _predict(
            params, feats, src, dst, inv, slot, sg.num_nodes,
            _make_agg(sg.to_edge_graph(), backend), stream_dtype,
        )
        out[sg.global_ids[: sg.num_core]] = np.asarray(pred)[: sg.num_core]
    return out


def accuracy(pred: np.ndarray, labels: np.ndarray) -> float:
    return float((pred == labels).mean())
