"""Plain reference of the polarity-separated GraphSAGE node classifier.

Written from the model's equations (arXiv 2511.18297 §III), in plain
``jax.numpy`` with segment sums and no kernels, plans or padding.  It imports
nothing of the system under test.  Per layer, for node u with activations h:

    h'_u = relu( W_s h_u + b
                 + sum_{g in fanin groups}  W_g mean_{v -> u, e in g} h_v
                 + sum_{g in fanout groups} W_g mean_{u -> v, e in g} h_v )

The four fanin groups split a node's incoming edges by fanin slot (left,
right) and polarity (plain, inverted); the two fanout groups split its
outgoing edges by polarity.  A mean over an empty group is 0.  The logits are
``h W_head + b_head`` after the last layer.

The 4-bit node features: bits 0-1 the node type (PI 00, AND 11, PO 0X with X
the polarity of its driver), bits 2-3 the input polarities (AND: left and
right inverted; PI 00; PO 11).

Training follows the recipe the configuration states: cross-entropy over every
node of a small design, AdamW with global-norm clipping, full batch, run as
one jitted scan on the device from the recipe's own fixed key, so every run
serves the same trained model.  A run's seed then relabels the hidden units
(:func:`relabel_hidden`): different weight arrays, the same function, hence
the same predictions and the same work downstream of them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

IN_GROUPS = ("w_in_l_pos", "w_in_l_neg", "w_in_r_pos", "w_in_r_neg")
OUT_GROUPS = ("w_out_pos", "w_out_neg")
_PI, _AND, _PO = 0, 1, 2


def graph(design: dict) -> dict:
    """fanin -> node edges with polarity and fanin slot, as numpy arrays."""
    kind, f0, f1 = design["kind"], design["fanin0"], design["fanin1"]
    ands = np.flatnonzero(kind == _AND)
    pos = np.flatnonzero(kind == _PO)
    lits = np.concatenate([f0[ands], f1[ands], f0[pos]])
    return {
        "n": int(kind.shape[0]),
        "src": (lits >> 1).astype(np.int32),
        "dst": np.concatenate([ands, ands, pos]).astype(np.int32),
        "inv": (lits & 1).astype(np.float32),
        "slot": np.concatenate([np.zeros(len(ands)), np.ones(len(ands)),
                                np.zeros(len(pos))]).astype(np.float32),
    }


def features(design: dict) -> np.ndarray:
    kind, f0, f1 = design["kind"], design["fanin0"], design["fanin1"]
    x = np.zeros((kind.shape[0], 4), np.float32)
    is_and, is_po = kind == _AND, kind == _PO
    x[is_and, 0] = x[is_and, 1] = 1.0
    x[is_po, 1] = f0[is_po] & 1
    x[is_and, 2] = f0[is_and] & 1
    x[is_and, 3] = f1[is_and] & 1
    x[is_po, 2] = x[is_po, 3] = 1.0
    return x


def init_params(gnn: dict, key) -> dict:
    """Uniform(+-1/sqrt(fan_in)) weights, zero biases."""
    dims = [gnn["in_features"]] + [gnn["hidden"]] * gnn["num_layers"]
    names = ("w_self",) + IN_GROUPS + OUT_GROUPS
    layers = []
    for i in range(gnn["num_layers"]):
        key, *keys = jax.random.split(key, 1 + len(names))
        s = 1.0 / np.sqrt(dims[i])
        layer = {nm: jax.random.uniform(k, (dims[i], dims[i + 1]), jnp.float32, -s, s)
                 for nm, k in zip(names, keys)}
        layer["b"] = jnp.zeros((dims[i + 1],), jnp.float32)
        layers.append(layer)
    key, kh = jax.random.split(key)
    s = 1.0 / np.sqrt(gnn["hidden"])
    head = {"w": jax.random.uniform(kh, (gnn["hidden"], gnn["num_classes"]),
                                    jnp.float32, -s, s),
            "b": jnp.zeros((gnn["num_classes"],), jnp.float32)}
    return {"layers": layers, "head": head}


def _logits(params, x, src, dst, inv, slot, n, precision):
    neg, right = inv, slot
    pos, left = 1 - neg, 1 - right
    in_w = dict(zip(IN_GROUPS, (left * pos, left * neg, right * pos, right * neg)))
    out_w = dict(zip(OUT_GROUPS, (pos, neg)))
    seg = lambda vals, idx: jax.ops.segment_sum(vals, idx, num_segments=n)
    inv_deg = lambda w, idx: 1 / jnp.maximum(seg(w, idx), 1)
    norm_in = {g: inv_deg(w, dst)[:, None] for g, w in in_w.items()}
    norm_out = {g: inv_deg(w, src)[:, None] for g, w in out_w.items()}
    dot = partial(jnp.dot, precision=precision)
    h = x
    for layer in params["layers"]:
        acc = dot(h, layer["w_self"]) + layer["b"]
        h_src, h_dst = h[src], h[dst]
        for g, w in in_w.items():
            acc = acc + dot(seg(h_src * w[:, None], dst) * norm_in[g], layer[g])
        for g, w in out_w.items():
            acc = acc + dot(seg(h_dst * w[:, None], src) * norm_out[g], layer[g])
        h = jax.nn.relu(acc)
    return dot(h, params["head"]["w"]) + params["head"]["b"]


_logits_jit = jax.jit(_logits, static_argnames=("n", "precision"))


def logits(params, x, g: dict, *, precision=jax.lax.Precision.HIGHEST) -> np.ndarray:
    """(n, classes) float32 logits on the default device."""
    out = _logits_jit(params, jnp.asarray(x), jnp.asarray(g["src"]), jnp.asarray(g["dst"]),
                      jnp.asarray(g["inv"]), jnp.asarray(g["slot"]), n=g["n"],
                      precision=precision)
    return np.asarray(out)


def relabel_hidden(params: dict, key) -> dict:
    """The same network with each layer's hidden units permuted from ``key``:
    columns of a layer and rows of the next move together, so the logits are
    unchanged up to the order of float sums."""
    out, prev = [], None
    for layer, k in zip(params["layers"], jax.random.split(key, len(params["layers"]))):
        perm = jax.random.permutation(k, layer["b"].shape[0])
        new = {nm: (w if prev is None else w[prev])[:, perm]
               for nm, w in layer.items() if nm != "b"}
        new["b"] = layer["b"][perm]
        out.append(new)
        prev = perm
    return {"layers": out, "head": {"w": params["head"]["w"][prev], "b": params["head"]["b"]}}


def weights(gnn: dict, recipe: dict, design: dict, relabel_key) -> dict:
    """The recipe's trained model, its hidden units relabelled from
    ``relabel_key``, in one device call."""
    g = graph(design)
    x = jnp.asarray(features(design))
    labels = jnp.asarray(design["label"].astype(np.int32))
    arrays = tuple(jnp.asarray(g[k]) for k in ("src", "dst", "inv", "slot"))
    return _weights(jax.random.key(recipe["key"]), relabel_key, x, arrays, labels,
                    gnn=tuple(sorted(gnn.items())), n=g["n"], epochs=recipe["epochs"],
                    lr=recipe["lr"], wd=recipe["weight_decay"], b1=recipe["b1"],
                    b2=recipe["b2"], eps=recipe["eps"], clip=recipe["grad_clip_norm"])


@partial(jax.jit, static_argnames=("gnn", "n", "epochs", "lr", "wd", "b1", "b2", "eps", "clip"))
def _weights(train_key, relabel_key, x, arrays, labels, *, gnn, **recipe):
    return relabel_hidden(_train(init_params(dict(gnn), train_key), x, arrays, labels, **recipe),
                          relabel_key)


def _train(params, x, arrays, labels, *, n, epochs, lr, wd, b1, b2, eps, clip):
    def loss(p):
        out = _logits(p, x, *arrays, n, None)
        logp = jax.nn.log_softmax(out)
        return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()

    def step(carry, t):
        p, m, v = carry
        grads = jax.grad(loss)(p)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / (gnorm + 1e-12))
        grads = jax.tree.map(lambda gr: gr * scale, grads)
        m = jax.tree.map(lambda mm, gr: b1 * mm + (1 - b1) * gr, m, grads)
        v = jax.tree.map(lambda vv, gr: b2 * vv + (1 - b2) * gr * gr, v, grads)
        mhat, vhat = 1 / (1 - b1 ** t), 1 / (1 - b2 ** t)
        p = jax.tree.map(
            lambda pp, mm, vv: pp - lr * ((mm * mhat) / (jnp.sqrt(vv * vhat) + eps) + wd * pp),
            p, m, v)
        return (p, m, v), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    steps = jnp.arange(1, epochs + 1, dtype=jnp.float32)
    (params, _, _), _ = jax.lax.scan(step, (params, zeros, zeros), steps)
    return params
