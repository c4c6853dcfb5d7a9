"""Plain reference of the classifier run on partitioned subgraphs with boundary
edge re-growth.

Written from arXiv 2511.18297 §III-C (Algorithm 1, Eq. 1-2).  It imports
nothing of the system under test: the partition arrives as one id per node,
the subgraphs are built here from the design's edges, and each is classified
by :mod:`references.sage_polarity`.  For a partition with core S_p:

  ``regrow`` false       nodes S_p; edges E[S_p], both ends in the core.
  ``regrow``, hops 1     the paper's method.  B_p = N(S_p) \\ S_p (Eq. 1),
                         C_p the edges with one end in S_p and one in B_p
                         (Eq. 2); nodes S_p u B_p, edges E[S_p] u C_p: every
                         edge with at least one end in the core.  An edge
                         between two halo nodes is left out.
  ``regrow``, hops h > 1 iterated growth: nodes A = N^h(S_p), edges E[A],
                         every edge with both ends in A, halo to halo too.
                         Unlike hops 1, the halo's own edges feed the halo's
                         representations and degree norms, so once h is at
                         least the number of layers every core node sees its
                         whole receptive field and the core rows equal the
                         full graph's.

A node keeps the features it has in the whole design; the degree norms are
those of its subgraph.  The logits of node v are the rows of v in the
subgraph whose core holds v.  The subgraphs run as one disjoint union, one
relabelled copy of each, since no edge joins two copies.
"""
from __future__ import annotations

import numpy as np

from references import sage_polarity


def grown(src: np.ndarray, dst: np.ndarray, core: np.ndarray, hops: int) -> np.ndarray:
    """Mask of N^hops(core): the core and every node within ``hops`` edges."""
    nodes = core.copy()
    for _ in range(hops):
        touch = nodes[src] | nodes[dst]
        nodes[src[touch]] = True
        nodes[dst[touch]] = True
    return nodes


def subgraph(g: dict, part: np.ndarray, p: int, *, regrow: bool, hops: int):
    """(node ids, core first; the core's size; mask of the kept edges) of
    partition p."""
    src, dst = g["src"], g["dst"]
    core = part == p
    if not regrow:
        nodes, edges = core, core[src] & core[dst]
    elif hops == 1:
        edges = core[src] | core[dst]
        nodes = core.copy()
        nodes[src[edges]] = True
        nodes[dst[edges]] = True
    else:
        nodes = grown(src, dst, core, hops)
        edges = nodes[src] & nodes[dst]
    core_ids = np.flatnonzero(core)
    return np.concatenate([core_ids, np.flatnonzero(nodes & ~core)]), len(core_ids), edges


def logits(params, x: np.ndarray, g: dict, part: np.ndarray, *, regrow: bool,
           hops: int) -> np.ndarray:
    """(n, classes) float32 logits at highest precision, each node's row from
    the subgraph whose core holds it.  ``part`` gives every node its
    partition's id, 0..k-1."""
    n = g["n"]
    local = np.empty(n, np.int64)
    union = {"src": [], "dst": [], "inv": [], "slot": []}
    xs, rows = [], np.empty(n, np.int64)
    offset = 0
    for p in range(int(part.max()) + 1):
        ids, n_core, edges = subgraph(g, part, p, regrow=regrow, hops=hops)
        local[ids] = offset + np.arange(len(ids))
        rows[ids[:n_core]] = local[ids[:n_core]]
        union["src"].append(local[g["src"][edges]])
        union["dst"].append(local[g["dst"][edges]])
        union["inv"].append(g["inv"][edges])
        union["slot"].append(g["slot"][edges])
        xs.append(x[ids])
        offset += len(ids)
    ug = {k: np.concatenate(v).astype(g[k].dtype) for k, v in union.items()}
    ug["n"] = offset
    return sage_polarity.logits(params, np.concatenate(xs), ug)[rows]
