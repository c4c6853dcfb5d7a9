"""Operations and bytes that one forward of the node classifier needs.

Counted from the graph's own node and edge counts at the model's true
feature widths (``in_features`` for the first layer, ``hidden`` after), not
from any plan's padding, degree buckets or lane layout, so the count is the
same whatever implements it.

Per layer with input width F, output width H, N nodes and E edges:

* model FLOPs: the self matmul and the six group matmuls, ``7 * 2*N*F*H``;
  the two aggregations (fanin and fanout), one add per edge and feature,
  ``2 * E*F``; the mean normalisation, ``6 * N*F``.  The head adds
  ``2*N*H*C``.
* aggregation FLOPs: ``2 * E*F`` (the adds above).
* aggregation bytes (float32, per direction): read the node rows ``N*F``,
  read each edge's source index, destination index and group weight
  ``3*E``, write one aggregated plane ``N*F``.  A lower bound for any
  implementation: it writes at least one plane and reads every edge once.
"""
from __future__ import annotations

F32 = 4


def _widths(gnn: dict) -> list[int]:
    return [gnn["in_features"]] + [gnn["hidden"]] * (gnn["num_layers"] - 1)


def model_flops(nodes: int, edges: int, gnn: dict) -> int:
    h, flops = gnn["hidden"], 0
    for f in _widths(gnn):
        flops += 7 * 2 * nodes * f * h + 2 * edges * f + 6 * nodes * f
    return flops + 2 * nodes * h * gnn["num_classes"]


def agg_flops(nodes: int, edges: int, gnn: dict) -> int:
    return sum(2 * edges * f for f in _widths(gnn))


def agg_bytes(nodes: int, edges: int, gnn: dict) -> int:
    return sum(2 * F32 * (2 * nodes * f + 3 * edges) for f in _widths(gnn))


def least_seconds(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time and which of the two peaks bounds it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = bytes_ / peak["bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
