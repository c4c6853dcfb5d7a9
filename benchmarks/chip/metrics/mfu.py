"""The whole forward's share of the chip's peak FLOP/s: model FLOPs per
request (``work.py``) times the requests completed, over the traced window
times the peak."""

import work


def read(run):
    red = run.reduced
    if not red or red["window_s"] <= 0:
        return None
    flops = len(run.done) * work.model_flops(run.traffic.nodes, run.traffic.edges,
                                             run.config["gnn"])
    return 100.0 * flops / (red["window_s"] * run.peak["flops_per_s"])
