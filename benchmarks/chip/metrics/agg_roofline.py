"""Share of the roofline reached by the aggregation kernels: the least time
the chip's peaks allow for the aggregation work of every request in the
traced window (``work.py``, at the graph's true widths), over the summed
device time of the kernels ``agg_kernels.json`` names."""

import work


def read(run):
    red = run.reduced
    if not red or red["kernel_s"] <= 0:
        return None
    nodes, edges, gnn = run.traffic.nodes, run.traffic.edges, run.config["gnn"]
    n = len(run.done)
    least, _ = work.least_seconds(n * work.agg_flops(nodes, edges, gnn),
                                  n * work.agg_bytes(nodes, edges, gnn), run.peak)
    return 100.0 * least / red["kernel_s"]
