"""Operation and byte counts against hand counts on a tiny graph."""
import work

# 3 nodes, 2 edges, widths 4 -> 8 -> 8, 5 classes
GNN = {"in_features": 4, "hidden": 8, "num_layers": 2, "num_classes": 5}
N, E = 3, 2


def test_model_flops_by_hand():
    layer1 = 7 * 2 * 3 * 4 * 8 + 2 * 2 * 4 + 6 * 3 * 4    # 1344 + 16 + 72
    layer2 = 7 * 2 * 3 * 8 * 8 + 2 * 2 * 8 + 6 * 3 * 8    # 2688 + 32 + 144
    head = 2 * 3 * 8 * 5                                  # 240
    assert layer1 + layer2 + head == 4536
    assert work.model_flops(N, E, GNN) == 4536


def test_agg_counts_by_hand():
    assert work.agg_flops(N, E, GNN) == 2 * 2 * 4 + 2 * 2 * 8 == 48
    # per layer, two directions, 4 bytes each: read N*F, write N*F, 3 per edge
    layer1 = 2 * 4 * (3 * 4 + 3 * 4 + 3 * 2)    # 240
    layer2 = 2 * 4 * (3 * 8 + 3 * 8 + 3 * 2)    # 432
    assert work.agg_bytes(N, E, GNN) == layer1 + layer2 == 672


def test_least_seconds_names_its_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.least_seconds(50, 100, peak) == (10.0, "bytes")
    assert work.least_seconds(5000, 100, peak) == (50.0, "flops")


def test_counts_scale_with_the_graph():
    # a tiled batch does exactly batch times the work of one copy
    for f in (work.model_flops, work.agg_flops, work.agg_bytes):
        assert f(16 * N, 16 * E, GNN) == 16 * f(N, E, GNN)
