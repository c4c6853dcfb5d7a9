"""Sharded streaming executor (repro.mesh): launch throughput vs devices.

One table, toward the paper's headline scale (a 1,024-bit CSA at batch
16 needs more than one accelerator's worth of launch bandwidth):

  * ``sharded_scaling`` — the same partition plan streamed across 1/2/4/8
    host devices: per-device launch balance, the modeled-launch speedup
    (``MeshPlan.modeled_speedup`` — total batches over the busiest
    lane's), compile probe, wall/pack/device seconds, and the verdict
    hash.

Gates (assertion-enforced, so the suite fails loudly in CI):

  * **verdict identity** — every device count produces a bit-identical
    prediction vector (sha256 over the int32 verdict);
  * **near-linear scaling** — modeled-launch speedup >= 1.6x at 2
    devices (the paper's partitions are independent, so the only loss is
    round-robin remainder imbalance);
  * **compile discipline** — <= num_buckets compile units TOTAL at every
    device count (the pmap program is shared by all lanes).

Wall time is reported but NOT gated across device counts: on a CPU the
"devices" are XLA host-platform fakes sharing the same physical cores,
so real wall scaling is not observable there — the modeled-launch metric
is the honest scaling signal (it is exact on real accelerators, where
lanes run concurrently).

Every device count runs in THIS process, through ``mesh_devices``, over
the powers of two up to ``jax.local_device_count()``: one process holds
all of a TPU host's chips, and a child could not reach a chip its
parent holds.  To fake devices on a CPU, the caller sets ``XLA_FLAGS``
for the whole run:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.bench_sharded [--quick]
"""
from __future__ import annotations

import argparse
import hashlib
import time

from benchmarks.common import print_table, save_table

#: the acceptance gate: modeled-launch speedup at 2 devices
MIN_SPEEDUP_AT_2 = 1.6


def device_grid() -> list[int]:
    """Powers of two up to the visible device count (at most 8)."""
    import jax

    visible = jax.local_device_count()
    return [d for d in (1, 2, 4, 8) if d <= visible]


def _run(params, plan, feats, num_nodes, capacity, devices) -> dict:
    from repro.core import gnn
    from repro.mesh import ShardedStreamingExecutor, build_mesh_plan

    mplan = build_mesh_plan(plan, devices, capacity)
    ex = ShardedStreamingExecutor(
        params, "ref", num_devices=devices, capacity=capacity)
    t0 = time.perf_counter()
    pred = ex.run_plan(plan, feats, gnn_cfg=gnn.GNNConfig())
    wall = time.perf_counter() - t0
    return {
        "devices": devices,
        "num_nodes": num_nodes,
        "num_buckets": plan.num_buckets,
        "batches": mplan.total_batches,
        "waves": len(mplan.waves),
        "lane_batches": list(mplan.lane_batches),
        "modeled_speedup": mplan.modeled_speedup,
        "modeled_peak_mb": mplan.per_device_peak_bytes(gnn.GNNConfig()) / 1e6,
        "compiles": ex.stats.compiles,
        "launches": ex.stats.launches,
        "wall_s": wall,
        "pack_s": ex.stats.pack_s,
        "device_s": ex.stats.device_s,
        "launches_per_s": ex.stats.launches / wall if wall else 0.0,
        "pred_sha": hashlib.sha256(pred.tobytes()).hexdigest()[:16],
    }


def bench_scaling(bits: int, k: int, capacity: int,
                  device_grid: list[int]) -> list[dict]:
    import jax

    from repro.core import aig as A, gnn
    from repro.core.features import groot_features
    from repro.exec import build_partition_plan

    d = A.csa_multiplier(bits)
    g = d.to_edge_graph()
    feats = groot_features(d)
    params = gnn.init_params(gnn.GNNConfig(), jax.random.key(0))
    plan = build_partition_plan(g, k, partitioner="multilevel", seed=0)
    rows = [
        _run(params, plan, feats, g.num_nodes, capacity, D) for D in device_grid
    ]
    for row in rows:
        row.update(bits=bits, k=k, capacity=capacity)
        row["lane_batches"] = "/".join(map(str, row["lane_batches"]))

    # gate 1: verdict identity across every device count
    hashes = {r["pred_sha"] for r in rows}
    assert len(hashes) == 1, f"verdict diverged across device counts: {rows}"
    # gate 2: near-linear modeled-launch scaling at 2 devices
    by_dev = {r["devices"]: r for r in rows}
    if 2 in by_dev:
        got = by_dev[2]["modeled_speedup"]
        assert got >= MIN_SPEEDUP_AT_2, (
            f"modeled-launch speedup at 2 devices {got:.2f} < "
            f"{MIN_SPEEDUP_AT_2} (lane balance regressed)"
        )
    # gate 3: compile discipline — shared program, not per-device
    for r in rows:
        assert r["compiles"] <= r["num_buckets"], (
            f"devices={r['devices']}: {r['compiles']} compiles > "
            f"{r['num_buckets']} buckets"
        )
    # monotonicity: more lanes never lower the modeled speedup
    speeds = [r["modeled_speedup"] for r in rows]
    assert all(b >= a - 1e-9 for a, b in zip(speeds, speeds[1:])), speeds
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="csa-64 instead of the csa-256 headline design")
    args = ap.parse_args(argv)

    if args.quick:
        bits, k, capacity = 64, 32, 2
    else:
        bits, k, capacity = 256, 16, 2
    rows = bench_scaling(bits, k, capacity, device_grid())
    print_table(
        f"sharded scaling: csa-{bits}, k={k}, capacity={capacity} "
        f"(modeled-launch speedup gated >= {MIN_SPEEDUP_AT_2}x at 2 devices)",
        rows,
    )
    save_table("sharded_scaling", rows)


if __name__ == "__main__":
    main()
