"""Quickstart: GROOT end-to-end through the `repro.api.Session` façade —
train the GNN on an 8-bit multiplier, then verify a larger one through
every execution route the session can take: full graph, partitioned with
and without re-growth, streamed under a device memory budget, and the
Pallas kernel backends.

    PYTHONPATH=src python examples/quickstart.py            # full demo
    PYTHONPATH=src python examples/quickstart.py --quick    # CI smoke run
"""
import argparse

from repro.api import Session, SessionConfig
from repro.compile_cache import enable_compile_cache

ap = argparse.ArgumentParser()
ap.add_argument("--quick", action="store_true",
                help="small bits / few epochs (the CI fast-lane smoke test)")
ap.add_argument("--trace", metavar="OUT.json", default=None,
                help="record every verify below and write a Chrome-trace "
                     "JSON (derived sessions share the base tracer)")
ap.add_argument("--chaos", action="store_true",
                help="seeded fault-injection smoke: transient device "
                     "faults through the service path must be retried "
                     "away without changing the result")
args = ap.parse_args()
enable_compile_cache()
BITS = 16 if args.quick else 32
EPOCHS = 120 if args.quick else 300

sess = Session(config=SessionConfig(dataset="csa", bits=BITS,
                                    trace=bool(args.trace)))

print("1) training GraphSAGE on the 8-bit CSA multiplier (paper's setup)...")
hist = sess.train("csa", 8, epochs=EPOCHS)
print(f"   final loss: {hist[-1][1]:.2e}")

print(f"2) verifying a {BITS}-bit CSA multiplier, unpartitioned...")
r = sess.verify()
print(f"   route: {r.routing.mode} — {r.routing.reason}")
print(f"   accuracy {r.accuracy:.2%}  memory {r.peak_memory_bytes/1e6:.1f} MB  "
      f"verdict: {r.verdict.status}")

print("3) same design, 8 partitions WITHOUT re-growth...")
r_no = sess.options(num_partitions=8, regrow=False).verify(verify=False)
print(f"   route: {r_no.routing.mode} (k={r_no.routing.k}, "
      f"{r_no.routing.num_buckets} buckets)")
print(f"   accuracy {r_no.accuracy:.2%}  memory {r_no.peak_memory_bytes/1e6:.1f} MB")

print("4) 8 partitions WITH boundary edge re-growth (paper Alg. 1)...")
r_re = sess.options(num_partitions=8, regrow=True).verify(verify=False)
print(f"   accuracy {r_re.accuracy:.2%}  memory {r_re.peak_memory_bytes/1e6:.1f} MB")
print(f"\n   re-growth recovered +{(r_re.accuracy - r_no.accuracy)*100:.2f}% accuracy")
print(f"   memory reduced {(1 - r_re.peak_memory_bytes / r.unpartitioned_memory_bytes)*100:.1f}% vs unpartitioned")

print("5) a device memory budget: the router partitions and streams to fit...")
import jax  # noqa: E402 — consulted for the device count and backend

n_devices = jax.local_device_count()
stream_mode = "sharded" if n_devices > 1 else "streamed"
budget = sess.options(memory_budget_bytes=r.unpartitioned_memory_bytes // 3)
decision = budget.explain()
print(f"   explain(): {decision.reason}")
r_st = budget.verify(verify=False)
assert r_st.routing.mode == decision.mode == stream_mode
print(f"   accuracy {r_st.accuracy:.2%}  "
      f"packed peak {r_st.routing.modeled_peak_bytes/1e6:.1f} MB  "
      f"compiles {r_st.exec_stats['compiles']}  "
      f"launches {r_st.exec_stats['launches']}")

if n_devices > 1:
    print(f"6) sharding the stream across {n_devices} devices (repro.mesh, "
          f"CI fakes them via XLA_FLAGS)...")
    shard = sess.options(num_partitions=8)
    d_sh = shard.explain()
    assert d_sh.mode == "sharded" and d_sh.mesh_devices == n_devices
    print(f"   explain(): {d_sh.reason}")
    r_sh = shard.verify(verify=False, return_predictions=True)
    r_1d = shard.options(mesh_devices=1).verify(
        verify=False, return_predictions=True)
    # the two gates CI holds the mesh to: a compile unit per BUCKET
    # shared by all lanes (never per device), and a bit-identical verdict
    assert r_sh.exec_stats["compiles"] <= d_sh.num_buckets, (
        r_sh.exec_stats["compiles"], d_sh.num_buckets)
    assert (r_sh.predictions == r_1d.predictions).all()
    print(f"   verdict bit-identical to the single-device route; "
          f"compiles {r_sh.exec_stats['compiles']} <= "
          f"{d_sh.num_buckets} buckets across {n_devices} devices")
else:
    print("6) sharding across devices: skipped (1 visible device; set "
          "XLA_FLAGS=--xla_force_host_platform_device_count=4 to fake a "
          "mesh on CPU)")

print("7) inference through the Pallas GROOT kernels "
      f"({'interpreted' if jax.default_backend() == 'cpu' else 'compiled'})...")
r_k = sess.options(backend="groot_fused").verify(
    bits=8 if args.quick else 16, verify=False
)
print(f"   accuracy {r_k.accuracy:.2%} (HD/LD degree-bucketed kernel path)")

if args.trace:
    sess.save_trace(args.trace)
    rep = sess.report()
    print(f"\n8) observability: {rep!r}")
    print(f"   trace written to {args.trace}")

if args.chaos:
    from repro import faults

    print("\n9) chaos smoke: two injected transient device faults, retried "
          "away (repro.faults)...")
    chaos = sess.options(launch_retries=3, retry_backoff_s=0.01)
    with faults.injected("service.device:every=1,kind=transient,max_fires=2,seed=5"):
        ticket = chaos.submit(bits=8, verify=False)
        rr = chaos.result(ticket, timeout=300)
    chaos.close()
    assert rr.status == "classified", f"chaos smoke failed: {rr.error}"
    retried = chaos.obs.metrics.snapshot()["counters"].get("service.retries", 0)
    assert retried == 2, f"expected exactly 2 replayed transients, saw {retried}"
    print(f"   survived {retried} injected faults; status {rr.status!r}, "
          f"accuracy {rr.accuracy:.2%}")
