#!/usr/bin/env python3
"""Bring-up smoke test: the GROOT verifier on a TPU, through ``Session``.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # the sharded route only (4 chips)

One process holds the chip(s); nothing here starts a child.  Phases
(one chip, ``mesh_devices=1`` everywhere):

  1. device   — fail unless JAX's first device is a TPU (no CPU fallback);
  2. train    — ``Session.train("csa", 8)``: the paper's train-on-8-bit;
  3. full     — ``Session.verify`` of csa:256 and booth:256 through the
                compiled ``groot_fused`` kernels, compared node by node
                with the ``ref`` forward at highest matmul precision, and
                the verdict checked against ``simulation_check``;
  4. streamed — csa:512 under a memory budget that routes it to k >= 8
                partitions re-grown by num_layers hops (exact with the
                full graph in exact arithmetic), streamed one partition
                per launch through ``groot_fused``, compared with the
                full-graph ``ref`` predictions;
  5. service  — warmed ``backend="groot"`` and ``"ref"`` services answer
                a few designs and one resubmission: no error, the
                resubmission cached and compiling nothing, and no cold
                compile at all on ``ref``.

``--chips 4`` runs csa:512 streamed over ``mesh_devices=4`` and over
``mesh_devices=1`` in the same process, once with ``ref`` (the pmap
path) and once with ``groot_fused`` (the per-device jit path); the
predictions must be identical.

A failed phase exits non-zero.  The last line of a passing run is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
#: the most nodes whose predicted class may differ from the reference
MAX_DIFF_FRAC = 1e-3
TRAIN_EPOCHS = 300
#: CSA width of the streamed and sharded phases
STREAM_BITS = 512
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileMeter:
    """XLA compiles and their seconds, from JAX's monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self) -> tuple[int, float]:
        return self.count, self.seconds

    def since(self, mark: tuple[int, float]) -> str:
        return (f"compiles={self.count - mark[0]} "
                f"compile_s={self.seconds - mark[1]:.2f}")


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's device is {dev.platform!r}); "
                 f"this smoke test runs only on a TPU")
    return jax.devices()


def _graph_arrays(g):
    import jax.numpy as jnp

    return (jnp.asarray(g.edge_src), jnp.asarray(g.edge_dst),
            jnp.asarray(g.edge_inv), jnp.asarray(g.edge_slot))


def ref_logits(params, design, feats):
    """The oracle: the ``ref`` forward at highest matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import gnn

    g = design.to_edge_graph()
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x, s, d, i, sl: gnn.forward(
            p, x, s, d, i, sl, num_nodes=g.num_nodes))
        out = fwd(params, jnp.asarray(feats), *_graph_arrays(g))
    return np.asarray(out)


def kernel_logits(params, design, feats, backend):
    """The kernel forward at highest matmul precision, compiled once:
    (logits, compiled HLO text, the aggregation pair with its degree
    plans).  With every XLA matmul at f32 too, its distance from the
    oracle is the kernels' own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import gnn
    from repro.kernels import ops

    g = design.to_edge_graph()
    pair = ops.make_agg_pair(g.edge_src, g.edge_dst, g.num_nodes, backend)
    fwd = jax.jit(lambda p, x, s, d, i, sl: gnn.forward(
        p, x, s, d, i, sl, num_nodes=g.num_nodes, agg=pair))
    args = (params, jnp.asarray(feats), *_graph_arrays(g))
    with jax.default_matmul_precision("highest"):
        compiled = fwd.lower(*args).compile()
    return np.asarray(compiled(*args)), compiled.as_text(), pair


def compare(name, pred, want_pred, logits=None, want_logits=None) -> None:
    import numpy as np

    n = len(want_pred)
    diff = int((np.asarray(pred)[:n] != want_pred).sum())
    line = f"{name}: pred_diff_nodes={diff}/{n} ({diff / n:.6%})"
    if logits is not None:
        line += f" max_logit_diff={float(np.abs(logits - want_logits).max()):.6g}"
    log(line)
    check(diff <= MAX_DIFF_FRAC * n,
          f"{name}: {diff} of {n} nodes differ from ref (limit {MAX_DIFF_FRAC:.1%})")


def check_verdict(name, result, design, signed) -> None:
    from repro.core.verify import simulation_check

    bits = design.n_pi // 2
    t0 = time.perf_counter()
    sim_ok = simulation_check(design, bits, signed)
    status = result.verdict.status
    log(f"{name}: verdict={status} coverage={result.verdict.coverage:.4f} "
        f"adders={result.verdict.n_adders} simulation_check={sim_ok} "
        f"sim_s={time.perf_counter() - t0:.2f}")
    # "inconclusive" claims nothing; a verdict that claims must agree
    check(not (status == "verified" and not sim_ok),
          f"{name}: verified, but simulation disagrees")
    check(not (status == "falsified" and sim_ok),
          f"{name}: falsified, but simulation agrees with the spec")


def phase_train(sess, epochs, meter):
    t0, m = time.perf_counter(), meter.mark()
    hist = sess.train("csa", 8, epochs=epochs)
    log(f"[train] csa:8 epochs={epochs} final_loss={hist[-1][1]:.6g} "
        f"seconds={time.perf_counter() - t0:.2f} {meter.since(m)}")


def phase_full(sess, designs, meter):
    from repro.core import aig as A
    from repro.core.features import groot_features

    params = sess.params
    full = sess.options(backend="groot_fused", mesh_devices=1)
    for dataset, bits in designs:
        name = f"{dataset}:{bits}"
        t0, m = time.perf_counter(), meter.mark()
        r = full.verify(dataset=dataset, bits=bits, return_predictions=True)
        check(r.routing.mode == "full", f"{name}: routed {r.routing.mode}")
        log(f"[full] {name}: nodes={r.num_nodes} edges={r.num_edges} "
            f"accuracy={r.accuracy:.6f} verify_s={time.perf_counter() - t0:.2f} "
            f"{meter.since(m)}")
        design = A.make_design(dataset, bits, seed=sess.config.seed)
        feats = groot_features(design)
        t0, m = time.perf_counter(), meter.mark()
        logits, hlo, pair = kernel_logits(params, design, feats, "groot_fused")
        calls = [ln for ln in hlo.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        kernels = len(calls)
        # the HD kernels are the only ones with a scalar-prefetch operand
        hd_calls = sum("operand_layout_constraints={s32[" in ln for ln in calls)
        hd = pair.out_plan.hd
        log(f"[full] {name}: tpu_custom_calls={kernels} hd_kernel_calls={hd_calls} "
            f"fanout_hd_rows={0 if hd is None else len(hd.rows)} "
            f"fanout_hd_chunks={0 if hd is None else hd.num_chunks} "
            f"seconds={time.perf_counter() - t0:.2f} {meter.since(m)}")
        check(kernels > 0, f"{name}: no tpu_custom_call in the compiled forward")
        check((hd is None) == (hd_calls == 0),
              f"{name}: HD plan and HD kernel calls disagree")
        t0, m = time.perf_counter(), meter.mark()
        want = ref_logits(params, design, feats)
        log(f"[full] {name}: ref_highest_s={time.perf_counter() - t0:.2f} "
            f"{meter.since(m)}")
        compare(f"[full] {name} session-vs-ref", r.predictions, want.argmax(-1))
        compare(f"[full] {name} kernel-highest-vs-ref", logits.argmax(-1),
                want.argmax(-1), logits, want)
        check_verdict(f"[full] {name}", r, design, signed=dataset == "booth")
        if dataset == "booth":
            check(hd is not None and hd_calls > 0,
                  f"{name}: the HD kernel did not run")


def phase_streamed(sess, bits, k, meter):
    from repro.core import aig as A
    from repro.core.features import groot_features
    from repro.exec.plan import HALO_FRAC, _estimated_batch_bytes

    hops = sess.config.gnn.num_layers
    name = f"csa:{bits}"
    design = A.make_design("csa", bits, seed=sess.config.seed)
    g = design.to_edge_graph()
    # One partition per launch.  A num_layers-hop halo covers ~60% of a
    # CSA multiplier (every input bit fans out to a row of partial
    # products), so at csa:512 one partition pads to 2^21 nodes; packed
    # two to a launch, its compiled forward needs 16.5 GB of temporaries
    # (compiled for a v5e), more than the chip's 16 GB of HBM.
    capacity = 1
    # The budget the router's own estimate fits at a k-way cut, so
    # choose_k starts at k.  The real halo is larger than the estimate:
    # prepare() then tries finer cuts and streams at the best one.
    budget = _estimated_batch_bytes(
        g.num_nodes, g.num_edges, k, sess.config.gnn, capacity,
        halo_frac=HALO_FRAC * hops, min_nodes=sess.config.min_nodes,
        min_edges=sess.config.min_edges)
    streamed = sess.options(backend="groot_fused", mesh_devices=1,
                            memory_budget_bytes=budget, regrow_hops=hops,
                            stream_capacity=capacity)
    t0, m = time.perf_counter(), meter.mark()
    r = streamed.verify(design, verify=False, return_predictions=True)
    st = r.exec_stats
    log(f"[streamed] {name}: mode={r.routing.mode} k={r.routing.k} hops={hops} "
        f"budget_bytes={budget} modeled_peak_bytes={r.routing.modeled_peak_bytes} "
        f"buckets={list(r.routing.buckets)} nodes={r.num_nodes} "
        f"accuracy={r.accuracy:.6f} launches={st.get('launches')} "
        f"executor_compiles={st.get('compiles')} "
        f"prepare_s={sum(v for key, v in r.timings.items() if key in ('gen', 'partition')):.2f} "
        f"infer_s={r.timings['inference']:.2f} "
        f"total_s={time.perf_counter() - t0:.2f} {meter.since(m)}")
    check(r.routing.mode == "streamed" and r.routing.k >= k,
          f"{name}: routed {r.routing.mode} with k={r.routing.k}")
    t0, m = time.perf_counter(), meter.mark()
    want = ref_logits(sess.params, design, groot_features(design))
    log(f"[streamed] {name}: full_ref_highest_s={time.perf_counter() - t0:.2f} "
        f"{meter.since(m)}")
    compare(f"[streamed] {name} streamed-vs-full-ref", r.predictions,
            want.argmax(-1))


def phase_service(params, seed, meter):
    """The batched service on a warmed engine.

    Warmup compiles one program per bucket shape.  For the shape-stable
    ``ref`` backend that covers every design of those shapes, so no
    submit may pay a cold compile.  The ``groot`` backend embeds each packed
    structure's degree plan in its program, so a design seen for the
    first time compiles once (ROADMAP A3) and a resubmission compiles
    nothing."""
    from repro.api import Session, SessionConfig
    from repro.core import aig as A
    from repro.kernels import ops

    jobs = [("csa", 32), ("booth", 16), ("csa", 32)]
    cfg = SessionConfig(warmup=True, mesh_devices=1, seed=seed)
    # the bucket shapes these designs pack into: what warmup compiles
    shapes = []
    for dataset, bits in jobs:
        g = A.make_design(dataset, bits, seed=seed).to_edge_graph()
        shapes.append(ops.padded_shape(g.num_nodes, g.num_edges,
                                       min_nodes=cfg.min_nodes,
                                       min_edges=cfg.min_edges))
    cfg = cfg.replace(warmup_shapes=tuple(sorted(set(shapes))))
    for backend, max_cold in (("groot", 2), ("ref", 0)):
        t0, m = time.perf_counter(), meter.mark()
        svc = Session(params, cfg.replace(backend=backend))
        try:
            svc.warm()
            log(f"[service] {backend}: warmup_s={time.perf_counter() - t0:.2f} "
                f"{meter.since(m)}")
            t0, m = time.perf_counter(), meter.mark()
            results, cold = [], []
            # one at a time: the resubmission finds the first run finished
            for dataset, bits in jobs:
                ticket = svc.submit(dataset=dataset, bits=bits)
                results.append(svc.result(ticket, timeout=600))
                cold.append(svc.stats()["service"]["cold_compiles"])
        finally:
            svc.close()
        for (dataset, bits), res in zip(jobs, results):
            log(f"[service] {backend} {dataset}:{bits}: status={res.status} "
                f"cached={res.cached} accuracy={res.accuracy:.6f}")
            check(res.status != "error",
                  f"service {backend} {dataset}:{bits}: {res.error}")
        log(f"[service] {backend}: cold_compiles={cold[-1]} "
            f"seconds={time.perf_counter() - t0:.2f} {meter.since(m)}")
        check(results[2].cached, f"service {backend}: resubmission not cached")
        check(cold[2] == cold[1],
              f"service {backend}: the resubmission compiled")
        check(cold[-1] <= max_cold,
              f"service {backend}: {cold[-1]} cold compiles after warmup "
              f"(limit {max_cold})")


def phase_sharded(params, bits, k, devices, seed, meter):
    from repro.api import Session, SessionConfig
    from repro.core import aig as A

    design = A.make_design("csa", bits, seed=seed)
    sess = Session(params, SessionConfig(num_partitions=k, seed=seed))
    for backend in ("ref", "groot_fused"):
        preds = {}
        for d in (devices, 1):
            t0, m = time.perf_counter(), meter.mark()
            r = sess.options(backend=backend, mesh_devices=d).verify(
                design, verify=False, return_predictions=True)
            want_mode = "sharded" if d > 1 else "streamed"
            check(r.routing.mode == want_mode,
                  f"{backend} mesh_devices={d}: routed {r.routing.mode}")
            preds[d] = r.predictions
            log(f"[sharded] csa:{bits} {backend} mesh_devices={d}: "
                f"mode={r.routing.mode} k={r.routing.k} nodes={r.num_nodes} "
                f"launches={r.exec_stats.get('launches')} "
                f"infer_s={r.timings['inference']:.2f} "
                f"total_s={time.perf_counter() - t0:.2f} {meter.since(m)}")
        same = int((preds[devices] != preds[1]).sum())
        log(f"[sharded] csa:{bits} {backend}: {devices}-vs-1 pred_diff_nodes={same}")
        check(same == 0, f"{backend}: {devices}-device predictions differ "
                         f"from 1-device on {same} nodes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded route, 4 devices vs 1")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the designs, the training and the weights")
    args = ap.parse_args(argv)

    devices = require_tpu()
    import jax

    sys.path.insert(0, str(REPO / "src"))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} compile_cache={cache_dir}")
    if len(devices) < args.chips:
        raise SmokeFailure(f"--chips {args.chips} but {len(devices)} device(s)")

    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter)
    from repro.api import Session, SessionConfig

    t_start = time.perf_counter()
    if args.chips == 4:
        from repro.core import gnn

        params = gnn.init_params(gnn.GNNConfig(), jax.random.key(args.seed))
        phase_sharded(params, STREAM_BITS, 8, 4, args.seed, meter)
    else:
        sess = Session(config=SessionConfig(mesh_devices=1, seed=args.seed))
        phase_train(sess, TRAIN_EPOCHS, meter)
        phase_full(sess, [("csa", 256), ("booth", 256)], meter)
        phase_streamed(sess, STREAM_BITS, 8, meter)
        phase_service(sess.params, args.seed, meter)
    log(f"[done] seconds={time.perf_counter() - t_start:.2f} "
        f"compiles={meter.count} compile_s={meter.seconds:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
