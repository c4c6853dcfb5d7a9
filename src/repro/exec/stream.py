"""Double-buffered streaming execution of partition plans.

:class:`StreamingExecutor` turns a :class:`~repro.exec.plan.PartitionPlan`
into a stream of packed launches through ONE jitted padded forward pass
(the service layer's :class:`~repro.service.scheduler.BucketRunner`):

    host prefetch thread                 device (caller thread)
    --------------------                 ----------------------
    pack batch 0  ──queue──▶
    pack batch 1  ──queue──▶             run batch 0, scatter cores
    pack batch 2  ──queue──▶             run batch 1, scatter cores
    ...                                  ...

While the device runs batch *i*, the prefetch thread gathers and pads
batch *i+1*'s features — the host staging that made the sequential
``predict_partitioned`` loop transfer-bound.  The queue depth
(``prefetch``) bounds host memory: at most ``prefetch + 1`` packed batches
exist at once, so the host footprint is O(batch), not O(design).

Compile discipline: every launch of the same bucket reuses the same jit
executable, so a whole streamed run compiles at most ``plan.num_buckets``
programs for shape-stable backends ("ref"/"onehot") — the probe-asserted
acceptance criterion.  Structure-keyed ``groot*`` backends compile per
distinct packed structure instead (each batch's degree plan is a jit
constant); recurring designs still hit the process-wide plan cache and
compile nothing new.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Optional

import numpy as np

from repro import faults
from repro.core.graph import EdgeGraph
from repro.core.regrowth import Subgraph
from repro.exec.packing import PackedBatch, pack_partitions, scatter_core_predictions
from repro.exec.plan import PartitionPlan, build_partition_plan, plan_from_subgraphs
from repro.obs import REGISTRY, current_tracer, span
from repro.service.scheduler import BucketRunner


@dataclasses.dataclass
class StreamStats:
    """Probe counters for one executor (cumulative across runs)."""

    runs: int = 0                 # run_plan invocations
    batches: int = 0              # packed launches issued
    partitions: int = 0           # subgraphs streamed
    core_rows: int = 0            # core predictions scattered
    compiles: int = 0             # jit traces of the padded forward
    launches: int = 0             # device calls
    bytes_h2d: int = 0            # staged host->device transfer bytes
    pack_s: float = 0.0           # host packing time (prefetch thread)
    launch_s: float = 0.0         # host clock around each launch and its readback
    wall_s: float = 0.0           # end-to-end streamed time
    max_queue_depth: int = 0      # prefetch occupancy high-water mark
    # failure-domain counters: launches replayed at reduced pack capacity
    # after a device resource error, and partitions skipped on a resumed
    # run because a journal already held their core predictions
    capacity_halvings: int = 0
    resumed_partitions: int = 0
    # model-vs-actual memory accounting (high-water marks): what the plan
    # modeled as the packed-launch peak vs the model evaluated on the
    # REAL launched padded shapes — the validation loop for choose_k
    modeled_peak_bytes: int = 0
    actual_peak_bytes: int = 0

    @property
    def overlap_s(self) -> float:
        """Host pack time hidden behind the launches."""
        return max(0.0, self.pack_s + self.launch_s - self.wall_s)

    def delta(self, before: "StreamStats") -> "StreamStats":
        """Per-run view: this (cumulative) snapshot minus ``before``.
        High-water marks (``max_queue_depth``, ``*_peak_bytes``) keep the
        later value — a peak has no meaningful difference."""
        return StreamStats(
            runs=self.runs - before.runs,
            batches=self.batches - before.batches,
            partitions=self.partitions - before.partitions,
            core_rows=self.core_rows - before.core_rows,
            compiles=self.compiles - before.compiles,
            launches=self.launches - before.launches,
            bytes_h2d=self.bytes_h2d - before.bytes_h2d,
            pack_s=self.pack_s - before.pack_s,
            launch_s=self.launch_s - before.launch_s,
            wall_s=self.wall_s - before.wall_s,
            capacity_halvings=self.capacity_halvings - before.capacity_halvings,
            resumed_partitions=self.resumed_partitions - before.resumed_partitions,
            max_queue_depth=self.max_queue_depth,
            modeled_peak_bytes=self.modeled_peak_bytes,
            actual_peak_bytes=self.actual_peak_bytes,
        )


_SENTINEL = object()


class StreamingExecutor:
    """Drives partition plans through bucketed, double-buffered launches."""

    def __init__(
        self,
        params=None,
        backend: str = "ref",
        *,
        runner: Optional[BucketRunner] = None,
        capacity: int = 2,
        prefetch: int = 1,
        min_nodes: int = 64,
        min_edges: int = 128,
        stream_dtype: Optional[str] = None,
    ):
        """Either ``params`` (a fresh runner is built) or an existing
        ``runner`` (the service scheduler shares its compile probe)."""
        if runner is None:
            if params is None:
                raise ValueError("need params or a BucketRunner")
            runner = BucketRunner(params, backend, stream_dtype=stream_dtype)
        self.runner = runner
        self.capacity = max(1, capacity)
        self.prefetch = max(0, prefetch)
        self.min_nodes = min_nodes
        self.min_edges = min_edges
        self.stats = StreamStats()
        #: every distinct bucket shape streamed through this executor —
        #: the denominator of the compile-count probe (for shape-stable
        #: backends, runner.compile_count <= len(buckets_seen))
        self.buckets_seen: set = set()

    # -- plan construction helpers ------------------------------------------

    def plan_graph(
        self,
        graph: EdgeGraph,
        k: int,
        *,
        regrow: bool = True,
        hops: int = 1,
        partitioner: str = "multilevel",
        seed: int = 0,
    ) -> PartitionPlan:
        return build_partition_plan(
            graph, k, regrow=regrow, hops=hops, partitioner=partitioner,
            seed=seed, min_nodes=self.min_nodes, min_edges=self.min_edges,
        )

    # -- execution ----------------------------------------------------------

    def run_plan(self, plan: PartitionPlan, features: np.ndarray,
                 gnn_cfg=None, journal=None) -> np.ndarray:
        """Stream every partition batch; returns (num_nodes,) int32 global
        predictions with every core row written (halo rows are computed
        under their owning partition).

        ``gnn_cfg`` enables model-vs-actual memory accounting: the plan's
        modeled packed-launch peak and the same analytic model evaluated
        on every REAL launched padded shape land in ``stats`` and the
        ``exec.modeled_peak_bytes`` / ``exec.actual_peak_bytes`` gauges.

        ``journal`` (a :class:`repro.checkpoint.PartitionJournal`) makes
        the run crash-safe: each launched partition's core predictions are
        committed as they land, previously committed partitions are
        restored into ``out`` and dropped from the schedule, and the
        journal is cleared once every partition has been written.
        """
        t_wall = time.perf_counter()
        schedule = plan.schedule(self.capacity)
        self.buckets_seen.update(plan.buckets)
        if gnn_cfg is not None:
            modeled = plan.peak_batch_memory_bytes(gnn_cfg, self.capacity)
            self.stats.modeled_peak_bytes = max(
                self.stats.modeled_peak_bytes, modeled
            )
            REGISTRY.gauge("exec.modeled_peak_bytes").set(modeled)
        out = np.zeros(plan.num_nodes, dtype=np.int32)
        if journal is not None:
            restored = journal.restore(plan, out)
            if restored:
                schedule = [
                    (shape, kept)
                    for shape, indices in schedule
                    if (kept := [i for i in indices if i not in restored])
                ]
                self.stats.resumed_partitions += len(restored)
                REGISTRY.counter("exec.resumed_partitions").inc(len(restored))
        compiles_before = self.runner.compile_count
        tracer = current_tracer()
        # per-run degradation state: a device resource error halves the
        # effective pack capacity for the REST of this run (mutated by
        # _launch_degradable), so one undersized device doesn't turn every
        # remaining batch into its own failure
        degrade = {"cap": self.capacity}

        with tracer.span(
            "exec.stream",
            partitions=plan.num_parts,
            batches=len(schedule),
        ) as stream_sp:
            if self.prefetch == 0 or len(schedule) <= 1:
                # synchronous fallback (also the degenerate 0/1-batch case)
                for shape, indices in schedule:
                    batch = self._pack_timed(plan, indices, features, shape)
                    self._launch_degradable(
                        plan, batch, out, features, gnn_cfg, degrade, journal
                    )
            else:
                q: queue.Queue = queue.Queue(maxsize=self.prefetch)
                stop = threading.Event()  # consumer died: unblock producer
                # pack spans from the prefetch thread parent under this
                # run's stream span, not under whatever that thread did last
                stream_id = stream_sp.span_id

                def _put(item) -> bool:
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.1)
                            return True
                        except queue.Full:
                            continue
                    return False

                def _producer():
                    with tracer.adopt(stream_id):
                        try:
                            for shape, indices in schedule:
                                faults.fire(
                                    "exec.prefetch",
                                    tag=lambda: f"parts={len(indices)}",
                                )
                                if not _put(
                                    self._pack_timed(plan, indices, features, shape)
                                ):
                                    return
                            _put(_SENTINEL)
                        except faults.WorkerKilled:
                            # simulated abrupt thread death: deliver NOTHING
                            # — the consumer-side watchdog must catch this
                            return
                        except BaseException as e:  # noqa: BLE001 — forwarded to consumer
                            _put(e)

                th = threading.Thread(
                    target=_producer, name="exec-prefetch", daemon=True
                )
                th.start()
                try:
                    while True:
                        depth = q.qsize()
                        self.stats.max_queue_depth = max(
                            self.stats.max_queue_depth, depth
                        )
                        REGISTRY.gauge("exec.queue_depth").set(depth)
                        got = self._next_batch(q, th)
                        if got is _SENTINEL:
                            break
                        if isinstance(got, BaseException):
                            raise got
                        self._launch_degradable(
                            plan, got, out, features, gnn_cfg, degrade, journal
                        )
                finally:
                    # a launch failure leaves the producer blocked mid-put;
                    # the stop flag makes its bounded put give up promptly
                    # instead of stalling join for its full timeout
                    stop.set()
                    th.join(timeout=60.0)

        if journal is not None:
            journal.complete()

        self.stats.runs += 1
        # delta, not the runner's cumulative count: a runner shared with
        # the service scheduler also compiles for regular bucketed items,
        # and those must not be attributed to this stream
        run_compiles = self.runner.compile_count - compiles_before
        self.stats.compiles += run_compiles
        wall = time.perf_counter() - t_wall
        self.stats.wall_s += wall
        REGISTRY.counter("exec.runs").inc()
        REGISTRY.counter("exec.compiles").inc(run_compiles)
        REGISTRY.histogram("exec.wall_s").observe(wall)
        return out

    def run_subgraphs(
        self,
        subgraphs: list[Subgraph],
        features: np.ndarray,
        num_nodes: int,
    ) -> np.ndarray:
        """Stream pre-extracted partitions (``predict_partitioned``'s
        calling convention)."""
        plan = plan_from_subgraphs(
            list(subgraphs), num_nodes,
            min_nodes=self.min_nodes, min_edges=self.min_edges,
        )
        return self.run_plan(plan, features)

    def run_graph(
        self,
        graph: EdgeGraph,
        features: np.ndarray,
        k: int,
        *,
        regrow: bool = True,
        hops: int = 1,
        partitioner: str = "multilevel",
        seed: int = 0,
    ) -> np.ndarray:
        """Plan + stream in one call (the service auto-route entry)."""
        plan = self.plan_graph(
            graph, k, regrow=regrow, hops=hops, partitioner=partitioner,
            seed=seed,
        )
        return self.run_plan(plan, features)

    # -- internals ----------------------------------------------------------

    @staticmethod
    def _next_batch(q: queue.Queue, th: threading.Thread):
        """Bounded-wait queue read with a producer watchdog.

        A blocking ``q.get()`` turns a dead prefetch thread into a silent
        hang: nothing will ever arrive, and nothing ever raises.  Poll
        instead, and if the producer has died without delivering either a
        batch or a forwarded exception, fail the run loudly.
        """
        while True:
            try:
                return q.get(timeout=0.2)
            except queue.Empty:
                if not th.is_alive():
                    REGISTRY.counter("exec.prefetch_deaths").inc()
                    raise RuntimeError(
                        "prefetch thread died without delivering a batch "
                        "or an error (see exec.prefetch_deaths)"
                    ) from None

    def _pack_timed(self, plan, indices, features, shape,
                    capacity: Optional[int] = None) -> PackedBatch:
        t0 = time.perf_counter()
        with span("exec.pack", parts=len(indices)) as sp:
            batch = pack_partitions(
                plan, indices, features, shape, capacity or self.capacity
            )
            sp.set(bytes=batch.nbytes)
        dt = time.perf_counter() - t0
        self.stats.pack_s += dt
        self.stats.bytes_h2d += batch.nbytes
        REGISTRY.counter("exec.bytes_h2d").inc(batch.nbytes)
        REGISTRY.histogram("exec.pack_s").observe(dt)
        return batch

    def _launch_degradable(self, plan, batch: PackedBatch, out: np.ndarray,
                           features, gnn_cfg, degrade: dict,
                           journal=None) -> None:
        """Launch with graceful capacity degradation.

        On a device resource error (OOM and friends, classified by
        :func:`repro.faults.is_resource_error`) the effective pack
        capacity for the rest of the run is halved and the failed batch
        is re-packed as smaller chunks and relaunched — smaller padded
        arrays, a smaller jit signature, a smaller device footprint.  A
        singleton batch that still hits a resource error cannot shrink
        further, so it propagates.
        """
        cap = max(1, degrade["cap"])
        if len(batch.indices) > cap:
            # capacity already degraded earlier in the run: proactively
            # split batches packed (e.g. by the prefetch thread) at the
            # old capacity instead of rediscovering the OOM per batch
            self._relaunch_split(
                plan, batch, out, features, gnn_cfg, degrade, journal, cap
            )
            return
        try:
            self._launch(batch, out, gnn_cfg, journal)
        except Exception as e:
            if not faults.is_resource_error(e) or len(batch.indices) <= 1:
                raise
            degrade["cap"] = cap = max(1, min(cap, len(batch.indices)) // 2)
            self.stats.capacity_halvings += 1
            REGISTRY.counter("exec.capacity_halvings").inc()
            REGISTRY.gauge("exec.effective_capacity").set(cap)
            self._relaunch_split(
                plan, batch, out, features, gnn_cfg, degrade, journal, cap
            )

    def _relaunch_split(self, plan, batch, out, features, gnn_cfg,
                        degrade, journal, cap: int) -> None:
        indices = list(batch.indices)
        for at in range(0, len(indices), cap):
            chunk = indices[at:at + cap]
            repacked = self._pack_timed(
                plan, chunk, features, batch.shape, capacity=cap
            )
            self._launch_degradable(
                plan, repacked, out, features, gnn_cfg, degrade, journal
            )

    def _launch(self, batch: PackedBatch, out: np.ndarray,
                gnn_cfg=None, journal=None) -> None:
        if gnn_cfg is not None:
            # the same analytic model, evaluated on the padded shapes this
            # launch ACTUALLY ships (capacity*n_pad rows, capacity*e_pad
            # edges) — staged bytes are separately measured as bytes_h2d
            from repro.core.pipeline import memory_model_bytes

            actual = memory_model_bytes(
                int(batch.arrays["x"].shape[0]),
                int(batch.arrays["edge_src"].shape[0]),
                gnn_cfg,
            )
            self.stats.actual_peak_bytes = max(
                self.stats.actual_peak_bytes, actual
            )
            REGISTRY.gauge("exec.actual_peak_bytes").set(actual)
        t0 = time.perf_counter()
        with span("exec.launch", parts=len(batch.items)):
            faults.fire(
                "exec.launch",
                tag=lambda: f"parts={len(batch.items)} shape={batch.shape}",
            )
            pred = self.runner(batch.arrays)
        dt = time.perf_counter() - t0
        self.stats.launch_s += dt
        self.stats.launches += 1
        self.stats.batches += 1
        self.stats.partitions += len(batch.items)
        self.stats.core_rows += scatter_core_predictions(out, batch, pred)
        REGISTRY.counter("exec.launches").inc()
        REGISTRY.histogram("exec.launch_s").observe(dt)
        if journal is not None:
            # commit core predictions partition-by-partition AFTER the
            # scatter: each journal file is written atomically, so a crash
            # between launches loses at most the in-flight batch
            for idx, it in zip(batch.indices, batch.items):
                ids = it.global_ids[: it.num_core]
                journal.commit(int(idx), ids, out[ids])


#: small identity-keyed executor reuse pool: a fresh executor per call
#: would mean a fresh ``jax.jit`` per call, retracing every bucket on
#: every ``predict_partitioned`` — the exact recompile churn the bucket
#:  discipline exists to kill.  Entries hold a strong ref to the params
#: tree, so an ``id()`` can never alias a collected object.
_EXECUTOR_POOL: dict[tuple, tuple[object, "StreamingExecutor"]] = {}
_EXECUTOR_POOL_MAX = 8


def shared_executor(
    params, backend: str, *, capacity: int = 2, prefetch: int = 1,
    stream_dtype: Optional[str] = None,
    min_nodes: int = 64, min_edges: int = 128,
) -> StreamingExecutor:
    """The process-wide executor for (params identity, backend, knobs)."""
    if stream_dtype == "float32":
        stream_dtype = None   # numerically identical: share the executor
    key = (id(params), backend, capacity, prefetch, stream_dtype,
           min_nodes, min_edges)
    hit = _EXECUTOR_POOL.get(key)
    if hit is not None and hit[0] is params:
        return hit[1]
    ex = StreamingExecutor(params, backend, capacity=capacity, prefetch=prefetch,
                           stream_dtype=stream_dtype,
                           min_nodes=min_nodes, min_edges=min_edges)
    if len(_EXECUTOR_POOL) >= _EXECUTOR_POOL_MAX:
        _EXECUTOR_POOL.clear()
    _EXECUTOR_POOL[key] = (params, ex)
    return ex


def stream_predict_partitioned(
    params,
    subgraphs: list[Subgraph],
    features: np.ndarray,
    num_nodes: int,
    backend: str = "ref",
    *,
    capacity: int = 2,
    prefetch: int = 1,
    stream_dtype: Optional[str] = None,
) -> np.ndarray:
    """One-shot convenience: stream through the shared executor pool.

    Predictions are bit-exact with the sequential per-subgraph loop
    (:func:`repro.core.gnn.predict_partitioned_loop`) on core rows — the
    padding/packing contract keeps every real row's arithmetic identical.
    Repeated calls with the same params reuse one executor (and so one
    jit cache): recurring subgraph buckets compile nothing new.
    """
    ex = shared_executor(params, backend, capacity=capacity, prefetch=prefetch,
                         stream_dtype=stream_dtype)
    return ex.run_subgraphs(subgraphs, features, num_nodes)
