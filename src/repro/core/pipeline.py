"""End-to-end GROOT verification pipeline (paper Fig. 2 stages a-e).

    netlist/AIG -> features -> [partition -> re-growth] -> GNN inference
    -> XOR/MAJ classification -> algebraic verification

The stable front door over this flow is :class:`repro.api.Session`
(``run_pipeline`` survives as a deprecated shim over it).  The module
exposes the three reusable stages the façade composes —

  :func:`prepare`          host-side: design gen/ingest, features,
                           partitioning + boundary re-growth
  :func:`infer`            device-side: (partitioned) GNN prediction
  :func:`verify_prepared`  host-side: adder extraction + simulation check

— so batch schedulers (``repro.service``) can interleave the host and
device stages of many requests instead of running each end to end.

Also provides the device-memory model used by the Fig. 8 / Table II
benchmark: because this container is CPU-only, "GPU memory" is an
*analytic but array-accurate* count of the device buffers each inference
step allocates (features, activations for L layers, edge arrays, gathered
edge streams, params).  Partitioned runs count the PEAK over partitions —
exactly the quantity the paper's partitioning bounds.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from repro.core import aig as A
from repro.core import gnn
from repro.core.features import groot_features
from repro.core.graph import EdgeGraph, batch_graphs
from repro.core.partition import PARTITIONERS
from repro.core.regrowth import Subgraph, extract_partitions, boundary_edge_fraction
from repro.core.verify import VerifyResult, verify
from repro.obs import REGISTRY, span


def resolve_backend_alias(backend: Optional[str], aggregate: Optional[str],
                          *, owner: str) -> str:
    """Collapse the ``aggregate``/``backend`` naming split to ``backend``.

    ``aggregate=`` (the old ``PipelineConfig`` spelling) keeps working as
    a write-only alias: it warns, fills ``backend`` when that is unset,
    and conflicts loudly instead of silently preferring one.  Returns the
    resolved backend (default ``"ref"``).  Lives here (not ``repro.api``)
    so the core layer never imports upward.
    """
    if aggregate is not None:
        import warnings

        warnings.warn(
            f"{owner}(aggregate=...) is deprecated; the knob is named "
            f"backend= everywhere now",
            DeprecationWarning,
            # resolve_backend_alias <- __post_init__ <- generated __init__
            # <- the user's call site
            stacklevel=4,
        )
        if backend is None:
            backend = aggregate
        elif backend != aggregate:
            raise ValueError(
                f"{owner}: backend={backend!r} and its deprecated alias "
                f"aggregate={aggregate!r} disagree — pass only backend="
            )
    return "ref" if backend is None else backend


@dataclasses.dataclass
class PipelineConfig:
    dataset: str = "csa"
    bits: int = 32
    batch: int = 1
    num_partitions: int = 1
    regrow: bool = True
    regrow_hops: int = 1          # re-growth depth (iterated Algorithm 1);
                                  # >= gnn.num_layers -> partitioned == full
    partitioner: str = "multilevel"
    gnn: gnn.GNNConfig = dataclasses.field(default_factory=gnn.GNNConfig)
    # aggregation backend: "ref" | "onehot" | "groot" | "groot_mxu" |
    # "groot_fused" — the ONE name for the knob across every layer (the
    # service config always called it backend).  None resolves to "ref".
    backend: Optional[str] = None
    seed: int = 0
    # streaming-executor knobs (repro.exec).  ``memory_budget_bytes`` set
    # and num_partitions <= 1: prepare() derives the partition count from
    # the device budget via choose_k (the "fit this accelerator" mode).
    memory_budget_bytes: Optional[int] = None
    stream_capacity: int = 2      # same-bucket partitions packed per launch
    stream_prefetch: int = 1      # packed batches staged ahead of the device
    # edge-stream dtype for the hoisted groot* forward ("bfloat16" halves
    # the staged stream bytes; kernels accumulate f32).  None defers to
    # ``gnn.stream_dtype``.
    stream_dtype: Optional[str] = None
    # device-mesh sharding of the streamed route (repro.mesh).  None =
    # auto: use every visible device when more than one exists; 1 forces
    # the single-device executor; N shards across the first N devices.
    mesh_devices: Optional[int] = None
    # crash-safe resume for streamed runs: when ``checkpoint_dir`` is set
    # (and the design has a structural hash), every launched partition's
    # core predictions are journaled atomically, and a re-run restores
    # committed partitions instead of re-executing them.  ``resume=False``
    # keeps journaling but ignores (wipes) any prior journal.
    checkpoint_dir: Optional[str] = None
    resume: bool = True
    # deprecated write-only alias of ``backend`` (the old spelling);
    # consumed and reset to None at construction so dataclasses.replace
    # with backend= never sees a stale conflicting alias
    aggregate: Optional[str] = None

    def __post_init__(self):
        self.backend = resolve_backend_alias(
            self.backend, self.aggregate, owner="PipelineConfig"
        )
        self.aggregate = None


@dataclasses.dataclass
class PipelineResult:
    accuracy: float
    core_accuracy: float          # accuracy on S_p nodes (what the paper plots)
    peak_memory_bytes: int
    unpartitioned_memory_bytes: int
    boundary_edge_frac: float
    timings: dict
    verdict: Optional[VerifyResult]
    num_nodes: int
    num_edges: int
    # structural plan-cache activity during this run's inference stage:
    # {"builds": new plans/pairs built, "hits": reused}.  A repeated run
    # over the same structure shows builds == 0.  Deltas of the
    # process-global cache counters: attribution is only exact when no
    # other thread (e.g. a live VerificationService) runs inference
    # concurrently.
    plan_cache: dict = dataclasses.field(default_factory=dict)
    # streaming-executor probes for partitioned runs: compiles, launches,
    # bytes_h2d, pack/launch/wall seconds, peak_packed_memory_bytes (the
    # modeled bytes of the largest capacity-slot launch — the quantity
    # that must fit the device budget), chosen_k.
    exec_stats: dict = dataclasses.field(default_factory=dict)
    # per-verify span subtree (repro.obs.TraceHandle) when the session
    # that produced this result ran with SessionConfig(trace=True)
    trace: Optional[object] = None


def memory_model_bytes(
    num_nodes: int, num_edges: int, cfg: gnn.GNNConfig, include_params: bool = True
) -> int:
    """Device bytes for one inference over a (sub)graph.

    features (N,Fin) fp32 + per-layer activations 2x(N,H) (double-buffered
    current/next) + 2x aggregated (N,H) + edge index arrays 2x int32 x2
    directions + gathered edge stream (E,H) fp32 (the gather->MXU stream of
    the TPU formulation) + params.
    """
    f32 = 4
    n, e = num_nodes, num_edges
    bytes_ = n * cfg.in_features * f32
    h = cfg.hidden
    bytes_ += 2 * n * h * f32          # h, h_next
    bytes_ += 2 * n * h * f32          # agg_in, agg_out
    bytes_ += 2 * 2 * e * 4            # edge src/dst, both directions
    bytes_ += e * h * f32              # gathered edge stream
    if include_params:
        p = cfg.in_features * h * 3 + (cfg.num_layers - 1) * 3 * h * h + h * cfg.num_classes
        bytes_ += p * f32
    return int(bytes_)


def layer_traffic_model_bytes(
    num_nodes: int,
    num_edges: int,
    cfg: gnn.GNNConfig,
    *,
    hoisted: bool = True,
    stream_dtype: Optional[str] = None,
    slots_in: Optional[int] = None,
    slots_out: Optional[int] = None,
    segments_in: int = 4,
    segments_out: int = 4,
) -> int:
    """Modeled per-layer HBM traffic of the grouped aggregation hot path.

    Counts the three per-layer terms the ForwardPlan hoisting targets
    (array-accurate when the caller passes the real plan ``num_slots`` /
    ``num_segments``; pow-2-padding estimates otherwise):

      * **edge-message streams** — ``x[src]`` gathered once per direction
        per layer: ``(slots_in + slots_out) * H * stream_bytes``.  Both
        paths pay it; ``stream_dtype="bfloat16"`` halves it.
      * **edge-weight streams** — pre-hoist each layer re-gathers the
        (E, 4) fanin + (E, 2) fanout group weights into kernel layout;
        hoisted stages them once per forward, so the per-layer share is
        amortised by ``num_layers``.
      * **output assembly** — pre-hoist each aggregation issues one
        ``(N, H)`` scatter per LD bucket plus one for HD (each a
        read-modify-write of the output array) plus the final read;
        hoisted assembles with a single permutation gather (concat write
        + gather read + result write: 3 passes).
    """
    f32 = 4
    sdt = np.dtype(stream_dtype) if stream_dtype is not None else np.dtype("float32")
    sb = sdt.itemsize
    h = cfg.hidden
    s_in = 2 * num_edges if slots_in is None else slots_in
    s_out = 2 * num_edges if slots_out is None else slots_out
    layers = max(cfg.num_layers, 1)

    traffic = (s_in + s_out) * h * sb                 # message streams
    w_bytes = (4 * s_in + 2 * s_out) * sb             # group-weight streams
    traffic += w_bytes // layers if hoisted else w_bytes
    out_plane = num_nodes * h * f32                   # one (N, H) pass
    if hoisted:
        traffic += 2 * 3 * out_plane                  # both directions
    else:
        # segments already counts the HD pass: 2 touches (read+write) per
        # scatter segment, plus the final read of the assembled output
        traffic += (2 * segments_in + 1) * out_plane
        traffic += (2 * segments_out + 1) * out_plane
    return int(traffic)


@dataclasses.dataclass
class PreparedDesign:
    """Host-side output of :func:`prepare` — everything inference needs."""

    cfg: PipelineConfig
    design: object               # AIG or LUTGraph
    labels: np.ndarray
    feats: np.ndarray
    graph: EdgeGraph
    subgraphs: Optional[list[Subgraph]]   # None when unpartitioned
    boundary_edge_frac: float
    timings: dict

    @property
    def num_nodes(self) -> int:
        return self.graph.num_nodes

    @property
    def num_edges(self) -> int:
        return self.graph.num_edges

    @property
    def num_partitions(self) -> int:
        """Effective partition count (budget-driven prepare may exceed
        ``cfg.num_partitions``)."""
        return len(self.subgraphs) if self.subgraphs else 1

    def memory_bytes(self) -> tuple[int, int]:
        """(unpartitioned, peak-over-partitions) device bytes."""
        full = memory_model_bytes(self.num_nodes, self.num_edges, self.cfg.gnn)
        if not self.subgraphs:
            return full, full
        peak = max(
            memory_model_bytes(sg.num_nodes, sg.num_edges, self.cfg.gnn)
            for sg in self.subgraphs
        )
        return full, peak


def prepare(cfg: PipelineConfig, design=None) -> PreparedDesign:
    """Stage 1 (host): design generation/ingest, features, partition+re-growth.

    ``design`` overrides generation — the ingestion path for AIGs parsed
    from AIGER files (``repro.io.aiger``); ``cfg.dataset``/``cfg.bits``
    are then only used for verification metadata downstream.
    """
    t0 = time.perf_counter()
    with span("prepare.features"):
        if design is None:
            design = A.make_design(cfg.dataset, cfg.bits, seed=cfg.seed)
        labels = design.label
        feats = groot_features(design)
        g1 = design.to_edge_graph()
        if cfg.batch > 1:
            g = batch_graphs([g1] * cfg.batch)
            feats = np.tile(feats, (cfg.batch, 1))
            labels = np.tile(labels, cfg.batch)
        else:
            g = g1
    t_gen = time.perf_counter() - t0
    REGISTRY.counter("pipeline.prepares").inc()

    t0 = time.perf_counter()
    k = cfg.num_partitions
    budgeted = k <= 1 and cfg.memory_budget_bytes is not None
    if budgeted:
        from repro.exec.plan import HALO_FRAC, choose_k

        # halo grows with re-growth depth; scale the planning margin so
        # deep-hop runs are not fitted with the 1-hop estimate
        k = choose_k(
            g.num_nodes, g.num_edges, cfg.gnn, cfg.memory_budget_bytes,
            capacity=cfg.stream_capacity,
            halo_frac=HALO_FRAC * max(1, cfg.regrow_hops if cfg.regrow else 1),
        )

    def _cut(k):
        part = PARTITIONERS[cfg.partitioner](g, k, seed=cfg.seed)
        return part, extract_partitions(
            g, part, regrow=cfg.regrow, hops=cfg.regrow_hops
        )

    if k <= 1:
        subs, bfrac, t_part = None, 0.0, 0.0
    else:
        with span("prepare.partition", k=k, partitioner=cfg.partitioner) as sp:
            part, subs = _cut(k)
            if budgeted and subs:
                # the estimate can undershoot real halo growth: validate the
                # BUILT plan's packed peak and re-split finer until it fits.
                # A finer cut that does not shrink the peak ends the search
                # (deep re-growth halos can cover most of the design): past
                # that point every doubling costs a full re-cut and buys
                # nothing, and the run streams at the best cut found
                from repro.exec.plan import plan_from_subgraphs

                def _peak(subs):
                    return plan_from_subgraphs(
                        subs, g.num_nodes
                    ).peak_batch_memory_bytes(cfg.gnn, cfg.stream_capacity)

                peak = _peak(subs)
                while k < g.num_nodes and peak > cfg.memory_budget_bytes:
                    finer_part, finer_subs = _cut(2 * k)
                    finer_peak = _peak(finer_subs)
                    if finer_peak >= peak:
                        break
                    k, part, subs, peak = 2 * k, finer_part, finer_subs, finer_peak
            bfrac = boundary_edge_fraction(g, part)
            if not subs:  # empty graph: fall back to the unpartitioned path
                subs = None
            sp.set(final_k=len(subs) if subs else 1)
        REGISTRY.counter("pipeline.partition_cuts").inc()
        t_part = time.perf_counter() - t0
    return PreparedDesign(
        cfg=cfg,
        design=design,
        labels=labels,
        feats=feats,
        graph=g,
        subgraphs=subs,
        boundary_edge_frac=bfrac,
        timings={"gen": t_gen, "partition": t_part},
    )


def infer(params, prep: PreparedDesign, *, backend: Optional[str] = None) -> np.ndarray:
    """Stage 2 (device): per-node class predictions over the full graph.

    Partitioned designs stream (prepare -> plan -> stream -> scatter);
    :func:`infer_streaming` exposes the executor's probe counters too.
    """
    if prep.subgraphs is None:
        backend = backend or prep.cfg.backend
        return gnn.predict(
            params, prep.graph, prep.feats, backend=backend,
            stream_dtype=_effective_stream_dtype(prep.cfg),
        )
    pred, _ = infer_streaming(params, prep, backend=backend)
    return pred


def _effective_stream_dtype(cfg: PipelineConfig) -> Optional[str]:
    """The staged edge-stream dtype a run uses: the pipeline-level knob
    wins, else the GNN config's; f32 normalises to None (bit-exact path)."""
    sdt = cfg.stream_dtype or cfg.gnn.stream_dtype
    return None if sdt in (None, "float32") else sdt


def _journal_for(prep: PreparedDesign):
    """Build the crash-resume journal for a streamed run, or None.

    Journaling needs a durable identity for "the same work": the design's
    structural hash (the service dedup key).  Only single-AIG runs have
    one, so batched/LUT runs stream unjournaled.  ``resume=False`` wipes
    any prior journal before the run — fresh execution, fresh journal.
    """
    cfg = prep.cfg
    if not cfg.checkpoint_dir or cfg.batch != 1 or not isinstance(prep.design, A.AIG):
        return None
    from repro.checkpoint import PartitionJournal
    from repro.io import aiger

    journal = PartitionJournal(cfg.checkpoint_dir, aiger.structural_hash(prep.design))
    if not cfg.resume:
        journal.complete()  # discard any prior partial run
    return journal


def infer_streaming(
    params,
    prep: PreparedDesign,
    *,
    backend: Optional[str] = None,
    executor=None,
    plan=None,
    journal=None,
) -> tuple[np.ndarray, dict]:
    """Partitioned inference through the streaming executor.

    Returns ``(pred, exec_stats)`` where ``exec_stats`` carries the
    executor probes (compiles, launches, bytes_h2d, pack/launch/wall
    seconds) plus ``peak_packed_memory_bytes`` — the modeled device bytes
    of the largest packed launch — and ``chosen_k``.

    ``journal``: explicit :class:`~repro.checkpoint.PartitionJournal`
    override; when None one is derived from ``cfg.checkpoint_dir`` (keyed
    by the design's structural hash) if configured — see
    :func:`_journal_for`.
    """
    from repro.exec.plan import plan_from_subgraphs
    from repro.exec.stream import shared_executor

    assert prep.subgraphs, "infer_streaming needs a partitioned PreparedDesign"
    backend = backend or prep.cfg.backend
    cfg = prep.cfg
    if executor is None:
        devices = cfg.mesh_devices
        if devices is None:
            import jax

            devices = jax.local_device_count()
        if devices > 1:
            # >1 visible device (or an explicit mesh_devices): shard the
            # stream across the mesh data axis — same packed launches,
            # same verdict, one journal
            from repro.mesh import shared_mesh_executor

            executor = shared_mesh_executor(
                params, backend or "ref", num_devices=devices,
                capacity=cfg.stream_capacity,
                prefetch=cfg.stream_prefetch,
                stream_dtype=_effective_stream_dtype(cfg),
            )
        else:
            # reused per (params, backend): repeated partitioned runs hit
            # the same jit cache instead of retracing every bucket
            executor = shared_executor(
                params, backend, capacity=cfg.stream_capacity,
                prefetch=cfg.stream_prefetch,
                stream_dtype=_effective_stream_dtype(cfg),
            )
    if plan is None:
        plan = plan_from_subgraphs(
            list(prep.subgraphs), prep.num_nodes, num_edges=prep.num_edges,
            regrow=cfg.regrow, partitioner=cfg.partitioner, seed=cfg.seed,
            min_nodes=executor.min_nodes, min_edges=executor.min_edges,
        )
    if journal is None:
        journal = _journal_for(prep)
    before = dataclasses.replace(executor.stats)
    pred = executor.run_plan(plan, prep.feats, gnn_cfg=cfg.gnn, journal=journal)
    stats = dataclasses.asdict(executor.stats.delta(before))
    stats["peak_packed_memory_bytes"] = plan.peak_batch_memory_bytes(
        cfg.gnn, executor.capacity
    )
    stats["num_buckets"] = plan.num_buckets
    stats["chosen_k"] = prep.num_partitions
    # model drift: the analytic model on real launched shapes over the
    # plan-time prediction choose_k budgeted against.  >1 means launches
    # were bigger than modeled (the budget was optimistic); kept next to
    # chosen_k because that is the decision this ratio validates.
    modeled, actual = stats["modeled_peak_bytes"], stats["actual_peak_bytes"]
    if modeled:
        stats["model_drift"] = actual / modeled
    return pred, stats


def verify_prepared(
    prep: PreparedDesign, pred: np.ndarray, *, signed: Optional[bool] = None
) -> Optional[VerifyResult]:
    """Stage 3 (host): algebraic adder extraction + simulation cross-check.

    Returns None when the prepared design is not verifiable as a single
    multiplier AIG (batched runs, LUT graphs).
    """
    if prep.cfg.batch != 1 or not isinstance(prep.design, A.AIG):
        return None
    bits = prep.design.n_pi // 2
    if signed is None:
        signed = prep.cfg.dataset == "booth" or prep.design.name.startswith("booth")
    REGISTRY.counter("pipeline.verifications").inc()
    return verify(
        prep.design,
        pred[: prep.design.num_nodes],
        bits=bits,
        signed=signed,
        simulate=bits <= 64,
    )


def run_pipeline(
    cfg: PipelineConfig, params, *, verify_result: bool = False
) -> PipelineResult:
    """DEPRECATED shim over :class:`repro.api.Session` (the one façade).

    Behaviour-preserving: the session is configured field-for-field from
    ``cfg`` (``SessionConfig.from_pipeline``) and its router takes the
    same full/streamed path this function used to hard-code, with the
    result LRU bypassed so every call really runs.
    """
    import warnings

    warnings.warn(
        "run_pipeline is deprecated; use repro.api.Session.verify",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.api import Session, SessionConfig

    r = Session(params, SessionConfig.from_pipeline(cfg)).verify(
        verify=verify_result, use_cache=False
    )
    return PipelineResult(
        accuracy=r.accuracy,
        core_accuracy=r.core_accuracy,
        peak_memory_bytes=r.peak_memory_bytes,
        unpartitioned_memory_bytes=r.unpartitioned_memory_bytes,
        boundary_edge_frac=r.boundary_edge_frac,
        timings=r.timings,
        verdict=r.verdict,
        num_nodes=r.num_nodes,
        num_edges=r.num_edges,
        plan_cache=r.plan_cache,
        exec_stats=r.exec_stats,
        trace=r.trace,
    )


def train_model(
    dataset: str = "csa",
    bits: int = 8,
    *,
    cfg: Optional[gnn.GNNConfig] = None,
    epochs: int = 300,
    seed: int = 0,
):
    """Train the GNN on a small design (the paper trains on 8-bit)."""
    import jax

    cfg = cfg or gnn.GNNConfig()
    design = A.make_design(dataset, bits, seed=seed)
    feats = groot_features(design)
    batch = gnn.make_batch(design, feats, design.label.astype(np.int32))
    params = gnn.init_params(cfg, jax.random.key(seed))
    params, hist = gnn.train(params, batch, epochs=epochs, log_every=50)
    return params, hist
