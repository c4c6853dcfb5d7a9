"""End-to-end behaviour tests for the GROOT verification system.

Validates the paper's pipeline claims at test scale: functional-correct AIG
generators, oracle-consistent labels, partition/re-growth accuracy recovery,
memory-bound partitioned inference, and the full verify() flow.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import aig as A

pytestmark = pytest.mark.slow  # trains models; full-lane only
from repro.core import gnn, pipeline as P
from repro.core.features import groot_features, gamora_features
from repro.core.labels import structural_detect
from repro.core.partition import PARTITIONERS, edge_cut
from repro.core.regrowth import boundary_edge_fraction, extract_partitions
from repro.core.verify import simulation_check


@pytest.fixture(scope="module")
def trained_params():
    params, _ = P.train_model("csa", 8, epochs=200)
    return params


# ---------------------------------------------------------------------------
# Generators are functionally correct multipliers
# ---------------------------------------------------------------------------

# 96 bits: operands wider than an int64 (random vectors, bignum spec)
@pytest.mark.parametrize("bits", [2, 4, 6, 96])
def test_csa_multiplier_functional(bits):
    assert simulation_check(A.csa_multiplier(bits), bits, signed=False)


@pytest.mark.parametrize("bits", [2, 4, 6, 96])
def test_booth_multiplier_functional(bits):
    assert simulation_check(A.booth_multiplier(bits), bits, signed=True)


def test_wide_simulation_check_rejects_the_wrong_spec():
    # an unsigned multiplier is not a signed one: the bignum path must see it
    assert not simulation_check(A.csa_multiplier(96), 96, signed=True)


def test_mapped_multiplier_functional():
    assert simulation_check(A.csa_multiplier(4, mixed_decomp=True), 4, signed=False)


# ---------------------------------------------------------------------------
# Features reproduce the paper's worked example (§III-B, Fig. 3c)
# ---------------------------------------------------------------------------

def test_features_match_paper_vector_table():
    aig = A.csa_multiplier(2)
    f = groot_features(aig)
    # PIs: 0000
    assert (f[: aig.n_pi] == 0).all()
    # ANDs with non-inverted inputs -> 1100
    is_and = aig.kind == A.AND
    noninv = is_and & ((aig.fanin0 & 1) == 0) & ((aig.fanin1 & 1) == 0)
    assert (f[noninv] == np.array([1, 1, 0, 0], np.float32)).all()
    # ANDs with both inputs inverted -> 1111
    bothinv = is_and & ((aig.fanin0 & 1) == 1) & ((aig.fanin1 & 1) == 1)
    assert bothinv.any()
    assert (f[bothinv] == np.array([1, 1, 1, 1], np.float32)).all()
    # PO with non-inverted driver -> 0011
    is_po = aig.kind == A.PO
    po_pos = is_po & ((aig.fanin0 & 1) == 0)
    assert (f[po_pos] == np.array([0, 0, 1, 1], np.float32)).all()
    # GROOT has 4 features vs GAMORA's 3 (the paper's feature-count claim)
    assert f.shape[1] == 4 and gamora_features(aig).shape[1] == 3


def test_structural_detector_agrees_with_construction_labels():
    for ds, min_agree in (("csa", 0.98), ("booth", 0.99)):
        d = A.make_design(ds, 8)
        agree = float((structural_detect(d) == d.label).mean())
        assert agree >= min_agree, (ds, agree)


# ---------------------------------------------------------------------------
# Partitioning + re-growth (§III-C)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("partitioner", ["multilevel", "bfs"])
def test_partition_balance_and_cut(partitioner):
    g = A.csa_multiplier(16).to_edge_graph()
    k = 8
    part = PARTITIONERS[partitioner](g, k)
    sizes = np.bincount(part, minlength=k)
    assert sizes.min() > 0
    assert sizes.max() <= 1.6 * g.num_nodes / k
    assert edge_cut(g, part) < g.num_edges * 0.5


def test_regrowth_algorithm1_invariants():
    """Alg. 1: S_p+ ⊇ S_p; E_p+ = E[S_p] ∪ C_p; halo = 1-hop boundary."""
    g = A.csa_multiplier(8).to_edge_graph()
    part = PARTITIONERS["multilevel"](g, 4)
    subs = extract_partitions(g, part, regrow=True)
    covered = np.zeros(g.num_nodes, bool)
    for p, sg in enumerate(subs):
        covered[sg.global_ids[: sg.num_core]] = True
        core = set(sg.global_ids[: sg.num_core].tolist())
        halo = set(sg.global_ids[sg.num_core :].tolist())
        assert not core & halo
        # every halo node is 1 hop from a core node
        s, d = g.edge_src, g.edge_dst
        nbrs = set()
        mask_c = np.isin(s, list(core))
        nbrs.update(d[mask_c].tolist())
        mask_c2 = np.isin(d, list(core))
        nbrs.update(s[mask_c2].tolist())
        assert halo <= (nbrs - core)
        # every edge has >= 1 core endpoint (E[S_p] ∪ C_p, nothing more)
        gi = sg.global_ids
        src_is_core = sg.edge_src < sg.num_core
        dst_is_core = sg.edge_dst < sg.num_core
        assert (src_is_core | dst_is_core).all()
        # edges exist in the original graph
        orig = set(zip(g.edge_src.tolist(), g.edge_dst.tolist()))
        for es, ed in zip(gi[sg.edge_src].tolist(), gi[sg.edge_dst].tolist()):
            assert (es, ed) in orig
    assert covered.all()  # partitions tile the node set


def test_boundary_edge_fraction_matches_paper_order():
    """Paper §III-C: ~10% boundary edges."""
    g = A.csa_multiplier(32).to_edge_graph()
    part = PARTITIONERS["multilevel"](g, 8)
    assert boundary_edge_fraction(g, part) < 0.25


# ---------------------------------------------------------------------------
# Accuracy + memory claims (Figs. 6/8) at test scale
# ---------------------------------------------------------------------------

def test_unpartitioned_accuracy_high(trained_params):
    cfg = P.PipelineConfig(dataset="csa", bits=16, num_partitions=1)
    r = P.run_pipeline(cfg, trained_params)
    assert r.accuracy >= 0.99


def test_regrowth_recovers_accuracy(trained_params):
    base = P.run_pipeline(
        P.PipelineConfig(dataset="csa", bits=16, num_partitions=4, regrow=False),
        trained_params,
    )
    regrown = P.run_pipeline(
        P.PipelineConfig(dataset="csa", bits=16, num_partitions=4, regrow=True),
        trained_params,
    )
    assert regrown.accuracy > base.accuracy + 0.02  # recovery is real
    assert regrown.accuracy >= 0.95


def test_partitioning_reduces_memory(trained_params):
    full = P.run_pipeline(
        P.PipelineConfig(dataset="csa", bits=32, num_partitions=1), trained_params
    )
    parts = P.run_pipeline(
        P.PipelineConfig(dataset="csa", bits=32, num_partitions=8), trained_params
    )
    assert parts.peak_memory_bytes < 0.5 * full.unpartitioned_memory_bytes


def test_kernel_backend_equivalence(trained_params):
    """groot Pallas backend and ref backend agree on predictions."""
    r_ref = P.run_pipeline(
        P.PipelineConfig(dataset="csa", bits=8, backend="ref"), trained_params
    )
    for backend in ("groot", "groot_fused"):
        cfg = P.PipelineConfig(dataset="csa", bits=8, backend=backend)
        r = P.run_pipeline(cfg, trained_params)
        assert r.accuracy == r_ref.accuracy


def test_full_verification_flow(trained_params):
    cfg = P.PipelineConfig(dataset="csa", bits=8, num_partitions=1)
    r = P.run_pipeline(cfg, trained_params, verify_result=True)
    assert r.verdict is not None and r.verdict.status == "verified"
    assert r.verdict.nonlinear_terms_eliminated > 0


def test_batched_graphs(trained_params):
    cfg = P.PipelineConfig(dataset="csa", bits=8, batch=4, num_partitions=2)
    r = P.run_pipeline(cfg, trained_params)
    assert r.accuracy >= 0.95
    assert r.num_nodes == 4 * A.csa_multiplier(8).num_nodes
