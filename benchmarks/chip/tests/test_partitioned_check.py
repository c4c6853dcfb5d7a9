"""The check holds a partitioned request to the plain re-growth reference,
under the partition the program chose.

A session streams csa:16 through k=4 re-grown subgraphs on the CPU (Pallas
interpreted), with seeded random weights.  ``checks.evaluate`` must pass its
real results (and a whole run of a cell with that traffic must come out
correct), and fail each fault planted in what it judges: one boundary
node moved to a neighbouring part; a partitioned result that reports no
partition, one with another k, or one with ids other than 0..k-1; a result
run at one re-growth depth and checked at another; a cell that does not
state its depth; a batched partitioned result.  On the full route it gives
the numbers of the full-graph comparison alone.
"""
import copy
import time

import jax
import numpy as np
import pytest

import checks
import harness
import traffic as traffic_mod
from references import sage_polarity, sage_regrowth

BENCH = harness.load_bench()
CELL = next(w["name"] for w in BENCH["workloads"]
            if w["config"] == "csa" and "verify" in w["name"])
_, CONFIG, _, LIMITS = harness.find(BENCH, CELL)
BITS, K = 16, 4


def workload(batch: int = 1, **session) -> dict:
    return {"bits": BITS, "batch": batch, "verify": True, "session": session}


def partitioned(hops: int = 1, batch: int = 1) -> dict:
    return workload(batch, num_partitions=K, regrow=True, regrow_hops=hops)


def run(params, wl: dict, n: int = 2):
    """(traffic, requests) of ``n`` requests through the harness's own calls."""
    traffic = traffic_mod.build(CONFIG, wl)
    sess = harness.make_session(traffic, CONFIG, params, False)
    design = traffic.program_design()
    requests = []
    for _ in range(n):
        t0 = time.perf_counter()
        res = harness.request(sess, traffic, design)
        requests.append(harness.Request(t0, time.perf_counter(), res))
    sess.close()
    return traffic, requests


def numbers(traffic, params, requests) -> tuple[dict, bool]:
    found = checks.evaluate(CONFIG, traffic, params, requests, LIMITS)
    return {k: c["value"] for k, c in found.items()}, all(c["ok"] for c in found.values())


def reporting(requests, part):
    """The requests as a program that reports ``part`` would return them."""
    out = []
    for r in requests:
        res = copy.copy(r.result)
        res.partition = part
        out.append(harness.Request(r.t0, r.t1, res))
    return out


@pytest.fixture(scope="module")
def params():
    return sage_polarity.init_params(CONFIG["gnn"], jax.random.key(1))


@pytest.fixture(scope="module")
def streamed(params):
    return run(params, partitioned())


@pytest.fixture(scope="module")
def chosen(streamed):
    traffic, _ = streamed
    part = checks.program_partition(CONFIG, traffic)
    assert checks.valid_partition(part, traffic.design["kind"].shape[0], K) is not None
    return part


def test_streamed_results_pass(params, streamed, chosen):
    traffic, requests = streamed
    assert {r.result.routing.mode for r in requests} == {"streamed"}
    assert {r.result.routing.k for r in requests} == {K}
    assert numbers(traffic, params, requests) == (
        {"logit_gap_max": 0.0, "verdict_fields_off": 0, "requests_failed": 0}, True)
    # the same, from a program that reports each request's partition
    assert numbers(traffic, params, reporting(requests, chosen))[1]


def test_partitioned_run_is_correct():
    """A whole run of a cell, its traffic streamed at a tiny width."""
    line = harness.run_cell(CELL, 2**33 + 77, 0.5, False, t_start=time.perf_counter(),
                            require_chip=False, peaks={"flops_per_s": 1e12, "bytes_per_s": 1e11},
                            shrink=partitioned())
    assert line["route"] == {"mode": "streamed", "k": K, "buckets": line["route"]["buckets"]}
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["requests_failed"]["value"] == 0


def moved_boundary_node(params, traffic, requests, part):
    """The chosen partition with one boundary node moved into a neighbouring
    part: the first such move under which the reference sets a prediction
    more than the limit below its best."""
    g, x = sage_polarity.graph(traffic.design), sage_polarity.features(traffic.design)
    pred = requests[0].result.predictions
    cross = part[g["src"]] != part[g["dst"]]
    for v, to in zip(g["src"][cross], part[g["dst"][cross]]):
        moved = part.copy()
        moved[v] = to
        if (moved == part[v]).any():
            logits = sage_regrowth.logits(params, x, g, moved, regrow=True, hops=1)
            if checks.class_numbers(logits, pred, 1)[0] > LIMITS["logit_gap_max"]:
                return moved
    pytest.fail("no single boundary move changes a class")


FAULTS = ("boundary_node_moved", "no_partition", "another_k", "gappy_ids",
          "checked_at_2_hops", "hops_not_stated", "batched")


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught(params, streamed, chosen, fault):
    traffic, requests = streamed
    if fault == "boundary_node_moved":
        requests = reporting(requests, moved_boundary_node(params, traffic, requests, chosen))
    elif fault == "no_partition":
        requests = reporting(requests, None)
    elif fault == "another_k":
        requests = reporting(requests, np.where(chosen == K - 1, 0, chosen))
    elif fault == "gappy_ids":
        requests = reporting(requests, np.where(chosen == K - 1, K, chosen))
    elif fault == "checked_at_2_hops":
        traffic = traffic_mod.build(CONFIG, partitioned(hops=2))
    elif fault == "hops_not_stated":
        traffic = traffic_mod.build(CONFIG, workload(num_partitions=K, regrow=True))
    elif fault == "batched":
        traffic, requests = run(params, partitioned(batch=2), n=1)
        assert requests[0].result.routing.mode == "streamed"
    found, ok = numbers(traffic, params, requests)
    assert not ok
    if fault in ("boundary_node_moved", "checked_at_2_hops"):
        assert found["logit_gap_max"] > LIMITS["logit_gap_max"]
    else:
        assert found["requests_failed"] == len(requests)


def full_graph_evaluate(config, traffic, params, requests, limits):
    """``checks.evaluate`` as it was before partitioned routes were judged."""
    logits = checks.reference_logits(config, traffic, params)
    gap = 0.0
    off = failed = 0
    verdicts: dict = {}
    for r in requests:
        res = r.result
        if res is None or getattr(res, "predictions", None) is None:
            failed += 1
            continue
        gap = max(gap, checks.class_numbers(logits, res.predictions, traffic.batch)[0])
        off += checks.verdict_fields_off(config, traffic, res, verdicts)
    if not any(r.result is not None for r in requests):
        failed = max(failed, 1)
    found = {"logit_gap_max": gap, "verdict_fields_off": off, "requests_failed": failed}
    return {k: {"value": v, "limit": limits[k], "ok": v <= limits[k]} for k, v in found.items()}


@pytest.mark.parametrize("altered", [False, True])
def test_full_route_numbers_unchanged(params, altered):
    traffic, requests = run(params, workload())
    assert {r.result.routing.mode for r in requests} == {"full"}
    if altered:
        res = copy.copy(requests[0].result)
        res.predictions = res.predictions.copy()
        res.predictions[0] = 1 if res.predictions[0] != 1 else 2
        requests = [harness.Request(0.0, 1.0, res), harness.Request(1.0, 2.0, None)]
    assert (checks.evaluate(CONFIG, traffic, params, requests, LIMITS)
            == full_graph_evaluate(CONFIG, traffic, params, requests, LIMITS))
