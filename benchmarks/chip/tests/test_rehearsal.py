"""CPU rehearsal: every cell's path at a tiny width, Pallas interpreted.

Checks that the harness finds each cell's configuration, traffic, limits and
metric readers by name, and that the result line has the contract's keys
with the check last.  No number here is a device number.
"""
import json
import time

import pytest

import harness

BENCH = harness.load_bench()
CELLS = [w["name"] for w in BENCH["workloads"]]
CPU_PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
SEED = 2**33 + 12345          # wider than 32 bits: a run's seed may be


def tiny(name: str, bits: int) -> dict:
    batch = json.loads((harness.HERE / "workloads" / f"{name}.json").read_text()).get("batch", 1)
    return {"bits": bits, "batch": min(batch, 2)}


def rehearse(name: str, trace: bool, bits: int = 8) -> dict:
    return harness.run_cell(name, SEED, 0.5, trace, t_start=time.perf_counter(),
                            require_chip=False, peaks=CPU_PEAK, shrink=tiny(name, bits))


def check_line(line: dict, name: str, trace: bool) -> None:
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(line, allow_nan=False)
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    want = {m["name"]: m["unit"] for m in harness.metrics_for(BENCH, name, trace)}
    for m, v in line["metrics"].items():
        assert want[m] == v["unit"] and isinstance(v["value"], (int, float))
    if not trace:
        assert set(line["metrics"]) == set(want)
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_by_name(name):
    check_line(rehearse(name, False), name, False)


def test_traced_run_reports_per_layer_metrics():
    name = CELLS[0]
    line = rehearse(name, True)
    check_line(line, name, True)
    # the program's spans were found and read
    assert {"parse_s", "prepare_s", "execute_s", "window_compiles"} <= set(line["metrics"])


def test_every_metric_and_file_is_found_by_name():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    for c in BENCH["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        for sub in ("workloads", "limits"):
            assert (harness.HERE / sub / f"{w['name']}.json").is_file()
