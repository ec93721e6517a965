"""The TSBS query cell as BENCHMARK.json holds it: it rehearses on the CPU
at a tiny size from the committed file, every layer-metric file it brought
parses and reads a number or nothing, and the lower-precision control comes
out as not correct at the cell's own size."""

import json
import os

import pytest

from bench_chip import run
from bench_chip.readers import fold_roofline, prom_delta
from bench_chip.tests import control_lower_precision as control
from bench_chip.work import tsbs_queries as tsbs_work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
TINY_TSBS = ["--set", "hosts=4", "--set", "hours=14"]
CELL, TRAFFIC = "tsbs100.double-groupby-1", "tsbs-double-groupby-1"
# the quantities the cell reads: the ten whose files PR 26 committed and the ten of PR 30
KEPT = ["client.turnaround_ms", "server.queue_wait_ms", "server.batch_group", "promql.self_ms",
        "scan.host_s", "scan.device_s", "serving.hit_pct", "ops.compiles.query",
        "ops.query_roofline", "device.idle_pct.query"]
BROUGHT = ["ops.xla_compiles.query", "ops.xla_compile_pct.query", "server.loop_lag_pct.query",
           "device.idle_named_pct.query", "pushdown.fold_prep_s", "pushdown.fold_kernel_s",
           "pushdown.fold_xfer_s", "pushdown.folds", "pushdown.pad_x", "ops.fold_roofline"]
# A PR that changes the program may add no end-to-end entry, and a cell has to report one
# besides `setup_s`: the cell is appended to `write_ack_p50_ms` and `write_ack_p95_ms`, whose
# files read the p50 and the p95 of the window's requests (here: a query's answer, PERF.md
# section 2). The two numbers under their own names are per layer, reader `client`, read
# from the summary's query class.
CLIENT = {"client.query_rate": "query_rate", "client.query_p95_ms": "query_p95_ms"}
HELD = ("write_ack_p50_ms", "write_ack_p95_ms")
FROM_THE_PROGRAM = [n for n in BROUGHT if n.startswith("pushdown.")]


def load(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def rehearse(tmp_path, workload, trace):
    result, why = run.run_cell(["--workload", workload, "--seed", str(2**31 + 1234), "--seconds", "2",
                                "--trace", str(trace), "--out", str(tmp_path / "run"), *TINY_TSBS])
    assert result is not None, why
    assert "platform 'cpu'" in why
    return result


def test_the_committed_benchmark_enters_the_cell_as_the_issue_names_it():
    bench = load(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "tsbs-devops-cpu-100" and cells[CELL]["chips"] == 1
    assert cells[CELL]["traffic"] == TRAFFIC
    # the check reads a new cell's spread at the parent too, and the parent compiles 266
    # programs a window there (ledger, PR 29): a later PR's to add, as data files
    assert "tsbs100.single-groupby-1-1-1" not in cells
    config = next(c for c in bench["configs"] if c["name"] == "tsbs-devops-cpu-100")
    assert config["reduced"] == ["hours"] and len(config["source"]) <= 200
    assert load(ROOT, config["file"])["hours"] == 16
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert [m["name"] for m in bench["end_to_end"]] == [*HELD, "setup_s"]  # none added
    for held in HELD:  # the cell appended, the bound and the file as they were
        assert load(HERE, "end_to_end", held + ".json")["reader"] == "client"
    # the write cell's p95 spread by more than half of any bound the check allows, so it
    # reads per layer there (client.ack_p95_ms.write) and end to end in this cell alone
    assert e2e["write_ack_p50_ms"]["workloads"] == ["rwfleet.catchup", CELL]
    assert e2e["write_ack_p95_ms"]["workloads"] == [CELL]
    assert load(HERE, "end_to_end", "write_ack_p95_ms.json") == load(HERE, "end_to_end", "query_p95_ms.json")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in [*CLIENT, *KEPT, *BROUGHT]:  # every quantity is one entry, read in this cell alone
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["moves"] in HELD
    for name, field in CLIENT.items():
        assert load(HERE, "layer_metrics", name + ".json") == {"reader": "client", "field": field}
    # by name, in the order the cell entered them: later PRs append entries of their own
    entered = [*CLIENT, *KEPT, *BROUGHT]
    assert [m["name"] for m in bench["per_layer"] if m["name"] in entered] == entered


def test_the_cell_rehearses_from_the_committed_benchmark(tmp_path):
    bench = load(ROOT, "BENCHMARK.json")
    workload = CELL
    result = rehearse(tmp_path, workload, 0)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}
    assert set(result["metrics"]) == want and "setup_s" in want and len(want) >= 2
    assert all(v["value"] <= v["limit"] for v in result["compared"].values())


def test_a_traced_rehearsal_reads_the_folds_own_metrics(tmp_path):
    result = rehearse(tmp_path, CELL, 1)
    assert result["correct"] is True
    m = result["metrics"]
    # the program's counters are there on any platform; the device trace's are not on the CPU
    assert set(FROM_THE_PROGRAM) <= set(m) and "ops.fold_roofline" not in m
    assert m["pushdown.folds"]["value"] >= 1.0 and m["pushdown.pad_x"]["value"] >= 1.0
    assert m["ops.compiles.query"]["value"] == 0 and m["ops.xla_compiles.query"]["value"] == 0
    assert m["server.batch_group"]["value"] == 1.0
    assert m["client.query_rate"]["value"] > 0 and m["client.query_p95_ms"]["value"] > 0


@pytest.mark.parametrize("name", BROUGHT)
def test_a_new_metric_file_reads_a_number_or_nothing(name):
    """Against a program that has none of this PR's counters (the parent)
    and a run with no trace: nothing, never 0, unless the file says
    `"absent": 0`."""
    spec = load(HERE, "layer_metrics", name + ".json")
    assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
    reader = run.kind("readers", spec["reader"])
    ctx = {"metrics0": {"other_total": 1.0}, "metrics1": {"other_total": 2.0},
           "counts": {"queries": 10, "operations": 10}, "window_s": 40.0, "trace": None,
           "trace_counts": {"operations": 3.0}, "kernels0": {"kernels": []}, "kernels1": {"kernels": []},
           "traffic": load(HERE, "traffic", "tsbs-double-groupby-1.json"),
           "config": load(HERE, "configs", "tsbs-devops-cpu-100.json"), "device_kind": "TPU v5 lite"}
    got = reader.read(spec, ctx)
    assert got == spec["absent"] if "absent" in spec else got is None


def test_the_folds_metrics_on_hand_made_counters():
    m0 = {'horaedb_scan_stage_seconds_sum{stage="fold_kernel"}': 1.0,
          'horaedb_scan_stage_seconds_sum{stage="fold_h2d"}': 0.5,
          'horaedb_scan_stage_seconds_sum{stage="fold_d2h"}': 0.25,
          'horaedb_scan_stage_seconds_sum{stage="kernel"}': 7.0,
          'horaedb_pushdown_folds_total{impl="runs"}': 10.0,
          'horaedb_pushdown_rows_total{kind="real"}': 1000.0,
          'horaedb_pushdown_rows_total{kind="padded"}': 500.0}
    m1 = {k: v * 3 for k, v in m0.items()}
    m1['horaedb_pushdown_folds_total{impl="reduceat"}'] = 4.0
    ctx = {"metrics0": m0, "metrics1": m1, "counts": {"queries": 8}, "window_s": 40.0}
    spec = lambda name: load(HERE, "layer_metrics", name + ".json")  # noqa: E731
    assert prom_delta.read(spec("pushdown.fold_kernel_s"), ctx) == pytest.approx(2.0 / 8)
    assert prom_delta.read(spec("pushdown.fold_xfer_s"), ctx) == pytest.approx(1.5 / 8)
    assert prom_delta.read(spec("pushdown.folds"), ctx) == pytest.approx(24.0 / 8)
    assert prom_delta.read(spec("pushdown.pad_x"), ctx) == pytest.approx(3000.0 / 2000.0)


def test_fold_roofline_is_over_the_one_programs_seconds():
    traffic = load(HERE, "traffic", "tsbs-double-groupby-1.json")
    ctx = {"trace": {"busy_s": 9.0, "window_s": 13.0,
                     "device_ops": [["jit_other", 8.0], ["jit_downsample_fold", 0.02]]},
           "trace_counts": {"operations": 50.0}, "device_kind": "TPU v5 lite", "traffic": traffic,
           "config": load(HERE, "configs", "tsbs-devops-cpu-100.json")}
    spec = load(HERE, "layer_metrics", "ops.fold_roofline.json")
    least = 50 * (432_000 * 24 + 1200 * 8) / 819e9
    assert fold_roofline.read(spec, ctx) == pytest.approx(100 * least / 0.02)
    # the work function on a case small enough to count by hand: 4 hosts, 2 h at 10 s are
    # 2,880 rows of 24 B, and 4 hosts x 2 hourly steps are 8 cells of 8 B
    small = dict(traffic, range_s=7200)
    assert tsbs_work.per_query(small, {"hosts": 4, "log_interval_s": 10}) == {
        "rows": 2880, "cells": 8, "bytes": 2880 * 24 + 8 * 8, "flops": 2888}
    ctx["trace"]["device_ops"] = [["jit_other", 8.0]]  # a program without the fold: nothing
    assert fold_roofline.read(spec, ctx) is None
    ctx["trace_counts"] = {"operations": 0.0}
    assert fold_roofline.read(spec, ctx) is None


def test_the_float32_control_fails_value_gap_at_the_cells_own_size():
    faults, gap, limit = control.gap(TRAFFIC, 2**31 + 29, 60)
    assert faults == 0 and gap > limit
    faults, gap, limit = control.gap(TRAFFIC, 2**31 + 29, 60, dtype="float64")
    assert faults == 0 and gap <= limit
