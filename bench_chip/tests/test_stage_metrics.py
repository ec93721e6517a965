"""What PR 27 added to the benchmark: the idle_named reader on a recorded
reduction, every new per-layer metric's file against counters as the
program renders them (and against a parent that has none of them), and
the committed cell still rehearsing on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_chip.readers import idle_named, prom_delta

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "bench_chip")
NEW = ["flush.sort_s", "flush.encode_s", "flush.upload_s", "flush.manifest_s",
       "compaction.scan_pct", "compaction.encode_pct", "compaction.commit_pct",
       "compaction.rows_x", "server.loop_lag_pct.write", "ops.xla_compiles.write",
       "ops.xla_compile_pct.write", "device.idle_named_pct.write"]
# the breakdown of rwfleet.catchup in the ledger's line of PR 26 (names as the reduction gives them)
PR26_IDLE_GAPS = [["host: nothing traced", 6.899381586999995],
                  ["futex-default-SDomainT: XLA::TPU LLO dependency graph-based opti", 0.163172204],
                  ["futex-default-SDomainT: DCE", 0.081220005],
                  ["main: tpu-quantized-all-reduce-backend-config-setter", 0.059901187]]


def spec(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def test_idle_named_on_the_recorded_reduction():
    ctx = {"trace": {"idle_gaps": PR26_IDLE_GAPS}}
    assert idle_named.read({}, ctx) == pytest.approx(4.2, abs=0.05)  # 6.9 s of 7.2 s have no name
    named = [["python: scan.materialize", 3.0], ["host: nothing traced", 1.0]]
    assert idle_named.read({}, {"trace": {"idle_gaps": named}}) == 75.0
    assert idle_named.read({}, {"trace": None}) is None
    assert idle_named.read({}, {}) is None
    assert idle_named.read({}, {"trace": {"idle_gaps": []}}) is None


def test_the_benchmark_lists_each_new_metric_last_and_with_a_file():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # by name: later PRs append entries of their own after these
    assert [m["name"] for m in bench["per_layer"] if m["name"] in NEW] == NEW
    for m in (per_layer[name] for name in NEW):
        assert m["workloads"] == ["rwfleet.catchup"]
        assert spec(m["name"])["reader"] in ("prom_delta", "idle_named")
        assert m["source"] == ("device_trace" if m["name"].startswith("device.") else "program_counter")


M0 = {'horaedb_flush_stage_seconds_sum{table="a",stage="sort"}': 1.0,
      'horaedb_flush_stage_seconds_sum{table="b",stage="sort"}': 0.5,
      'horaedb_flush_stage_seconds_sum{table="a",stage="encode"}': 2.0,
      'horaedb_compaction_stage_seconds_sum{stage="scan"}': 3.0,
      'horaedb_compaction_stage_seconds_sum{stage="sst_encode"}': 9.0,
      'horaedb_compaction_rows_total{dir="in"}': 100.0,
      'horaedb_compaction_rows_total{dir="out"}': 90.0,
      "horaedb_loop_lag_seconds_sum": 0.25,
      "horaedb_xla_compile_seconds_count": 40.0, "horaedb_xla_compile_seconds_sum": 12.0}
M1 = {'horaedb_flush_stage_seconds_sum{table="a",stage="sort"}': 3.0,
      'horaedb_flush_stage_seconds_sum{table="b",stage="sort"}': 1.5,
      'horaedb_flush_stage_seconds_sum{table="a",stage="encode"}': 2.5,
      'horaedb_compaction_stage_seconds_sum{stage="scan"}': 13.0,
      'horaedb_compaction_stage_seconds_sum{stage="sst_encode"}': 99.0,
      'horaedb_compaction_rows_total{dir="in"}': 8100.0,
      'horaedb_compaction_rows_total{dir="out"}': 7000.0,
      "horaedb_loop_lag_seconds_sum": 8.25,
      "horaedb_xla_compile_seconds_count": 46.0, "horaedb_xla_compile_seconds_sum": 13.0}


@pytest.mark.parametrize("name,expected", [
    ("flush.sort_s", 3.0 / 4000 * 1000),       # every table's sort seconds per 1,000 acked samples
    ("flush.encode_s", 0.5 / 4000 * 1000),
    ("compaction.scan_pct", 25.0),             # 10 s of a 40 s window; sst_encode is another stage
    ("compaction.rows_x", 2.0),                # rows taken in per acked sample
    ("server.loop_lag_pct.write", 20.0),
    ("ops.xla_compiles.write", 6.0),
    ("ops.xla_compile_pct.write", 2.5),
])
def test_new_counter_metrics_read_the_window(name, expected):
    ctx = {"metrics0": M0, "metrics1": M1, "window_s": 40.0, "counts": {"samples": 4000}}
    assert prom_delta.read(spec(name), ctx) == pytest.approx(expected)


@pytest.mark.parametrize("name", [n for n in NEW if not n.startswith("device.")])
def test_a_parent_without_the_counters_reads_as_nothing_or_unmoved(name):
    """The parent commit has none of the new families: a stage metric is left
    out of the line, and a metric that says `"absent": 0` reads 0."""
    old = {'horaedb_storage_write_seconds_sum{table="a"}': 1.0}
    ctx = {"metrics0": old, "metrics1": old, "window_s": 40.0, "counts": {"samples": 4000}}
    s = spec(name)
    assert prom_delta.read(s, ctx) == (0.0 if "absent" in s else None)


def test_the_cell_still_rehearses(tmp_path):
    """3 s on the CPU at two targets: every phase passes and a result is
    printed (withheld: the platform is not a TPU)."""
    r = subprocess.run([sys.executable, os.path.join(HERE, "run.py"), "--workload", "rwfleet.catchup",
                        "--seed", "2147484001", "--seconds", "3", "--trace", "0", "--set", "targets=2",
                        "--set-traffic", "warm_seconds=1", "--out", str(tmp_path / "run")],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "every phase passed" in r.stderr, r.stderr[-2000:]
    held = json.loads(r.stderr.rsplit("withheld: ", 1)[1])
    assert held["correct"] is True and held["failed"] == 0 and held["attempted"] > 0
    # the window's counters, as the program rendered them: every new family moved
    with open(tmp_path / "run" / "report.json", encoding="utf-8") as f:
        report = json.load(f)
    ctx = {"metrics0": report["metrics0"], "metrics1": report["metrics1"],
           "window_s": report["summary"]["window_s"], "counts": report["counts"]}
    for name in ("flush.sort_s", "flush.encode_s", "flush.upload_s", "flush.manifest_s",
                 "server.loop_lag_pct.write"):
        assert prom_delta.read(spec(name), ctx) > 0, name
