"""Whole runs at tiny sizes on the CPU: every phase passes and the run
exits non-zero with the platform named; each fault a cell can have, and
each cell's control, comes out as not correct.

These drive the harness past its look for a chip (run.run_cell returns
the result it withholds) with the timed path broken underneath."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_chip import child, run
from bench_chip.tests import control_lower_precision as control

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the cells this PR could not hold on the chip, as a later PR would enter them
CANDIDATE = os.path.join(ROOT, "bench_chip", "tests", "BENCHMARK.with-query-cells.json")
TINY_TSBS = ["--set", "hosts=4", "--set", "hours=14"]
TINY_FLEET = ["--set", "targets=2", "--set-traffic", "warm_seconds=1"]


def cell(tmp_path, workload, *more, seconds="2"):
    result, why = run.run_cell(["--workload", workload, "--seed", str(2**31 + 77), "--seconds", seconds,
                                "--trace", "0", "--out", str(tmp_path / "run"), "--benchmark", CANDIDATE,
                                *more])
    assert result is not None, why
    assert "platform 'cpu'" in why
    return result


def test_rehearsal_passes_every_phase_and_exits_nonzero(tmp_path):
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench_chip", "run.py"), "--workload",
                        "tsbs100.single-groupby-1-1-1", "--seed", "3000000019", "--seconds", "2",
                        "--trace", "1", "--out", str(tmp_path / "run"), "--benchmark", CANDIDATE,
                        "--set", "hosts=4", "--set", "hours=2"],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "platform 'cpu'" in r.stderr and "every phase passed" in r.stderr
    held = json.loads(r.stderr.rsplit("withheld: ", 1)[1])
    assert held["correct"] is True and held["failed"] == 0 and held["attempted"] > 0
    assert list(held)[-1] == "compared"
    assert "promql.self_ms" in held["metrics"] and "query_rate" not in held["metrics"]


def test_the_committed_cell_rehearses(tmp_path):
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench_chip", "run.py"), "--workload",
                        "rwfleet.catchup", "--seed", "2147483999", "--seconds", "2", "--trace", "0",
                        "--out", str(tmp_path / "run"), *TINY_FLEET],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0 and r.stdout.strip() == ""
    held = json.loads(r.stderr.rsplit("withheld: ", 1)[1])
    assert held["correct"] is True and set(held["metrics"]) == {"write_ack_p50_ms", "write_ack_p95_ms", "setup_s"}
    assert [ln.split(":")[0] for ln in r.stderr.strip().splitlines()[-5:-1]] == [
        "compared unanswered", "compared lost_samples", "compared wrong_values", "compared extra_samples"]


def test_no_result_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench_chip"), tmp_path / "bench_chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, str(tmp_path / "bench_chip" / "run.py"), "--workload",
                        "rwfleet.catchup", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_sound_runs_are_correct(tmp_path):
    assert cell(tmp_path, "tsbs100.double-groupby-1", *TINY_TSBS)["correct"] is True
    assert cell(tmp_path, "rwfleet.catchup", *TINY_FLEET)["correct"] is True


def altering(monkeypatch, match, change):
    """Alter one answer where it is produced: the `nth` one that matches."""
    real = child.Conn.request
    seen = {"n": 0}

    def request(self, method, path, body=None, headers=None, timeout=None):
        hit = match(method, path)
        if hit:
            seen["n"] += 1
        fake = change(seen["n"], method, path, body) if hit else None
        if fake is not None and fake[0] == "instead":
            return fake[1]
        status, got = real(self, method, path, body, headers, timeout)
        if fake is not None:
            got = fake[1](got)
        return status, got

    monkeypatch.setattr(child.Conn, "request", request)


def nudge_one_value(body: bytes, factor: float) -> bytes:
    doc = json.loads(body)
    pair = doc["data"]["result"][0]["values"][-1]
    pair[1] = repr(float(pair[1]) * factor)
    return json.dumps(doc).encode()


@pytest.mark.parametrize("workload,factor", [
    ("tsbs100.double-groupby-1", 1.0 + 1e-6),
    ("tsbs100.single-groupby-1-1-1", 1.0 + 3e-16),  # one float64 step: selections are exact
])
def test_an_altered_answer_is_not_correct(tmp_path, monkeypatch, workload, factor):
    altering(monkeypatch, lambda m, p: p.startswith("/api/v1/query_range") and "explain" not in p,
             lambda n, m, p, b: ("after", lambda got: nudge_one_value(got, factor)) if n == 40 else None)
    result = cell(tmp_path, workload, *TINY_TSBS)
    assert result["correct"] is False
    assert result["compared"]["value_gap"]["value"] > result["compared"]["value_gap"]["limit"]


def test_a_dropped_answer_series_is_not_correct(tmp_path, monkeypatch):
    def drop(got):
        doc = json.loads(got)
        doc["data"]["result"].pop()
        return json.dumps(doc).encode()
    altering(monkeypatch, lambda m, p: p.startswith("/api/v1/query_range") and "explain" not in p,
             lambda n, m, p, b: ("after", drop) if n == 40 else None)
    result = cell(tmp_path, "tsbs100.double-groupby-1", *TINY_TSBS)
    assert result["correct"] is False and result["compared"]["shape_faults"]["value"] > 0


def test_an_acknowledged_write_that_was_not_stored_is_not_correct(tmp_path, monkeypatch):
    # 2 targets x 960 series over 8 shards: 240 samples a request
    altering(monkeypatch, lambda m, p: m == "POST" and p == "/api/v1/write",
             lambda n, m, p, b: ("instead", (200, b'{"samples": 240}')) if n == 200 else None)
    result = cell(tmp_path, "rwfleet.catchup", *TINY_FLEET, "--set-traffic", "readback_pairs=400")
    assert result["correct"] is False and result["compared"]["lost_samples"]["value"] > 0


def test_an_altered_read_back_is_not_correct(tmp_path, monkeypatch):
    altering(monkeypatch, lambda m, p: "max_over_time" in p and "explain" not in p,
             lambda n, m, p, b: ("after", lambda got: nudge_one_value(got, 1.0 + 3e-16)) if n == 3 else None)
    result = cell(tmp_path, "rwfleet.catchup", *TINY_FLEET)
    assert result["correct"] is False and result["compared"]["wrong_values"]["value"] > 0


def test_control_buffered_ingest_loses_acknowledged_samples(tmp_path):
    """The write cell's control: the program's own buffered-ingest path
    (ingest_buffer_rows > 0) acknowledges before the SST is written, so a
    SIGKILL loses acknowledged samples: durability on ack is broken."""
    toml = json.dumps({"ingest_buffer_rows = 0": "ingest_buffer_rows = 100000000",
                       'ingest_flush_interval = "1s"': 'ingest_flush_interval = "1h"'})
    result = cell(tmp_path, "rwfleet.catchup", *TINY_FLEET, "--child-toml", toml)
    assert result["correct"] is False and result["compared"]["lost_samples"]["value"] > 0


@pytest.mark.parametrize("traffic", ["tsbs-double-groupby-1", "tsbs-single-groupby-1-1-1"])
def test_control_float32_reference_is_not_correct(traffic):
    config = dict(control.load("configs", "tsbs-devops-cpu-100.json"), hosts=10, hours=14)
    faults, gap, limit = control.gap(traffic, 11, 50, config=config)
    assert faults == 0 and gap > limit
    faults, gap, limit = control.gap(traffic, 11, 50, dtype="float64", config=config)
    assert faults == 0 and gap <= limit
