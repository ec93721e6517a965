"""Each work count on hand-worked shapes, the peaks table, and the readers
on hand-made inputs."""

import json
import os

import pytest

from bench_chip.readers import client, device_trace, kernels_delta, prom_delta, span_self
from bench_chip.work import remote_write_closed as w_write
from bench_chip.work import tsbs_queries as w_query

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def test_double_groupby_work():
    one = w_query.per_query(load("traffic", "tsbs-double-groupby-1.json"),
                            load("configs", "tsbs-devops-cpu-100.json"))
    # 100 hosts x 12 h x 360 rows an hour; 100 x 12 cells out
    assert one["rows"] == 432_000 and one["cells"] == 1200
    assert one["bytes"] == 432_000 * 24 + 1200 * 8


def test_single_groupby_work():
    one = w_query.per_query(load("traffic", "tsbs-single-groupby-1-1-1.json"),
                            load("configs", "tsbs-devops-cpu-100.json"))
    assert one["rows"] == 360 and one["cells"] == 60
    assert w_query.logical(load("traffic", "tsbs-single-groupby-1-1-1.json"),
                           load("configs", "tsbs-devops-cpu-100.json"),
                           {"operations": 2.5})["bytes"] == 2.5 * (360 * 24 + 480)


def test_write_work():
    t = load("traffic", "rw-catchup.json")
    need = w_write.logical(t, {}, {"samples": 4000, "merged_sst_bytes": 1000.0})
    assert need["bytes"] == 2 * 24 * 4000 + 2 * 1000
    assert need["flops"] == pytest.approx(4000 * 10.965784 + 1000 / 24, rel=1e-6)


def test_peaks_known_and_unknown():
    assert device_trace.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device_trace.peaks("TPU v9")


def test_roofline_and_idle():
    ctx = {"trace": {"busy_s": 0.5, "window_s": 4.0}, "trace_counts": {"operations": 10},
           "trace_metrics0": {}, "trace_metrics1": {}, "device_kind": "TPU v5 lite",
           "traffic": load("traffic", "tsbs-double-groupby-1.json"),
           "config": load("configs", "tsbs-devops-cpu-100.json")}
    assert device_trace.read({"field": "idle_pct"}, ctx) == pytest.approx(87.5)
    least = 10 * (432_000 * 24 + 9600) / 819e9
    assert device_trace.read({"field": "roofline_pct"}, ctx) == pytest.approx(100 * least / 0.5)
    ctx["trace"]["busy_s"] = 0.0  # nothing to read is nothing, never 0
    assert device_trace.read({"field": "roofline_pct"}, ctx) is None
    ctx["trace"] = None
    assert device_trace.read({"field": "idle_pct"}, ctx) is None


def test_prom_delta():
    m0 = {'a_sum{stage="x"}': 1.0, 'a_sum{stage="y"}': 2.0, "n_total": 4.0}
    m1 = {'a_sum{stage="x"}': 2.0, 'a_sum{stage="y"}': 5.0, 'a_sum{stage="z"}': 9.0, "n_total": 6.0}
    ctx = {"metrics0": m0, "metrics1": m1, "counts": {"queries": 4}, "window_s": 2.0}
    spec = {"sum": [{"name": "a_sum", "labels": {"stage": ["x", "y"]}}], "per": "queries"}
    assert prom_delta.read(spec, ctx) == pytest.approx(1.0)
    spec = {"sum": [{"name": "a_sum"}], "per": [{"name": "n_total"}], "scale": 10}
    assert prom_delta.read(spec, ctx) == pytest.approx(10 * 13.0 / 2.0)
    assert prom_delta.read({"sum": [{"name": "nope"}], "per": "window_s"}, ctx) is None
    assert prom_delta.read({"sum": [{"name": "nope"}], "per": "window_s", "absent": 0}, ctx) == 0.0
    assert prom_delta.read({"sum": [{"name": "a_sum"}], "per": "missing"}, ctx) is None


def test_span_self_and_others():
    tree = {"root": {"name": "GET /q", "start_ms": 0.0, "duration_s": 1.0,
                     "attrs": {"stages": {"io": 0.2, "k": 0.1}},
                     "children": [{"name": "c", "start_ms": 100.0, "duration_s": 0.2, "children": []},
                                  {"name": "d", "start_ms": 200.0, "duration_s": 0.2, "children": []}]}}
    ctx = {"trees": [tree]}
    assert span_self.read({"span": "GET /q"}, ctx) == pytest.approx(700.0)
    assert span_self.read({"span": "GET /q", "minus_attr": "stages"}, ctx) == pytest.approx(400.0)
    assert span_self.read({"span": "other"}, ctx) is None
    k0 = {"kernels": [{"compiles": 2}, {"compiles": 1}]}
    k1 = {"kernels": [{"compiles": 2}, {"compiles": 3}, {"compiles": 1}]}
    assert kernels_delta.read({}, {"kernels0": k0, "kernels1": k1}) == 3
    assert client.read({"field": "rate"}, {"summary": {"rate": 2.5}}) == 2.5


@pytest.mark.parametrize("where_from", [("..", "BENCHMARK.json"),
                                        ("tests", "BENCHMARK.with-query-cells.json")])
def test_every_metric_of_the_benchmark_has_its_file(where_from):
    bench = load(*where_from)
    for section, where in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in bench[section]:
            spec = load(where, m["name"] + ".json")
            assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells and w in moved.get("workloads", cells)
