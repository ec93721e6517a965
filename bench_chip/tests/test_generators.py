"""Every generator is a pure function of the seed; the TSBS query strings
parse with the program's own parser; the hand-written remote-write
encoder agrees with the protobuf runtime."""

import json
import os
import urllib.parse

import numpy as np

from bench_chip import wire
from bench_chip.fleets import node_exporter, tsbs_devops
from bench_chip.generators import remote_write_closed, tsbs_queries
from bench_chip.reference import tsbs_queries as ref

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG_SEED = 2**31 + 12345


def load(*parts):
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def tsbs(seed, mix="tsbs-double-groupby-1", hosts=6, hours=14):
    cfg = dict(load("configs", "tsbs-devops-cpu-100.json"), hosts=hosts, hours=hours)
    fleet = tsbs_devops.build(cfg, seed)
    return fleet, tsbs_queries.build(load("traffic", mix + ".json"), cfg, fleet, seed)


def first(mix, worker, n):
    it = mix.stream(worker, 0)
    return [next(it) for _ in range(n)]


def test_tsbs_same_seed_same_bytes_new_seed_new_windows():
    fa, a = tsbs(BIG_SEED)
    fb, b = tsbs(BIG_SEED)
    fc, c = tsbs(BIG_SEED + 1)
    assert np.array_equal(fa.values, fb.values) and fa.host_tags == fb.host_tags
    assert [r.path for r in first(a, 3, 20)] == [r.path for r in first(b, 3, 20)]
    assert [r.path for r in first(a, 3, 20)] != [r.path for r in first(c, 3, 20)]
    assert [r.path for r in first(a, 3, 20)] != [r.path for r in first(a, 4, 20)]
    assert not np.array_equal(fa.values, fc.values)
    assert list(fa.batches())[0][0] == list(fb.batches())[0][0]


def test_tsbs_windows_lie_inside_the_data_and_queries_parse():
    from horaedb_tpu.promql import parse

    for name in ("tsbs-double-groupby-1", "tsbs-single-groupby-1-1-1"):
        fleet, mix = tsbs(7, name)
        for req in first(mix, 0, 200):
            q = urllib.parse.parse_qs(req.path.split("?", 1)[1])
            parse(q["query"][0])
            start, end, step = int(q["start"][0]), int(q["end"][0]), int(q["step"][0])
            assert (start - step) * 1000 >= fleet.ts[0]
            assert end * 1000 <= fleet.ts[-1] + fleet.interval_ms
            assert (end - start) // step + 1 == mix.range_s // mix.step_s


def test_tsbs_fleet_shape():
    fleet, _ = tsbs(1, hosts=5, hours=14)
    assert fleet.values.shape == (10, 5, 14 * 360)
    assert fleet.values.min() >= 0.0 and fleet.values.max() <= 100.0
    assert len(fleet.series_labels()) == 50 and len(fleet.host_tags[0]) == 10


def test_reference_window_is_half_open():
    ts = 1000 * np.arange(0, 100, 10, dtype=np.int64)
    v = np.arange(10.0)[None, :]
    steps = ref.steps_ms(30, 90, 30)
    got = ref.answer(v, ts, steps, 30, "mean", None)
    # [0,30) -> rows 0,1,2; [30,60) -> 3,4,5; [60,90) -> 6,7,8
    assert got.tolist() == [[1.0, 4.0, 7.0]]
    assert ref.answer(v, ts, steps, 30, "max", "max").tolist() == [[2.0, 5.0, 8.0]]


def test_node_fleet_is_960_series_a_target_and_pure():
    cfg = dict(load("configs", "prom-remote-write-fleet.json"), targets=3)
    a, b, c = (node_exporter.build(cfg, s) for s in (BIG_SEED, BIG_SEED, 5))
    assert len(a.per_target) == 960 and a.n_series == 2880
    assert len({tuple(sorted(a.labels(i).items())) for i in range(a.n_series)}) == a.n_series
    assert np.array_equal(a.values(7), b.values(7)) and not np.array_equal(a.values(7), c.values(7))
    assert not np.array_equal(a.values(7), a.values(8))
    counters = a.is_counter
    assert np.all(a.values(9)[counters] >= a.values(8)[counters])  # counters never fall


def test_write_mix_same_seed_same_bytes_and_whole_requests():
    cfg = load("configs", "prom-remote-write-fleet.json")
    traffic = load("traffic", "rw-catchup.json")
    full = node_exporter.build(cfg, 1)
    assert full.n_series == 96_000
    assert full.n_series % (traffic["shards"] * traffic["samples_per_send"]) == 0
    small = dict(cfg, targets=2)
    mixes = [remote_write_closed.build(traffic, small, node_exporter.build(small, s), s)
             for s in (BIG_SEED, BIG_SEED, 6)]
    bodies = [[r.body for r in m.stream(1, 0, 2)] for m in mixes]
    assert bodies[0] == bodies[1] and bodies[0] != bodies[2]
    metas = [r.meta for r in mixes[0].stream(1, 0, 2)]
    assert metas == sorted(metas, key=lambda m: (m[2], m[1]))  # time order


def test_encoder_against_protobuf_runtime():
    from horaedb_tpu.pb import remote_write_pb2
    import pyarrow as pa

    labels = [{"__name__": "m", "a": "1"}, {"__name__": "m", "a": "2", "b": "x" * 200}]
    tmpl = wire.Template([wire.series_labels(lb) for lb in labels], 3)
    values = np.asarray([[1.5, -2.0, 1e300], [0.0, 5e-324, 99.99967667212489]])
    ts = np.asarray([1_767_225_600_000, 1_767_225_610_000, 1_767_225_620_001])
    raw = tmpl.fill(values, ts)
    req = remote_write_pb2.WriteRequest()
    req.ParseFromString(pa.Codec("snappy").decompress(wire.compress(raw), len(raw), asbytes=True))
    assert len(req.timeseries) == 2
    for series, lb, vals in zip(req.timeseries, labels, values):
        assert {l.name.decode(): l.value.decode() for l in series.labels} == lb
        assert [(s.timestamp, s.value) for s in series.samples] == list(zip(ts.tolist(), vals.tolist()))
    # a second fill overwrites in place
    raw2 = tmpl.fill(values + 1.0, ts + 1)
    req.ParseFromString(raw2)
    assert req.timeseries[0].samples[0].value == 2.5 and req.timeseries[1].samples[2].timestamp == ts[2] + 1
