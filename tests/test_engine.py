"""Metric engine tests: seahash conformance + ingest->index->query loops."""

import numpy as np
import pytest

from horaedb_tpu.engine import MetricEngine, QueryRequest
from horaedb_tpu.engine.types import (
    seahash,
    series_id_of,
    series_key_of,
    tag_hash_of,
)
from horaedb_tpu.ingest import PooledParser
from horaedb_tpu.objstore import MemStore
from horaedb_tpu.pb import remote_write_pb2
from tests.conftest import async_test

HOUR = 3_600_000


class TestSeahash:
    def test_crate_documented_vector(self):
        """The seahash crate's doc example: hash(b"to be or not to be")."""
        assert seahash(b"to be or not to be") == 1988685042348123509

    def test_determinism_and_spread(self):
        xs = {seahash(f"metric-{i}".encode()) for i in range(1000)}
        assert len(xs) == 1000
        assert seahash(b"abc") == seahash(b"abc")

    def test_series_key_injective(self):
        a = series_key_of([(b"a", b"x=y"), (b"b", b"z")])
        b = series_key_of([(b"a", b"x"), (b"=yb", b"z")])
        assert a != b

    def test_series_key_order_insensitive(self):
        a = series_key_of([(b"a", b"1"), (b"b", b"2")])
        b = series_key_of([(b"b", b"2"), (b"a", b"1")])
        assert a == b
        assert series_id_of(a) == series_id_of(b)

    def test_tag_hash_distinct(self):
        assert tag_hash_of(b"host", b"a") != tag_hash_of(b"host", b"b")
        assert tag_hash_of(b"hos", b"ta") != tag_hash_of(b"host", b"a")


def make_remote_write(series_samples) -> bytes:
    """series_samples: list of (labels dict incl __name__, [(ts, val), ...])."""
    req = remote_write_pb2.WriteRequest()
    for labels, samples in series_samples:
        ts = req.timeseries.add()
        for k in sorted(labels):
            lab = ts.labels.add()
            lab.name = k.encode()
            lab.value = labels[k].encode()
        for t, v in samples:
            s = ts.samples.add()
            s.timestamp = t
            s.value = v
    return req.SerializeToString()


async def open_engine(store):
    return await MetricEngine.open(
        "metrics-db", store, segment_duration_ms=HOUR, enable_compaction=False
    )


class TestMetricEngine:
    @async_test
    async def test_write_then_query_raw(self):
        store = MemStore()
        eng = await open_engine(store)
        payload = make_remote_write(
            [
                ({"__name__": "cpu", "host": "a"}, [(1000, 1.0), (2000, 2.0)]),
                ({"__name__": "cpu", "host": "b"}, [(1500, 5.0)]),
                ({"__name__": "mem", "host": "a"}, [(1000, 9.0)]),
            ]
        )
        parsed = PooledParser.decode(payload)
        n = await eng.write_parsed(parsed)
        assert n == 4

        t = await eng.query(QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000))
        assert t.num_rows == 3
        assert sorted(t.column("value").to_pylist()) == [1.0, 2.0, 5.0]

        # tag filter: host=a only
        t = await eng.query(
            QueryRequest(
                metric=b"cpu", start_ms=0, end_ms=10_000, filters=[(b"host", b"a")]
            )
        )
        assert sorted(t.column("value").to_pylist()) == [1.0, 2.0]
        await eng.close()

    @async_test
    async def test_unknown_metric_and_no_match_filter(self):
        store = MemStore()
        eng = await open_engine(store)
        payload = make_remote_write([({"__name__": "cpu", "host": "a"}, [(1000, 1.0)])])
        await eng.write_parsed(PooledParser.decode(payload))
        assert await eng.query(QueryRequest(metric=b"nope", start_ms=0, end_ms=10)) is None
        out = await eng.query(
            QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000, filters=[(b"host", b"zzz")])
        )
        assert out is None
        await eng.close()

    @async_test
    async def test_overwrite_same_series_same_ts(self):
        """Same (metric, series, ts) written twice: newest seq wins."""
        store = MemStore()
        eng = await open_engine(store)
        p1 = make_remote_write([({"__name__": "cpu", "host": "a"}, [(1000, 1.0)])])
        p2 = make_remote_write([({"__name__": "cpu", "host": "a"}, [(1000, 42.0)])])
        await eng.write_parsed(PooledParser.decode(p1))
        await eng.write_parsed(PooledParser.decode(p2))
        t = await eng.query(QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000))
        assert t.column("value").to_pylist() == [42.0]
        await eng.close()

    @async_test
    async def test_downsample_query(self):
        store = MemStore()
        eng = await open_engine(store)
        samples_a = [(i * 1000, float(i)) for i in range(60)]  # 1 min of 1s points
        samples_b = [(i * 1000, 10.0) for i in range(60)]
        payload = make_remote_write(
            [
                ({"__name__": "cpu", "host": "a"}, samples_a),
                ({"__name__": "cpu", "host": "b"}, samples_b),
            ]
        )
        await eng.write_parsed(PooledParser.decode(payload))
        out = await eng.query(
            QueryRequest(metric=b"cpu", start_ms=0, end_ms=60_000, bucket_ms=15_000)
        )
        tsids, grids = out
        assert len(tsids) == 2
        assert grids["mean"].shape == (2, 4)
        # host=b series is constant 10.0
        key_b = series_id_of(series_key_of([(b"host", b"b")]))
        row_b = tsids.index(key_b)
        np.testing.assert_allclose(grids["mean"][row_b], 10.0)
        # host=a buckets: mean of 0..14 = 7, 15..29 = 22, ...
        row_a = 1 - row_b
        np.testing.assert_allclose(grids["mean"][row_a], [7.0, 22.0, 37.0, 52.0])
        await eng.close()

    @async_test
    async def test_downsample_pushdown_matches_materializing_path(self):
        """The pushdown grids must equal aggregating the raw scan output —
        across segments and with overwritten duplicates."""
        store = MemStore()
        eng = await open_engine(store)
        rng = np.random.default_rng(9)
        series = [{"__name__": "m", "host": f"h{i}"} for i in range(4)]
        for _round in range(3):  # overlapping writes create duplicates
            payload = make_remote_write(
                [
                    (
                        s,
                        [
                            (int(t), float(rng.normal()))
                            for t in rng.integers(0, 2 * HOUR, 25)
                        ],
                    )
                    for s in series
                ]
            )
            await eng.write_parsed(PooledParser.decode(payload))
        out = await eng.query(
            QueryRequest(metric=b"m", start_ms=0, end_ms=2 * HOUR, bucket_ms=15 * 60_000)
        )
        tsids, grids = out
        # oracle: raw rows (merged+deduped by the scan) aggregated on host
        raw = await eng.query(QueryRequest(metric=b"m", start_ms=0, end_ms=2 * HOUR))
        t = raw.column("ts").to_numpy()
        v = raw.column("value").to_numpy()
        tsid_col = raw.column("tsid").to_numpy()
        buckets = t // (15 * 60_000)
        for row, tsid in enumerate(tsids):
            for b in range(grids["mean"].shape[1]):
                sel = v[(tsid_col == tsid) & (buckets == b)]
                assert float(grids["count"][row, b]) == len(sel), (row, b)
                if len(sel):
                    assert np.isclose(float(grids["sum"][row, b]), sel.sum())
                    assert np.isclose(float(grids["min"][row, b]), sel.min())
                    assert np.isclose(float(grids["max"][row, b]), sel.max())
        await eng.close()

    @async_test
    async def test_downsample_f64_exact_on_cpu(self):
        """CPU/XLA-fallback aggregation accumulates in f64: values whose low
        bits vanish in f32 (counter-style, > 2^24) must sum EXACTLY like the
        reference's f64 aggregation (advisor round-1, data.py precision
        contract)."""
        store = MemStore()
        eng = await open_engine(store)
        # 2^24 + k: in f32, (2**24 + 1) == 2**24 exactly — any f32
        # accumulation of these sums visibly wrong
        samples = [(i * 1000, float(2**24 + i)) for i in range(64)]
        payload = make_remote_write([({"__name__": "ctr", "host": "a"}, samples)])
        await eng.write_parsed(PooledParser.decode(payload))
        out = await eng.query(
            QueryRequest(metric=b"ctr", start_ms=0, end_ms=64_000, bucket_ms=64_000)
        )
        _tsids, grids = out
        exact = float(sum(v for _t, v in samples))
        assert float(grids["sum"][0, 0]) == exact
        assert float(grids["count"][0, 0]) == 64.0
        await eng.close()

    @async_test
    async def test_multi_segment_write(self):
        """Samples spanning segments split into per-segment storage writes."""
        store = MemStore()
        eng = await open_engine(store)
        payload = make_remote_write(
            [({"__name__": "cpu", "host": "a"}, [(1000, 1.0), (HOUR + 1000, 2.0)])]
        )
        await eng.write_parsed(PooledParser.decode(payload))
        assert len(eng.data_table.manifest.all_ssts()) == 2
        t = await eng.query(QueryRequest(metric=b"cpu", start_ms=0, end_ms=2 * HOUR))
        assert t.column("value").to_pylist() == [1.0, 2.0]
        await eng.close()

    @async_test
    async def test_restart_recovers_index(self):
        store = MemStore()
        eng = await open_engine(store)
        payload = make_remote_write(
            [
                ({"__name__": "cpu", "host": "a", "dc": "x"}, [(1000, 1.0)]),
                ({"__name__": "cpu", "host": "b", "dc": "y"}, [(1000, 2.0)]),
            ]
        )
        await eng.write_parsed(PooledParser.decode(payload))
        await eng.close()

        eng2 = await open_engine(store)
        t = await eng2.query(
            QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000, filters=[(b"dc", b"y")])
        )
        assert t.column("value").to_pylist() == [2.0]
        assert eng2.label_values(b"cpu", b"host") == [b"a", b"b"]
        await eng2.close()

    @async_test
    async def test_extended_matchers(self):
        """!=, =~, !~ matchers over the inverted index."""
        store = MemStore()
        eng = await open_engine(store)
        payload = make_remote_write(
            [
                ({"__name__": "m", "host": f"web{i}", "dc": "a" if i < 2 else "b"},
                 [(1000, float(i))])
                for i in range(4)
            ]
        )
        await eng.write_parsed(PooledParser.decode(payload))

        async def values(**kw):
            t = await eng.query(QueryRequest(metric=b"m", start_ms=0, end_ms=10_000, **kw))
            return sorted(t.column("value").to_pylist()) if t is not None else []

        assert await values(matchers=[(b"host", "re", b"web[01]")]) == [0.0, 1.0]
        assert await values(matchers=[(b"host", "nre", b"web[01]")]) == [2.0, 3.0]
        assert await values(matchers=[(b"dc", "ne", b"a")]) == [2.0, 3.0]
        # combined with equality filter
        assert await values(
            filters=[(b"dc", b"b")], matchers=[(b"host", "re", b"web2")]
        ) == [2.0]
        # bad regex -> clear error
        from horaedb_tpu.common.error import HoraeError

        with pytest.raises(HoraeError, match="bad regex"):
            await values(matchers=[(b"host", "re", b"([")])
        # oversized pattern rejected (no-RE2 mitigation)
        with pytest.raises(HoraeError, match="too long"):
            await values(matchers=[(b"host", "re", b"a" * 1000)])
        # absent label reads as empty string for =~ and !~ (Prometheus
        # semantics): match-empty patterns include series lacking the key
        assert await values(matchers=[(b"nope", "re", b".*")]) == [0.0, 1.0, 2.0, 3.0]
        assert await values(matchers=[(b"nope", "re", b".+")]) == []
        assert await values(matchers=[(b"nope", "nre", b".+")]) == [0.0, 1.0, 2.0, 3.0]
        await eng.close()

    @async_test
    async def test_exemplars_persisted_and_queryable(self):
        store = MemStore()
        eng = await open_engine(store)
        req = remote_write_pb2.WriteRequest()
        ts = req.timeseries.add()
        for k, v in ((b"__name__", b"lat"), (b"host", b"a")):
            lab = ts.labels.add(); lab.name = k; lab.value = v
        s = ts.samples.add(); s.timestamp = 1000; s.value = 0.2
        ex = ts.exemplars.add(); ex.value = 0.99; ex.timestamp = 1500
        lab = ex.labels.add(); lab.name = b"trace_id"; lab.value = b"abc"
        await eng.write_parsed(PooledParser.decode(req.SerializeToString()))

        out = await eng.query_exemplars(
            QueryRequest(metric=b"lat", start_ms=0, end_ms=10_000)
        )
        assert out.num_rows == 1
        assert out.column("value").to_pylist() == [0.99]
        assert out.column("ts").to_pylist() == [1500]
        # the exemplar's labels (the trace link) survive the round trip
        from horaedb_tpu.engine.types import decode_series_key

        labels = decode_series_key(out.column("labels").to_pylist()[0])
        assert labels == [(b"trace_id", b"abc")]
        # samples unaffected
        t = await eng.query(QueryRequest(metric=b"lat", start_ms=0, end_ms=10_000))
        assert t.column("value").to_pylist() == [0.2]
        await eng.close()

    @async_test
    async def test_tagless_series_listed(self):
        """A series with only __name__ must still appear in listings."""
        store = MemStore()
        eng = await open_engine(store)
        await eng.write_parsed(
            PooledParser.decode(make_remote_write([({"__name__": "up"}, [(1000, 1.0)])]))
        )
        assert eng.metric_names() == [b"up"]
        series = eng.series(b"up")
        assert len(series) == 1 and "__tsid__" in series[0]
        await eng.close()

    @async_test
    async def test_label_values(self):
        store = MemStore()
        eng = await open_engine(store)
        payload = make_remote_write(
            [
                ({"__name__": "cpu", "host": f"h{i}"}, [(1000, 1.0)])
                for i in range(5)
            ]
        )
        await eng.write_parsed(PooledParser.decode(payload))
        assert eng.label_values(b"cpu", b"host") == [b"h0", b"h1", b"h2", b"h3", b"h4"]
        assert eng.label_values(b"cpu", b"nope") == []
        await eng.close()


class TestFastSlowPathEquivalence:
    """The hash-lane fast write path (_write_parsed_fast, C++ ids) and the
    Python slow path (PyParser decode, Python seahash) must produce the same
    engine state: same TSIDs, same index rows, same query results."""

    PAYLOAD = [
        ({"__name__": "cpu", "host": "a", "dc": "x"}, [(1000, 1.0), (2000, 2.0)]),
        ({"__name__": "cpu", "host": "b"}, [(1500, 5.0)]),
        ({"__name__": "mem", "host": "a"}, [(1000, 9.0)]),
        ({"__name__": "up"}, [(1000, 1.0)]),  # tagless
    ]

    @async_test
    async def test_same_state_and_results(self):
        from horaedb_tpu.ingest import native as native_mod
        from horaedb_tpu.ingest.py_parser import PyParser

        if native_mod.load() is None:
            pytest.skip("native parser not available")
        payload = make_remote_write(self.PAYLOAD)
        fast = native_mod.NativeParser().parse(payload)
        slow = PyParser().parse(payload)
        assert fast.series_tsid is not None and slow.series_tsid is None

        results = []
        for parsed in (fast, slow):
            store = MemStore()
            eng = await open_engine(store)
            n = await eng.write_parsed(parsed)
            assert n == 5
            rows = await eng.query(QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000))
            filtered = await eng.query(
                QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000,
                             filters=[(b"host", b"a")])
            )
            results.append(
                (
                    sorted(eng.index_mgr.series_of(eng.metric_mgr.get(b"cpu")[0])),
                    sorted(eng.metric_names()),
                    rows.column("tsid").to_pylist(),
                    rows.column("value").to_pylist(),
                    filtered.column("value").to_pylist(),
                    eng.series(b"cpu"),
                )
            )
            await eng.close()
        assert results[0] == results[1]

    @async_test
    async def test_buffered_matches_unbuffered(self):
        """ingest_buffer_rows must not change query results (flush-on-query
        consistency + the counting-sort flush ordering)."""
        payload = make_remote_write(self.PAYLOAD)
        outs = []
        for buffer_rows in (0, 10_000):
            store = MemStore()
            eng = await MetricEngine.open(
                "db", store, segment_duration_ms=HOUR,
                enable_compaction=False, ingest_buffer_rows=buffer_rows,
            )
            await eng.write_parsed(PooledParser.decode(payload))
            t = await eng.query(QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000))
            outs.append((t.column("tsid").to_pylist(), t.column("value").to_pylist(),
                         t.column("ts").to_pylist()))
            await eng.close()
        assert outs[0] == outs[1]

    @async_test
    async def test_lane_fingerprint_cache_still_registers_new_series(self):
        """The steady-state payload-shape fingerprint must only short-cut
        EXACTLY repeated (metric_id, tsid) lanes: a later payload adding a
        new series has different lane bytes and must register it."""
        from horaedb_tpu.ingest import native as native_mod

        if native_mod.load() is None:
            pytest.skip("native parser not available")
        base = self.PAYLOAD
        extended = base + [({"__name__": "cpu", "host": "NEW"}, [(3000, 7.0)])]
        store = MemStore()
        eng = await MetricEngine.open(
            "db", store, segment_duration_ms=HOUR,
            enable_compaction=False, ingest_buffer_rows=10_000,
        )
        parser = native_mod.NativeParser()
        # same payload three times: second+third hit the fingerprint cache
        p1 = make_remote_write(base)
        for _ in range(3):
            await eng.write_parsed(parser.parse(p1))
        assert len(eng._lanes_fp) == 1
        await eng.write_parsed(parser.parse(make_remote_write(extended)))
        assert len(eng._lanes_fp) == 2
        hosts = {s.get("host") for s in eng.series(b"cpu")}
        assert "NEW" in hosts and "a" in hosts and "b" in hosts
        t = await eng.query(
            QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000,
                         filters=[(b"host", b"NEW")])
        )
        assert t.column("value").to_pylist() == [7.0]
        await eng.close()

    @async_test
    async def test_missing_name_rejected_on_both_paths(self):
        from horaedb_tpu.common.error import HoraeError
        from horaedb_tpu.ingest import native as native_mod
        from horaedb_tpu.ingest.py_parser import PyParser

        req = remote_write_pb2.WriteRequest()
        ts = req.timeseries.add()
        lab = ts.labels.add(); lab.name = b"host"; lab.value = b"a"
        s = ts.samples.add(); s.timestamp = 1000; s.value = 1.0
        payload = req.SerializeToString()
        parsers = [PyParser()]
        if native_mod.load() is not None:
            parsers.append(native_mod.NativeParser())
        for parser in parsers:
            store = MemStore()
            eng = await open_engine(store)
            with pytest.raises(HoraeError):
                await eng.write_parsed(parser.parse(payload))
            await eng.close()


class TestRegexGuard:
    """_reject_catastrophic: hostile patterns must be refused before they
    reach sre (which backtracks in C holding the GIL)."""

    def test_catastrophic_patterns_rejected(self):
        from horaedb_tpu.common.error import HoraeError
        from horaedb_tpu.engine.index import _reject_catastrophic

        for pat in ("(a+)+b", "(a*)*b", "(a+){2,100}b", "((a|aa)+)+$",
                    "(?:x(a+)*y)+"):
            with pytest.raises(HoraeError):
                _reject_catastrophic(pat)

    def test_benign_patterns_accepted(self):
        from horaedb_tpu.engine.index import _reject_catastrophic

        for pat in ("host-[0-9]+", "us-(east|west)-1", "a{1,5}b{1,5}",
                    ".*", "cpu_(usage|idle)", "(ab)+c"):
            _reject_catastrophic(pat)


class TestBufferedFlushFailure:
    @async_test
    async def test_failed_flush_restores_buffer(self):
        """A failing storage write must not drop acked buffered samples:
        the snapshot merges back and a retrying flush persists everything
        (data.py::flush concurrency contract)."""
        from horaedb_tpu.common.error import HoraeError

        store = MemStore()
        eng = await MetricEngine.open(
            "db", store, segment_duration_ms=HOUR,
            enable_compaction=False, ingest_buffer_rows=10_000,
        )
        payload = make_remote_write(
            [({"__name__": "cpu", "host": "a"}, [(1000, 1.0), (2000, 2.0)])]
        )
        await eng.write_parsed(PooledParser.decode(payload))
        orig = eng.sample_mgr._write_segment
        calls = {"n": 0}

        async def failing(*a, **kw):
            calls["n"] += 1
            raise HoraeError("injected object-store failure")

        eng.sample_mgr._write_segment = failing
        with pytest.raises(HoraeError):
            await eng.flush()
        # the barrier attempts the write-out, re-buffers, and retries once
        # inline before surfacing the persistent error
        assert calls["n"] == 2
        assert eng.sample_mgr.buffered_rows == 2  # re-buffered, not dropped
        # more data lands in the restored buffer, then a successful retry
        payload2 = make_remote_write(
            [({"__name__": "cpu", "host": "a"}, [(3000, 3.0)])]
        )
        await eng.write_parsed(PooledParser.decode(payload2))
        eng.sample_mgr._write_segment = orig
        t = await eng.query(QueryRequest(metric=b"cpu", start_ms=0, end_ms=10_000))
        assert sorted(t.column("value").to_pylist()) == [1.0, 2.0, 3.0]
        await eng.close()


class TestLimitPushdown:
    @async_test
    async def test_limit_stops_reading_later_segments(self):
        """limit pushes into the scan: once enough merged rows accumulated,
        later segments' SSTs are never read (reference scan-stream laziness,
        storage.rs:335-370)."""
        store = MemStore()
        eng = await open_engine(store)
        # 5 segments (1h each), 10 rows apiece, oldest first
        payloads = []
        for seg in range(5):
            base = seg * HOUR + 1000
            payloads.append(make_remote_write(
                [({"__name__": "cpu", "host": "a"},
                  [(base + i, float(seg * 100 + i)) for i in range(10)])]
            ))
        for p in payloads:
            await eng.write_parsed(PooledParser.decode(p))

        reader = eng.data_table.parquet_reader
        orig = reader._open_sst  # a segment scan opens each of its SSTs here
        touched = []

        async def spy(sst, *args, **kw):
            touched.append(sst.id)
            return await orig(sst, *args, **kw)

        reader._open_sst = spy
        t = await eng.query(
            QueryRequest(metric=b"cpu", start_ms=0, end_ms=10 * HOUR, limit=12)
        )
        assert t.num_rows == 12
        # rows come oldest-first; 12 rows need exactly 2 of the 5 segments
        assert len(touched) == 2, touched
        # values are the oldest 12
        assert t.column("value").to_pylist() == [float(i) for i in range(10)] + [100.0, 101.0]
        reader._open_sst = orig
        # unlimited query still sees everything
        t_all = await eng.query(QueryRequest(metric=b"cpu", start_ms=0, end_ms=10 * HOUR))
        assert t_all.num_rows == 50
        await eng.close()


class TestIndexDeltaCompaction:
    @async_test
    async def test_compaction_preserves_queries(self, monkeypatch):
        """Delta->base merges must be invisible to queries: register past
        the threshold, then every lookup still sees every series."""
        import horaedb_tpu.engine.index as index_mod

        monkeypatch.setattr(index_mod, "DELTA_COMPACT_THRESHOLD", 10)
        store = MemStore()
        eng = await open_engine(store)
        for batch in range(4):
            payload = make_remote_write(
                [
                    ({"__name__": "cpu", "host": f"h{batch}-{i}",
                      "region": ["us", "eu"][i % 2]}, [(1000 + i, 1.0)])
                    for i in range(6)
                ]
            )
            await eng.write_parsed(PooledParser.decode(payload))
        mgr = eng.index_mgr
        mid = eng.metric_mgr.get(b"cpu")[0]
        # base tier must now hold compacted series; delta below threshold
        assert mgr._delta_series < 24
        assert len(mgr.series_of(mid)) == 24
        hits = mgr.find_tsids(mid, [(b"host", b"h2-3")])
        assert len(hits) == 1
        us = mgr.find_tsids(mid, [], matchers=[(b"region", "re", b"us")])
        assert len(us) == 12
        assert mgr.label_values(mid, b"region") == [b"eu", b"us"]
        labels = mgr.series_labels(mid)
        assert len(labels) == 24
        # restart: storage-backed recovery equals in-memory state
        await eng.close()
        eng2 = await open_engine(store)
        mid2 = eng2.metric_mgr.get(b"cpu")[0]
        assert eng2.index_mgr.series_of(mid2) == mgr.series_of(mid)
        await eng2.close()


class TestBackgroundFlushBackpressure:
    @async_test
    async def test_full_flush_queue_stalls_appends_and_surfaces_errors(self):
        """With the store broken, failed memtables PARK on the bounded
        flush queue; once it is full, appends block on the backpressure
        condition variable and surface a retryable error at the stall
        deadline instead of acking rows into an unbounded buffer."""
        import asyncio

        from horaedb_tpu.common.error import HoraeError
        from horaedb_tpu.engine.flush_executor import INGEST_STALL_SECONDS

        store = MemStore()
        eng = await MetricEngine.open(
            "db", store, segment_duration_ms=HOUR,
            enable_compaction=False, ingest_buffer_rows=10,
            flush_queue_max=2, flush_stall_deadline_s=0.2,
        )
        if not eng.sample_mgr.native_accum_active:
            pytest.skip("native accumulator unavailable")
        # break the storage so every flush fails
        calls = {"n": 0}

        async def failing(*a, **kw):
            calls["n"] += 1
            raise HoraeError("injected store failure")

        eng.sample_mgr._write_segment = failing
        stall = INGEST_STALL_SECONDS.labels(eng.sample_mgr._table_id)
        stalls0 = stall.count
        payload = make_remote_write(
            [({"__name__": "cpu", "host": f"h{i}"}, [(1000 + j, 1.0) for j in range(5)])
             for i in range(3)]
        )  # 15 rows/payload, threshold 10, queue_max 2: the first threshold
        # crossings seal + submit to the BACKGROUND executor (and fail,
        # parking the memtables) until the queue is full and the submit
        # stalls out to its deadline
        saw_error = False
        for _ in range(12):
            try:
                await eng.write_payload(payload)
            except HoraeError:
                saw_error = True
                break
            await asyncio.sleep(0.01)  # let background flushes run
        assert saw_error, "full flush queue never surfaced the storage failure"
        # bounded memory: queue_max sealed + one in flight + active buffer
        assert eng.sample_mgr.buffered_rows <= (2 + 1) * 15 + 30
        assert calls["n"] >= 2  # background write-outs ran (and failed)
        assert stall.count > stalls0  # the stall was measured
        eng.sample_mgr._write_segment = type(eng.sample_mgr)._write_segment.__get__(eng.sample_mgr)
        await eng.close()


class TestEngineRetention:
    @async_test
    async def test_ttl_expiry_through_engine_queries(self):
        """Retention end-to-end at the ENGINE level: after a TTL compaction,
        expired samples vanish from queries while fresh ones survive."""
        import asyncio

        from horaedb_tpu.common.time_ext import ReadableDuration, now_ms
        from horaedb_tpu.storage.config import SchedulerConfig, StorageConfig

        cfg = StorageConfig(
            scheduler=SchedulerConfig(
                ttl=ReadableDuration.hours(1), input_sst_min_num=2
            )
        )
        store = MemStore()
        eng = await MetricEngine.open(
            "db", store, segment_duration_ms=HOUR,
            enable_compaction=True, config=cfg,
        )
        now = now_ms()
        old_ts = now - 3 * HOUR
        fresh_ts = now - 60_000
        for ts_base, tag in ((old_ts, "old"), (fresh_ts, "new")):
            for i in range(3):  # several SSTs so the picker engages
                await eng.write_parsed(PooledParser.decode(make_remote_write(
                    [({"__name__": "ret", "host": tag},
                      [(ts_base + i, float(i))])]
                )))
        # scan-time retention (storage/visibility.py): expired rows are
        # masked IMMEDIATELY, before any compaction runs — retention is
        # exact from the moment the horizon passes, not eventually
        t = await eng.query(QueryRequest(metric=b"ret", start_ms=0, end_ms=2**60))
        assert t.num_rows == 3
        eng.data_table.compaction_scheduler.pick_once()
        for _ in range(200):
            ssts = eng.data_table.manifest.all_ssts()
            if all(s.meta.time_range.start >= now - 2 * HOUR for s in ssts):
                break
            await asyncio.sleep(0.02)
        await eng.data_table.compaction_scheduler.executor.drain()
        t2 = await eng.query(QueryRequest(metric=b"ret", start_ms=0, end_ms=2**60))
        assert t2.num_rows == 3, t2.num_rows
        hosts = set()
        per_tsid = eng.index_mgr.series_labels(eng.metric_mgr.get(b"ret")[0])
        for tsid in t2.column("tsid").to_pylist():
            hosts.add(per_tsid[tsid][b"host"])
        assert hosts == {b"new"}
        await eng.close()


class TestConcurrentPushdownUnderCompaction:
    @async_test
    async def test_multi_segment_pushdown_racing_compactions(self):
        """Concurrent per-segment pushdown tasks racing live compactions:
        grids must match the oracle even when segments refresh mid-query
        (the retry path) and other segments scan the old snapshot."""
        import asyncio

        from horaedb_tpu.storage.config import SchedulerConfig, StorageConfig

        cfg = StorageConfig(scheduler=SchedulerConfig(input_sst_min_num=2))
        store = MemStore()
        eng = await MetricEngine.open(
            "db", store, segment_duration_ms=HOUR,
            enable_compaction=True, config=cfg,
        )
        rng = np.random.default_rng(31)
        # 4 segments x several overlapping SSTs
        expect: dict[tuple[int, int], float] = {}  # (bucket, col) oracle later
        all_samples = []
        for seg in range(4):
            for _dup in range(3):
                samples = []
                for _ in range(50):
                    t = int(seg * HOUR + rng.integers(0, HOUR))
                    v = float(rng.normal())
                    samples.append((t, v))
                all_samples.append(samples)
                await eng.write_parsed(PooledParser.decode(make_remote_write(
                    [({"__name__": "rc", "host": "h0"}, samples)]
                )))

        async def churn():
            for _ in range(6):
                eng.data_table.compaction_scheduler.pick_once()
                await asyncio.sleep(0.01)

        async def query():
            return await eng.query(QueryRequest(
                metric=b"rc", start_ms=0, end_ms=4 * HOUR, bucket_ms=30 * 60_000
            ))

        results, _ = await asyncio.gather(
            asyncio.gather(*(query() for _ in range(4))), churn()
        )
        await eng.data_table.compaction_scheduler.executor.drain()
        # oracle from raw rows (dedup: last write wins per (tsid, ts))
        raw = await eng.query(QueryRequest(metric=b"rc", start_ms=0, end_ms=4 * HOUR))
        t = raw.column("ts").to_numpy()
        v = raw.column("value").to_numpy()
        buckets = t // (30 * 60_000)
        for out in results:
            tsids, grids = out
            assert len(tsids) == 1
            for b in range(grids["count"].shape[1]):
                sel = v[buckets == b]
                assert float(grids["count"][0, b]) == len(sel), b
                if len(sel):
                    assert np.isclose(float(grids["sum"][0, b]), sel.sum())
        await eng.close()
