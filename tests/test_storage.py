"""Engine round-trip tests (reference: storage.rs:377-537 inline tests)."""

import asyncio

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common.error import HoraeError
from horaedb_tpu.objstore import MemStore
from horaedb_tpu.ops import filter as F
from horaedb_tpu.storage import (
    ObjectBasedStorage,
    ScanRequest,
    StorageConfig,
    TimeRange,
    UpdateMode,
    WriteRequest,
)
from tests.conftest import async_test

SEGMENT_MS = 3_600_000


def make_schema():
    return pa.schema(
        [
            ("pk1", pa.int64()),
            ("pk2", pa.int64()),
            ("ts", pa.int64()),
            ("value", pa.float64()),
        ]
    )


def make_batch(schema, pk1, pk2, ts, value):
    return pa.RecordBatch.from_pydict(
        {
            "pk1": np.asarray(pk1, dtype=np.int64),
            "pk2": np.asarray(pk2, dtype=np.int64),
            "ts": np.asarray(ts, dtype=np.int64),
            "value": np.asarray(value, dtype=np.float64),
        },
        schema=schema,
    )


async def new_engine(store, schema=None, num_pks=2, config=None):
    return await ObjectBasedStorage.try_new(
        root="db",
        store=store,
        arrow_schema=schema or make_schema(),
        num_primary_keys=num_pks,
        segment_duration_ms=SEGMENT_MS,
        config=config,
        enable_compaction_scheduler=False,
        start_background_merger=False,
    )


async def collect(engine, req):
    out = []
    async for b in engine.scan(req):
        out.append(b)
    return pa.Table.from_batches(out) if out else None


class TestWriteScan:
    @async_test
    async def test_roundtrip_overwrite_dedup(self):
        """Two overlapping writes; newest seq wins per pk (storage.rs:392-491)."""
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        await eng.write(
            WriteRequest(
                make_batch(schema, [1, 2, 3], [0, 0, 0], [100, 200, 300], [1.0, 2.0, 3.0]),
                TimeRange(100, 301),
            )
        )
        await eng.write(
            WriteRequest(
                make_batch(schema, [2, 3, 4], [0, 0, 0], [201, 301, 401], [20.0, 30.0, 40.0]),
                TimeRange(201, 402),
            )
        )
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("pk1").to_pylist() == [1, 2, 3, 4]
        assert t.column("value").to_pylist() == [1.0, 20.0, 30.0, 40.0]
        # builtin columns are stripped from scan output
        assert t.schema.names == ["pk1", "pk2", "ts", "value"]
        await eng.close()

    @async_test
    async def test_sorted_output_across_many_writes(self):
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        rng = np.random.default_rng(0)
        seen = {}
        for w in range(6):
            pk1 = rng.integers(0, 50, 40)
            pk2 = rng.integers(0, 4, 40)
            vals = rng.normal(size=40)
            await eng.write(
                WriteRequest(
                    make_batch(schema, pk1, pk2, np.full(40, 10), vals),
                    TimeRange(10, 11),
                )
            )
            for a, b, v in zip(pk1, pk2, vals):
                seen[(a, b)] = v  # later writes overwrite
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        got = list(zip(t.column("pk1").to_pylist(), t.column("pk2").to_pylist()))
        assert got == sorted(seen.keys())
        for (a, b), v in zip(got, t.column("value").to_pylist()):
            assert np.isclose(v, seen[(a, b)])
        await eng.close()

    @async_test
    async def test_scan_with_predicate_and_projection(self):
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        await eng.write(
            WriteRequest(
                make_batch(schema, [1, 1, 2, 2], [1, 2, 1, 2], [10, 20, 30, 40], [1, 2, 3, 4]),
                TimeRange(10, 41),
            )
        )
        t = await collect(
            eng,
            ScanRequest(
                range=TimeRange(0, SEGMENT_MS),
                predicate=F.Compare("pk1", "eq", 1),
                projections=[0, 1, 3],  # pk1, pk2, value
            ),
        )
        assert t.schema.names == ["pk1", "pk2", "value"]
        assert t.column("pk1").to_pylist() == [1, 1]
        assert t.column("value").to_pylist() == [1.0, 2.0]
        await eng.close()

    @async_test
    async def test_scan_with_inset_predicate(self):
        """InSet (TSID membership) must evaluate inside the jitted kernel."""
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        await eng.write(
            WriteRequest(
                make_batch(schema, [1, 2, 3, 4], [0, 0, 0, 0], [10, 20, 30, 40], [1, 2, 3, 4]),
                TimeRange(10, 41),
            )
        )
        t = await collect(
            eng,
            ScanRequest(range=TimeRange(0, SEGMENT_MS), predicate=F.InSet("pk1", (2, 4))),
        )
        assert t.column("pk1").to_pylist() == [2, 4]
        await eng.close()

    @async_test
    async def test_filter_before_dedup_reference_semantics(self):
        """Filter runs before dedup (plan order read.rs:429-494): if the newest
        version is filtered out, the older version surfaces."""
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        await eng.write(
            WriteRequest(make_batch(schema, [1], [1], [10], [5.0]), TimeRange(10, 11))
        )
        await eng.write(
            WriteRequest(make_batch(schema, [1], [1], [10], [50.0]), TimeRange(10, 11))
        )
        t = await collect(
            eng,
            ScanRequest(
                range=TimeRange(0, SEGMENT_MS),
                predicate=F.Compare("value", "lt", 10.0),
            ),
        )
        assert t.column("value").to_pylist() == [5.0]
        await eng.close()

    @async_test
    async def test_multi_segment_scan_old_to_new(self):
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        # segment 1 (hour 1) has larger pks than segment 0: output must still
        # be old-segment first (trait contract, storage.rs:82-84)
        t1 = SEGMENT_MS + 5
        await eng.write(
            WriteRequest(make_batch(schema, [1], [0], [t1], [11.0]), TimeRange(t1, t1 + 1))
        )
        await eng.write(
            WriteRequest(make_batch(schema, [9], [0], [5], [9.0]), TimeRange(5, 6))
        )
        t = await collect(eng, ScanRequest(range=TimeRange(0, 2 * SEGMENT_MS)))
        assert t.column("value").to_pylist() == [9.0, 11.0]
        await eng.close()

    @async_test
    async def test_empty_scan_range(self):
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        await eng.write(
            WriteRequest(make_batch(schema, [1], [1], [10], [1.0]), TimeRange(10, 11))
        )
        assert await collect(eng, ScanRequest(range=TimeRange(1000, 2000))) is None
        await eng.close()

    @async_test
    async def test_write_cross_segment_rejected(self):
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        with pytest.raises(HoraeError, match="one segment"):
            await eng.write(
                WriteRequest(
                    make_batch(schema, [1], [1], [10], [1.0]),
                    TimeRange(10, SEGMENT_MS + 10),
                )
            )
        await eng.close()

    @async_test
    async def test_restart_recovery(self):
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        await eng.write(
            WriteRequest(make_batch(schema, [1, 2], [0, 0], [10, 20], [1.0, 2.0]),
                         TimeRange(10, 21))
        )
        await eng.close()
        eng2 = await new_engine(store)
        t = await collect(eng2, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("value").to_pylist() == [1.0, 2.0]
        await eng2.close()


class TestCrashConsistency:
    @async_test
    async def test_orphan_sst_ignored_on_recovery(self):
        """Crash between SST upload and manifest add leaves an orphan data
        file; recovery must ignore it (the manifest is the source of truth)."""
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()
        await eng.write(
            WriteRequest(make_batch(schema, [1], [0], [10], [1.0]), TimeRange(10, 11))
        )
        # simulate the crash artifact: an SST written but never committed
        orphan_id = await eng.write_batch(
            make_batch(schema, [9], [0], [10], [99.0])
        )
        assert len(await store.list("db/data")) == 2  # real + orphan
        await eng.close()

        eng2 = await new_engine(store)
        t = await collect(eng2, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("value").to_pylist() == [1.0]  # orphan invisible
        assert len(eng2.manifest.all_ssts()) == 1
        del orphan_id
        await eng2.close()

    @async_test
    async def test_concurrent_writers_and_scanners(self):
        """Race-pressure (SURVEY §5.2 analog): concurrent writes and scans
        must never yield torn state (scans see some consistent prefix)."""
        store = MemStore()
        eng = await new_engine(store)
        schema = make_schema()

        async def writer(w):
            for i in range(5):
                await eng.write(
                    WriteRequest(
                        make_batch(schema, [w * 10 + i], [0], [10], [float(w)]),
                        TimeRange(10, 11),
                    )
                )

        async def scanner(results):
            for _ in range(6):
                t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
                results.append(0 if t is None else t.num_rows)
                await asyncio.sleep(0)

        r1: list[int] = []
        r2: list[int] = []
        await asyncio.gather(*(writer(w) for w in range(4)), scanner(r1), scanner(r2))
        # final state: all 20 distinct pks present
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.num_rows == 20
        # with no compaction running, each scanner must observe monotonically
        # growing (never torn/decreasing) row counts
        assert r1 == sorted(r1), r1
        assert r2 == sorted(r2), r2
        await eng.close()


class TestChunkedScan:
    @async_test
    async def test_chunked_scan_matches_single_block(self):
        """Segments above scan_block_rows take the hierarchical path; output
        must be byte-identical to the single-block pipeline."""
        rng = np.random.default_rng(7)
        store = MemStore()
        big = await new_engine(store)  # default huge scan_block_rows
        schema = make_schema()
        for w in range(6):
            pk1 = rng.integers(0, 40, 500)
            pk2 = rng.integers(0, 3, 500)
            vals = rng.normal(size=500)
            await big.write(
                WriteRequest(
                    make_batch(schema, pk1, pk2, np.full(500, 10), vals),
                    TimeRange(10, 11),
                )
            )
        expect = await collect(
            big, ScanRequest(range=TimeRange(0, SEGMENT_MS),
                             predicate=F.Compare("value", "gt", 0.0))
        )
        # same store, tiny scan block -> forces chunking + merge tree
        small_cfg = StorageConfig(scan_block_rows=700)
        small = await ObjectBasedStorage.try_new(
            root="db", store=store, arrow_schema=schema, num_primary_keys=2,
            segment_duration_ms=SEGMENT_MS, config=small_cfg,
            enable_compaction_scheduler=False, start_background_merger=False,
        )
        got = await collect(
            small, ScanRequest(range=TimeRange(0, SEGMENT_MS),
                               predicate=F.Compare("value", "gt", 0.0))
        )
        assert got.num_rows == expect.num_rows
        for name in expect.schema.names:
            np.testing.assert_array_equal(
                got.column(name).to_numpy(), expect.column(name).to_numpy()
            )
        await big.close()
        await small.close()

    @async_test
    async def test_chunked_scan_append_mode_numeric(self):
        """Append mode (no dedup) through the chunked path keeps duplicates."""
        store = MemStore()
        cfg = StorageConfig(update_mode=UpdateMode.APPEND, scan_block_rows=4)
        eng = await new_engine(store, config=cfg)
        schema = make_schema()
        for v in (1.0, 2.0, 3.0):
            await eng.write(
                WriteRequest(
                    make_batch(schema, [1, 2], [0, 0], [10, 10], [v, v * 10]),
                    TimeRange(10, 11),
                )
            )
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.num_rows == 6
        assert t.column("value").to_pylist() == [1.0, 2.0, 3.0, 10.0, 20.0, 30.0]
        await eng.close()


class TestAppendMode:
    @async_test
    async def test_append_mode_keeps_duplicates(self):
        """Append mode without binary columns: duplicates all survive, sorted."""
        store = MemStore()
        cfg = StorageConfig(update_mode=UpdateMode.APPEND)
        eng = await new_engine(store, config=cfg)
        schema = make_schema()
        await eng.write(
            WriteRequest(make_batch(schema, [1], [1], [10], [1.0]), TimeRange(10, 11))
        )
        await eng.write(
            WriteRequest(make_batch(schema, [1], [1], [10], [2.0]), TimeRange(10, 11))
        )
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("value").to_pylist() == [1.0, 2.0]
        await eng.close()

    @async_test
    async def test_append_mode_binary_concat(self):
        """Append mode with binary values: groups concat bytes
        (BytesMergeOperator, operator.rs:59-111)."""
        store = MemStore()
        schema = pa.schema([("pk", pa.int64()), ("payload", pa.binary())])
        cfg = StorageConfig(update_mode=UpdateMode.APPEND)
        eng = await new_engine(store, schema=schema, num_pks=1, config=cfg)
        b1 = pa.RecordBatch.from_pydict(
            {"pk": np.array([1, 2], dtype=np.int64), "payload": [b"aa", b"xx"]}, schema=schema
        )
        b2 = pa.RecordBatch.from_pydict(
            {"pk": np.array([1], dtype=np.int64), "payload": [b"bb"]}, schema=schema
        )
        await eng.write(WriteRequest(b1, TimeRange(10, 11)))
        await eng.write(WriteRequest(b2, TimeRange(10, 11)))
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("pk").to_pylist() == [1, 2]
        assert t.column("payload").to_pylist() == [b"aabb", b"xx"]
        await eng.close()


class TestFusedPassKeepsF64Exact:
    """The fused filter->sort->dedup pass carries f64 lanes as their i64
    bits, so a stored sample comes back bit for bit whatever the device's
    f64 is; where it is not exact (an accelerator: faked here), an f64
    predicate evaluates on the host and the reduction keeps its selections
    on integer lanes."""

    WIDE = [1e300, -2.5e-300, 99.99967667212489, -0.0, 3.5e38, 1e-45]

    @pytest.fixture(params=[True, False], ids=["exact_f64", "inexact_f64"])
    def device_f64(self, request, monkeypatch):
        from horaedb_tpu.ops import aggregate

        from horaedb_tpu.storage.read import ParquetReader

        monkeypatch.setattr(
            aggregate, "device_f64_is_exact", lambda: request.param)
        fused, self.bit_lanes = ParquetReader._fused_pass, []

        def spy(reader, *a, **kw):
            out = fused(reader, *a, **kw)
            self.bit_lanes.append(set(out[-1]))
            return out

        monkeypatch.setattr(ParquetReader, "_fused_pass", spy)
        return request.param

    @async_test
    async def test_append_mode_numeric_lane_beside_binary_values(self, device_f64):
        store = MemStore()
        schema = pa.schema(
            [("pk", pa.int64()), ("w", pa.float64()), ("payload", pa.binary())])
        cfg = StorageConfig(update_mode=UpdateMode.APPEND)
        eng = await new_engine(store, schema=schema, num_pks=1, config=cfg)
        n = len(self.WIDE)
        batch = pa.RecordBatch.from_pydict(
            {"pk": np.arange(n, dtype=np.int64)[::-1].copy(),
             "w": np.asarray(self.WIDE), "payload": [b"x"] * n}, schema=schema)
        await eng.write(WriteRequest(batch, TimeRange(10, 11)))
        for pred, keep in ((None, list(range(n))),
                           (F.Compare("w", "gt", 1e299), [0])):
            t = await collect(
                eng, ScanRequest(range=TimeRange(0, SEGMENT_MS), predicate=pred))
            want = np.asarray(self.WIDE)[keep][::-1]  # pk ascending
            got = t.column("w").to_numpy()
            np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))
        # the predicate's lane stays f64 only where the device evaluates it
        assert self.bit_lanes == [{"w"}, {"w"} if not device_f64 else set()]
        await eng.close()

    @async_test
    async def test_downsample_pushdown_through_the_fused_pass(self, device_f64):
        store = MemStore()
        eng = await new_engine(store, num_pks=3)  # ts is part of the key
        schema = make_schema()
        rng = np.random.default_rng(2)
        n, series = 600, 4
        sid = rng.integers(0, series, n)
        ts = rng.permutation(n) * 10
        vals = rng.uniform(-100, 100, n)
        vals[::7] *= 1e300
        vals[1::7] *= 1e-300
        for lo in range(0, n, 200):  # three overlapping SSTs
            sl = slice(lo, lo + 200)
            await eng.write(WriteRequest(
                make_batch(schema, sid[sl], sid[sl], ts[sl], vals[sl]),
                TimeRange(0, n * 10)))
        ssts = eng.manifest.all_ssts()
        grids = await eng.parquet_reader.scan_segment_downsample(
            ssts, None, "ts", "value", "pk1", np.arange(series), 0, 1000, 6,
            packed_ok=False)
        want_mx = np.full((series, 6), -np.inf)
        want_mn = np.full((series, 6), np.inf)
        want_sum = np.zeros((series, 6))
        for s, t, v in zip(sid, ts, vals):
            want_mx[s, t // 1000] = max(want_mx[s, t // 1000], v)
            want_mn[s, t // 1000] = min(want_mn[s, t // 1000], v)
            want_sum[s, t // 1000] += v
        np.testing.assert_array_equal(grids["max"], want_mx)
        np.testing.assert_array_equal(grids["min"], want_mn)
        np.testing.assert_allclose(grids["sum"], want_sum, rtol=1e-9)
        assert grids["count"].sum() == n
        assert self.bit_lanes == [{"value"}]
        await eng.close()


class TestBinaryPrimaryKeys:
    """The reference compares binary pks too (macros.rs dispatch); here the
    host path handles them (sort/dedup via arrow compute)."""

    @async_test
    async def test_binary_pk_overwrite_roundtrip(self):
        store = MemStore()
        schema = pa.schema([("name", pa.binary()), ("v", pa.float64())])
        eng = await new_engine(store, schema=schema, num_pks=1)
        b1 = pa.RecordBatch.from_pydict(
            {"name": [b"zeta", b"alpha"], "v": [1.0, 2.0]}, schema=schema
        )
        b2 = pa.RecordBatch.from_pydict(
            {"name": [b"alpha"], "v": [20.0]}, schema=schema
        )
        await eng.write(WriteRequest(b1, TimeRange(10, 11)))
        await eng.write(WriteRequest(b2, TimeRange(10, 11)))
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("name").to_pylist() == [b"alpha", b"zeta"]  # sorted
        assert t.column("v").to_pylist() == [20.0, 1.0]  # newest alpha wins
        await eng.close()

    @async_test
    async def test_binary_pk_with_numeric_predicate(self):
        store = MemStore()
        schema = pa.schema([("name", pa.binary()), ("v", pa.float64())])
        eng = await new_engine(store, schema=schema, num_pks=1)
        b = pa.RecordBatch.from_pydict(
            {"name": [b"a", b"b", b"c"], "v": [1.0, 5.0, 9.0]}, schema=schema
        )
        await eng.write(WriteRequest(b, TimeRange(10, 11)))
        t = await collect(
            eng,
            ScanRequest(range=TimeRange(0, SEGMENT_MS), predicate=F.Compare("v", "gt", 2.0)),
        )
        assert t.column("name").to_pylist() == [b"b", b"c"]
        await eng.close()

    @async_test
    async def test_binary_pk_append_mode_concat(self):
        store = MemStore()
        schema = pa.schema([("name", pa.binary()), ("payload", pa.binary())])
        cfg = StorageConfig(update_mode=UpdateMode.APPEND)
        eng = await new_engine(store, schema=schema, num_pks=1, config=cfg)
        b1 = pa.RecordBatch.from_pydict(
            {"name": [b"k"], "payload": [b"aa"]}, schema=schema
        )
        b2 = pa.RecordBatch.from_pydict(
            {"name": [b"k"], "payload": [b"bb"]}, schema=schema
        )
        await eng.write(WriteRequest(b1, TimeRange(10, 11)))
        await eng.write(WriteRequest(b2, TimeRange(10, 11)))
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("payload").to_pylist() == [b"aabb"]
        await eng.close()


class TestBinaryPkEdgeCases:
    @async_test
    async def test_append_concat_with_projection(self):
        """Projected scans must resolve append-value columns by NAME (index
        positions shift under projection)."""
        store = MemStore()
        schema = pa.schema(
            [("name", pa.binary()), ("a", pa.binary()), ("b", pa.binary())]
        )
        cfg = StorageConfig(update_mode=UpdateMode.APPEND)
        eng = await new_engine(store, schema=schema, num_pks=1, config=cfg)
        for payload in (b"x1", b"x2"):
            await eng.write(
                WriteRequest(
                    pa.RecordBatch.from_pydict(
                        {"name": [b"k"], "a": [payload], "b": [payload.upper()]},
                        schema=schema,
                    ),
                    TimeRange(10, 11),
                )
            )
        t = await collect(
            eng, ScanRequest(range=TimeRange(0, SEGMENT_MS), projections=[0, 1])
        )
        assert t.column("a").to_pylist() == [b"x1x2"]
        await eng.close()

    @async_test
    async def test_large_binary_append_concat(self):
        store = MemStore()
        schema = pa.schema([("name", pa.binary()), ("payload", pa.large_binary())])
        cfg = StorageConfig(update_mode=UpdateMode.APPEND)
        eng = await new_engine(store, schema=schema, num_pks=1, config=cfg)
        for p in (b"aa", b"bb"):
            await eng.write(
                WriteRequest(
                    pa.RecordBatch.from_pydict(
                        {"name": [b"k"], "payload": [p]}, schema=schema
                    ),
                    TimeRange(10, 11),
                )
            )
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("payload").to_pylist() == [b"aabb"]
        await eng.close()

    @async_test
    async def test_predicate_on_binary_pk(self):
        """bytes-literal predicates evaluate on the host path."""
        store = MemStore()
        schema = pa.schema([("name", pa.binary()), ("v", pa.float64())])
        eng = await new_engine(store, schema=schema, num_pks=1)
        await eng.write(
            WriteRequest(
                pa.RecordBatch.from_pydict(
                    {"name": [b"a", b"b", b"c"], "v": [1.0, 2.0, 3.0]}, schema=schema
                ),
                TimeRange(10, 11),
            )
        )
        t = await collect(
            eng,
            ScanRequest(
                range=TimeRange(0, SEGMENT_MS), predicate=F.Compare("name", "eq", b"b")
            ),
        )
        assert t.column("v").to_pylist() == [2.0]
        # mismatched literal type -> clear HoraeError, not TypeError
        with pytest.raises(HoraeError):
            await collect(
                eng,
                ScanRequest(
                    range=TimeRange(0, SEGMENT_MS), predicate=F.Compare("name", "eq", 5)
                ),
            )
        await eng.close()


class TestOverwriteBinary:
    @async_test
    async def test_overwrite_with_binary_value(self):
        """Overwrite mode with a binary value column: hybrid device/host path."""
        store = MemStore()
        schema = pa.schema([("pk", pa.int64()), ("payload", pa.binary())])
        eng = await new_engine(store, schema=schema, num_pks=1)
        b1 = pa.RecordBatch.from_pydict(
            {"pk": np.array([1, 2], dtype=np.int64), "payload": [b"old1", b"old2"]}, schema=schema
        )
        b2 = pa.RecordBatch.from_pydict(
            {"pk": np.array([2], dtype=np.int64), "payload": [b"new2"]}, schema=schema
        )
        await eng.write(WriteRequest(b1, TimeRange(10, 11)))
        await eng.write(WriteRequest(b2, TimeRange(10, 11)))
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert t.column("pk").to_pylist() == [1, 2]
        assert t.column("payload").to_pylist() == [b"old1", b"new2"]
        await eng.close()


class TestIdCollisionGuard:
    def test_allocator_advances_past_manifest_max(self):
        """A clock moved backwards (or foreign ids in the manifest) must not
        let the allocator re-issue an existing SST id — the id doubles as the
        dedup sequence, so a collision silently overwrites data."""
        from horaedb_tpu.storage.sst import _ALLOCATOR, allocate_id, ensure_id_above

        current = allocate_id()
        ensure_id_above(current + 1_000_000)
        nxt = allocate_id()
        assert nxt > current + 1_000_000
        # floor below current: no-op
        ensure_id_above(nxt - 10)
        assert allocate_id() > nxt


class TestScanCompactionRace:
    @async_test
    async def test_stale_segment_list_retries_with_fresh_manifest(self):
        """A scan holding a pre-compaction SST list must transparently
        refresh and return the compacted segment's data when the input
        files have been physically deleted (the scan-vs-compaction race)."""
        import numpy as np
        import pyarrow as pa

        from horaedb_tpu.objstore import MemStore
        from horaedb_tpu.storage.read import ScanRequest, WriteRequest
        from horaedb_tpu.storage.storage import ObjectBasedStorage
        from horaedb_tpu.storage.types import TimeRange

        HOUR = 3_600_000
        schema = pa.schema([("pk", pa.int64()), ("v", pa.float64())])
        store = MemStore()
        eng = await ObjectBasedStorage.try_new(
            root="db", store=store, arrow_schema=schema, num_primary_keys=1,
            segment_duration_ms=HOUR, enable_compaction_scheduler=True,
        )
        for i in range(6):
            batch = pa.RecordBatch.from_pydict(
                {"pk": np.asarray([i], dtype=np.int64), "v": np.asarray([float(i)])},
                schema=schema,
            )
            await eng.write(WriteRequest(batch, TimeRange(0, 10)))
        stale = eng.manifest.all_ssts()  # pre-compaction snapshot
        eng.compaction_scheduler.pick_once()
        import asyncio

        for _ in range(200):
            if len(eng.manifest.all_ssts()) == 1:
                break
            await asyncio.sleep(0.02)
        await eng.compaction_scheduler.executor.drain()
        assert len(eng.manifest.all_ssts()) == 1
        # the stale list's files are gone; the retry must serve the segment
        batches = await eng.scan_segment_retrying(
            stale, TimeRange(0, 100),
            lambda fresh: eng.parquet_reader.scan_segment(
                fresh, predicate=None, projections=None, keep_builtin=False
            ),
            empty_result=[],
        )
        rows = sum(b.num_rows for b in batches)
        assert rows == 6
        # end-to-end: a full scan still works
        got = []
        async for b in eng.scan(ScanRequest(range=TimeRange(0, 100))):
            got.append(b)
        assert sum(b.num_rows for b in got) == 6
        await eng.close()


class TestCrashArtifacts:
    @async_test
    async def test_leftover_tmp_files_ignored_on_recovery(self):
        """A crash mid-put_stream leaves only a `.tmp` staging file; reopen
        must ignore it (never list it as an object) and writes must still
        succeed over it."""
        import os
        import tempfile

        import numpy as np
        import pyarrow as pa

        from horaedb_tpu.objstore import LocalStore
        from horaedb_tpu.storage.read import ScanRequest, WriteRequest
        from horaedb_tpu.storage.storage import ObjectBasedStorage
        from horaedb_tpu.storage.types import TimeRange

        HOUR = 3_600_000
        root = tempfile.mkdtemp(prefix="crash_")
        store = LocalStore(root)
        schema = pa.schema([("pk", pa.int64()), ("v", pa.float64())])
        eng = await ObjectBasedStorage.try_new(
            root="db", store=store, arrow_schema=schema, num_primary_keys=1,
            segment_duration_ms=HOUR, enable_compaction_scheduler=False,
        )
        batch = pa.RecordBatch.from_pydict(
            {"pk": np.arange(3), "v": np.zeros(3)}, schema=schema
        )
        await eng.write(WriteRequest(batch, TimeRange(0, 10)))
        await eng.close()
        # simulate a crashed stream: truncated staging files in data/ and
        # manifest/
        data_dir = os.path.join(root, "db", "data")
        with open(os.path.join(data_dir, "999.sst.tmp"), "wb") as f:
            f.write(b"partial")
        with open(os.path.join(root, "db", "manifest", "snapshot.tmp"), "wb") as f:
            f.write(b"partial")
        listed = {m.path for m in await store.list("db/data")}
        # staging artifacts must never surface as objects
        assert not any(p.endswith(".tmp") for p in listed), listed
        # recovery: open, scan, write again
        eng2 = await ObjectBasedStorage.try_new(
            root="db", store=store, arrow_schema=schema, num_primary_keys=1,
            segment_duration_ms=HOUR, enable_compaction_scheduler=False,
        )
        rows = 0
        async for b in eng2.scan(ScanRequest(range=TimeRange(0, 100))):
            rows += b.num_rows
        assert rows == 3
        batch2 = pa.RecordBatch.from_pydict(
            {"pk": np.arange(10, 13), "v": np.ones(3)}, schema=schema
        )
        await eng2.write(WriteRequest(batch2, TimeRange(10, 20)))
        rows2 = 0
        async for b in eng2.scan(ScanRequest(range=TimeRange(0, 100))):
            rows2 += b.num_rows
        assert rows2 == 6
        # post-recovery listing is equally .tmp-free
        listed_after = {m.path for m in await store.list("db/data")}
        assert not any(p.endswith(".tmp") for p in listed_after), listed_after
        await eng2.close()
        import shutil

        shutil.rmtree(root, ignore_errors=True)


class TestBlockCache:
    @async_test
    async def test_cache_hits_and_correctness_under_new_predicates(self):
        """A cached full-column table must serve DIFFERENT predicates
        correctly (the device mask is the correctness filter) and repeat
        reads must skip the store entirely."""
        import numpy as np
        import pyarrow as pa

        from horaedb_tpu.objstore import MemStore
        from horaedb_tpu.ops import filter as F
        from horaedb_tpu.storage.read import ScanRequest, WriteRequest
        from horaedb_tpu.storage.storage import ObjectBasedStorage
        from horaedb_tpu.storage.types import TimeRange

        HOUR = 3_600_000
        schema = pa.schema([("pk", pa.int64()), ("v", pa.float64())])
        store = MemStore()
        eng = await ObjectBasedStorage.try_new(
            root="db", store=store, arrow_schema=schema, num_primary_keys=1,
            segment_duration_ms=HOUR, enable_compaction_scheduler=False,
        )
        batch = pa.RecordBatch.from_pydict(
            {"pk": np.arange(100), "v": np.arange(100).astype(np.float64)},
            schema=schema,
        )
        await eng.write(WriteRequest(batch, TimeRange(0, 10)))

        async def rows(pred):
            out = 0
            async for b in eng.scan(ScanRequest(range=TimeRange(0, 100), predicate=pred)):
                out += b.num_rows
            return out

        assert await rows(F.Compare("pk", "lt", 10)) == 10
        assert len(eng.parquet_reader._blk_cache) == 1
        # different predicate against the cached entry; then prove the
        # store is no longer consulted at all
        orig_get = store.get
        calls = {"n": 0}

        async def counting_get(path):
            calls["n"] += 1
            return await orig_get(path)

        store.get = counting_get
        assert await rows(F.Compare("pk", "ge", 90)) == 10
        assert await rows(None) == 100
        assert calls["n"] == 0, "cache hit still touched the object store"
        store.get = orig_get
        # deletes evict
        sst_id = eng.manifest.all_ssts()[0].id
        eng.parquet_reader.evict_cached(sst_id)
        assert len(eng.parquet_reader._blk_cache) == 0
        await eng.close()

    @async_test
    async def test_cache_cap_evicts_lru(self):
        import numpy as np
        import pyarrow as pa

        from horaedb_tpu.objstore import MemStore
        from horaedb_tpu.storage.config import StorageConfig
        from horaedb_tpu.storage.read import ScanRequest, WriteRequest
        from horaedb_tpu.storage.storage import ObjectBasedStorage
        from horaedb_tpu.storage.types import TimeRange
        from horaedb_tpu.common.size_ext import ReadableSize

        HOUR = 3_600_000
        schema = pa.schema([("pk", pa.int64()), ("v", pa.float64())])
        cfg = StorageConfig(scan_cache=ReadableSize.kb(16))
        store = MemStore()
        eng = await ObjectBasedStorage.try_new(
            root="db", store=store, arrow_schema=schema, num_primary_keys=1,
            segment_duration_ms=HOUR, config=cfg,
            enable_compaction_scheduler=False,
        )
        for i in range(8):
            batch = pa.RecordBatch.from_pydict(
                {"pk": np.arange(i * 100, i * 100 + 100),
                 "v": np.zeros(100)},
                schema=schema,
            )
            await eng.write(WriteRequest(batch, TimeRange(0, 10)))
        total = 0
        async for b in eng.scan(ScanRequest(range=TimeRange(0, 100))):
            total += b.num_rows
        assert total == 800
        reader = eng.parquet_reader
        # the 8 decoded row groups exceed 16KB, so the LRU must have evicted
        assert reader._blk_cache_bytes <= 16 * 1024
        assert 0 < len(reader._blk_cache) < 8, len(reader._blk_cache)
        # byte accounting never goes negative and matches the live entries
        assert reader._blk_cache_bytes == sum(
            t.nbytes for t in reader._blk_cache.values()
        )
        await eng.close()
