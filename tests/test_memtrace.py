"""The memory observatory: buffer-lineage ledger (common/memtrace.py),
the unified byte-budget pool registry (common/bytebudget.py), and the
route-level alloc/copy accounting the data-plane funnels feed.

Pins:
- ledger mechanics: kinds, copy-vs-view honesty of every funnel helper,
  verdict schema, fleet verdict_merge, deep-mode attribution;
- route shapes: cold scan allocates + copies, the cache-hit route
  allocates NOTHING new, the encoded route reports decode-stage allocs,
  the rollup read reports the fill once (then serves from cache silently);
- the scan pool's gauge: a cold scan's decoded blocks show in the
  unified registry and leave it when their SST is evicted;
- memtrace's own cost: off mode is a string compare, default mode stays
  microseconds-free per event (the <2% query-p50 bound is measured by
  tools/mem_smoke.py on real scans — these bounds only catch a runaway).
"""

import gc
import time
from types import SimpleNamespace

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import memtrace
from horaedb_tpu.common.bytebudget import (
    GLOBAL_POOLS,
    POOLS,
    PoolRegistry,
    rss_bytes,
)
from horaedb_tpu.objstore import MemStore
from horaedb_tpu.ops.filter import And, Compare, InSet
from horaedb_tpu.storage import (
    ObjectBasedStorage,
    ScanRequest,
    StorageConfig,
    TimeRange,
    WriteRequest,
    scanstats,
)
from horaedb_tpu.storage.config import EncodingConfig
from horaedb_tpu.storage.rollup import (
    RollupRecord,
    compute_rollup,
    encode_rollup,
    evict_rollup,
    read_rollup,
)

from tests.conftest import async_test

SEGMENT_MS = 24 * 3_600_000
T0 = (1_700_000_000_000 // SEGMENT_MS + 1) * SEGMENT_MS


@pytest.fixture(autouse=True)
def default_mode():
    """Every test starts in default ("") mode and restores the prior."""
    prior = memtrace.mode()
    memtrace.configure("")
    yield
    memtrace.configure(prior)


# ---------------------------------------------------------------------------
# Ledger mechanics


class TestLedger:
    def test_track_returns_buf_and_records(self):
        buf = np.zeros(100, dtype=np.float64)
        with memtrace.mem_trace() as led:
            out = memtrace.track(buf, "materialize", "alloc")
            assert out is buf
            memtrace.track_bytes(50, "materialize", "copy")
        v = memtrace.verdict(led)
        assert v["enabled"] is True
        assert v["allocs"] == 1 and v["copies"] == 1
        assert v["per_stage"]["materialize"]["alloc_bytes"] == buf.nbytes
        assert v["per_stage"]["materialize"]["copy_bytes"] == 50
        # alloc + copy both count toward bytes_allocated; only copy
        # toward bytes_copied
        assert v["bytes_allocated"] == buf.nbytes + 50
        assert v["bytes_copied"] == 50

    def test_off_mode_yields_none_and_records_nothing(self):
        memtrace.configure("off")
        before = memtrace.copy_tax_table()
        with memtrace.mem_trace() as led:
            assert led is None
            memtrace.track(np.zeros(10), "parse", "alloc")
            memtrace.track_bytes(10, "parse", "alloc")
            memtrace.device_staged(10)
        assert memtrace.copy_tax_table() == before
        v = memtrace.verdict(led)
        assert v["enabled"] is False and v["allocs"] == 0

    def test_funnels_classify_copy_vs_view(self):
        contig = np.arange(64, dtype=np.int64)
        strided = np.arange(128, dtype=np.int64)[::2]
        single = pa.table({"a": np.arange(8)})
        multi = pa.Table.from_batches([
            pa.record_batch({"a": np.arange(8)}),
            pa.record_batch({"a": np.arange(8)}),
        ])
        with memtrace.mem_trace() as led:
            out = memtrace.tracked_contiguous(contig, "h2d")
            assert out is contig                        # view
            memtrace.tracked_contiguous(strided, "h2d")  # copy
            memtrace.tracked_copy(contig, "host_prep")   # copy
            memtrace.tracked_concat([contig, contig], "seal")  # copy
            memtrace.tracked_combine(single, "materialize")    # view
            memtrace.tracked_combine(multi, "materialize")     # copy
            memtrace.tracked_concat_tables(
                [single, single], "host_prep")                 # view
        v = memtrace.verdict(led)
        assert v["per_stage"]["h2d"] == {
            "copy": 1, "copy_bytes": strided.nbytes,
            "view": 1, "view_bytes": contig.nbytes,
        }
        assert v["per_stage"]["materialize"]["view"] == 1
        assert v["per_stage"]["materialize"]["copy"] == 1
        assert v["copies"] == 4 and v["views"] == 3

    def test_funnels_identical_data_in_off_mode(self):
        """The data path must not depend on the mode — same outputs,
        only the accounting differs."""
        strided = np.arange(128, dtype=np.int64)[::2]
        multi = pa.Table.from_batches([
            pa.record_batch({"a": np.arange(8)}),
            pa.record_batch({"a": np.arange(8)}),
        ])
        on = (
            memtrace.tracked_contiguous(strided, "h2d"),
            memtrace.tracked_concat([strided, strided], "seal"),
            memtrace.tracked_combine(multi, "materialize"),
        )
        memtrace.configure("off")
        off = (
            memtrace.tracked_contiguous(strided, "h2d"),
            memtrace.tracked_concat([strided, strided], "seal"),
            memtrace.tracked_combine(multi, "materialize"),
        )
        np.testing.assert_array_equal(on[0], off[0])
        np.testing.assert_array_equal(on[1], off[1])
        assert on[2].equals(off[2])

    def test_device_staged_rides_ledger_and_odometer(self):
        with memtrace.mem_trace() as led:
            memtrace.device_staged(4096)
        v = memtrace.verdict(led)
        assert v["device_staging_bytes"] == 4096
        assert v["per_stage"]["h2d"]["copy_bytes"] == 4096

    def test_verdict_schema_pinned(self):
        with memtrace.mem_trace() as led:
            memtrace.track_bytes(1, "parse", "alloc")
        assert tuple(sorted(memtrace.verdict(led)))\
            == tuple(sorted(memtrace.VERDICT_KEYS))
        # off-mode verdict renders the SAME keys (dashboards never
        # branch on key presence)
        assert tuple(sorted(memtrace.verdict(None)))\
            == tuple(sorted(memtrace.VERDICT_KEYS))

    def test_nested_trace_shadows_outer(self):
        with memtrace.mem_trace() as outer:
            memtrace.track_bytes(10, "parse", "alloc")
            with memtrace.mem_trace() as inner:
                memtrace.track_bytes(99, "decode", "copy")
            memtrace.track_bytes(10, "parse", "alloc")
        assert memtrace.verdict(outer)["allocs"] == 2
        assert memtrace.verdict(outer)["copies"] == 0
        assert memtrace.verdict(inner)["copies"] == 1

    def test_verdict_merge_fleet_graft(self):
        with memtrace.mem_trace() as led:
            memtrace.track_bytes(100, "materialize", "alloc")
        base = memtrace.verdict(led)
        frag = {
            "enabled": True, "deep": True, "bytes_allocated": 7,
            "bytes_copied": 7, "allocs": 0, "copies": 2, "views": 1,
            "reuses": 0, "device_staging_bytes": 5,
            "peak_delta_bytes": 1234,
            "per_stage": {"materialize": {"copy": 2, "copy_bytes": 7}},
            "top_sites": [{"site": "x.py:1", "kib": 9.0, "count": 1}],
        }
        merged = memtrace.verdict_merge(base, frag)
        assert merged["copies"] == 2 and merged["allocs"] == 1
        assert merged["bytes_allocated"] == 100 + 7
        assert merged["device_staging_bytes"] == 5
        assert merged["per_stage"]["materialize"]["copy"] == 2
        assert merged["per_stage"]["materialize"]["alloc"] == 1
        # peaks take max (peaks on different nodes do not sum)
        assert merged["peak_delta_bytes"] == 1234 and merged["deep"]
        assert merged["top_sites"][0]["site"] == "x.py:1"
        # a disabled fragment is a no-op
        assert memtrace.verdict_merge(base, memtrace.verdict(None)) == base

    def test_deep_mode_attributes_peak_and_sites(self):
        memtrace.configure("deep")
        with memtrace.mem_trace() as led:
            blobs = [np.zeros(256 * 1024, dtype=np.uint8)
                     for _ in range(4)]
            memtrace.track(blobs[0], "materialize", "alloc")
        v = memtrace.verdict(led)
        assert v["deep"] is True
        assert v["peak_delta_bytes"] is not None
        assert v["peak_delta_bytes"] >= 4 * 256 * 1024
        assert v["top_sites"], "deep mode must attribute sites"
        assert {"site", "kib", "count"} <= set(v["top_sites"][0])

    def test_configure_rejects_unknown_mode(self):
        from horaedb_tpu.common.error import HoraeError

        with pytest.raises(HoraeError):
            memtrace.configure("verbose")


# ---------------------------------------------------------------------------
# memtrace's own cost: loose runaway bounds; the honest <2% scan-p50
# measurement lives in tools/mem_smoke.py where the scan does real work.


class TestOverhead:
    def _ns_per_event(self, n: int = 50_000) -> float:
        with memtrace.mem_trace():
            t0 = time.perf_counter()
            for _ in range(n):
                memtrace.track_bytes(1024, "parse", "alloc")
            return (time.perf_counter() - t0) / n * 1e9

    def test_off_mode_is_near_free(self):
        memtrace.configure("off")
        assert self._ns_per_event() < 2_000  # a string compare + return

    def test_default_mode_stays_cheap(self):
        assert self._ns_per_event() < 20_000  # dict hit + counter add


# ---------------------------------------------------------------------------
# Byte-budget pool registry


class TestByteBudget:
    def test_refresh_shape_covers_all_pools(self):
        out = GLOBAL_POOLS.refresh()
        assert set(POOLS) <= set(out)
        for pool, row in out.items():
            assert {"bytes", "entries", "capacity_bytes", "utilization",
                    "evictions", "owners"} <= set(row)

    def test_provider_sum_and_weakref_pruning(self):
        reg = PoolRegistry()

        class Owner:
            def __init__(self, b, n):
                self.b, self.n = b, n

        a, b = Owner(100, 2), Owner(50, 1)
        reg.register_provider("scan", a, lambda o: (o.b, o.n))
        reg.register_provider("scan", b, lambda o: (o.b, o.n))
        row = reg.refresh()["scan"]
        assert row["bytes"] == 150 and row["entries"] == 3
        assert row["owners"] == 2
        del b
        gc.collect()
        row = reg.refresh()["scan"]
        assert row["bytes"] == 100 and row["owners"] == 1

    def test_capacity_and_utilization(self):
        reg = PoolRegistry()

        class Owner:
            pass

        o = Owner()
        reg.register_provider("result", o, lambda _o: (256, 4))
        reg.set_capacity("result", 1024)
        row = reg.refresh()["result"]
        assert row["capacity_bytes"] == 1024
        assert row["utilization"] == 0.25
        reg.set_capacity("result", 0)
        assert reg.refresh()["result"]["utilization"] is None

    def test_torn_provider_read_is_skipped(self):
        reg = PoolRegistry()

        class Owner:
            pass

        o = Owner()
        reg.register_provider("rollup", o, lambda _o: 1 / 0)
        row = reg.refresh()["rollup"]
        assert row["bytes"] == 0 and row["owners"] == 0

    def test_eviction_counter(self):
        before = GLOBAL_POOLS.refresh()["sidecar"]["evictions"]
        GLOBAL_POOLS.note_eviction("sidecar")
        GLOBAL_POOLS.note_eviction("sidecar", 2)
        assert GLOBAL_POOLS.refresh()["sidecar"]["evictions"] == before + 3

    def test_rss_bytes_reads_statm(self):
        rss = rss_bytes()
        # linux CI: statm exists and a python process is >10 MiB resident
        assert rss is None or rss > 10 * 1024 * 1024


# ---------------------------------------------------------------------------
# Route-level accounting through a real storage tree


def make_schema():
    return pa.schema([
        ("tsid", pa.int64()), ("ts", pa.int64()), ("value", pa.float64()),
    ])


async def new_engine(store, config=None, **kw):
    kw.setdefault("enable_compaction_scheduler", False)
    kw.setdefault("start_background_merger", False)
    return await ObjectBasedStorage.try_new(
        root="db", store=store, arrow_schema=make_schema(),
        num_primary_keys=2, segment_duration_ms=SEGMENT_MS,
        config=config, **kw,
    )


async def write_rows(eng, seed, n=4000):
    rng = np.random.default_rng(seed)
    tsid = np.sort(rng.integers(0, 32, n))
    ts = T0 + (np.arange(n, dtype=np.int64) * 1000) % SEGMENT_MS
    batch = pa.RecordBatch.from_pydict(
        {"tsid": tsid, "ts": ts, "value": rng.normal(size=n)},
        schema=make_schema(),
    )
    await eng.write(WriteRequest(
        batch, TimeRange(int(ts.min()), int(ts.max()) + 1),
    ))


async def scan_verdict(eng, predicate=None) -> dict:
    req = ScanRequest(range=TimeRange(0, 2**62), predicate=predicate)
    with scanstats.scan_stats() as st:
        async for _ in eng.scan(req):
            pass
    return memtrace.verdict(st.mem)


class TestRouteAccounting:
    @async_test
    async def test_cold_scan_vs_cache_hit(self):
        """The raw route's shape: a cold scan allocates (parquet decode)
        and copies (host_prep / materialize); the cache-hit rerun of the
        SAME scan allocates NOTHING new — the decoded blocks are served
        by reference. The exact counts are pinned by `make mem-smoke`;
        this test pins the route-shape INVARIANTS."""
        eng = await new_engine(MemStore())
        try:
            await write_rows(eng, seed=1)
            await write_rows(eng, seed=2)
            cold = await scan_verdict(eng)
            warm = await scan_verdict(eng)
        finally:
            await eng.close()
        assert cold["enabled"] and cold["allocs"] > 0
        assert "materialize" in cold["per_stage"]
        assert cold["bytes_allocated"] > 0
        # the cache-hit route: zero fresh allocations, and no more
        # copies than the cold route paid
        assert warm["per_stage"].get("materialize", {}).get("alloc", 0) == 0
        assert warm["allocs"] == 0
        assert warm["copies"] <= cold["copies"]

    @async_test
    async def test_encoded_route_reports_decode_stage(self):
        """Format-v2 scans expand encoded pages through ops/decode.py —
        the verdict must carry the decode-stage allocation so EXPLAIN
        distinguishes 'decoded N bytes' from 'materialized N bytes'."""
        cfg = StorageConfig(
            encoding=EncodingConfig(enabled=True, min_rows=1),
        )
        eng = await new_engine(MemStore(), config=cfg)
        try:
            await write_rows(eng, seed=3)
            pred = And(
                InSet("tsid", (1, 2, 3)),
                Compare("value", "gt", 0.0),
            )
            v = await scan_verdict(eng, predicate=pred)
        finally:
            await eng.close()
        assert "decode" in v["per_stage"], sorted(v["per_stage"])
        assert v["per_stage"]["decode"].get("alloc", 0) >= 1

    @async_test
    async def test_rollup_read_reports_fill_once(self):
        """read_rollup charges the rollup_fill stage when the artifact
        enters the decoded-LRU; the repeat read serves from cache and
        charges nothing."""
        src = pa.table({
            "tsid": np.repeat(np.arange(4, dtype=np.int64), 25),
            "ts": np.tile(np.arange(25, dtype=np.int64) * 1000, 4),
            "value": np.ones(100),
        })
        rolled = compute_rollup(src, ["tsid"], "ts", "value", 5000)
        blob = encode_rollup(rolled)
        sst_id = 987_654_321  # unique: never collides with other tests
        evict_rollup(sst_id)
        rec = RollupRecord(
            id=1, resolution_ms=5000, segment_start=0, sst_id=sst_id,
            num_rows=rolled.num_rows, size=len(blob),
            time_range=TimeRange(0, 25_000),
            source_sst_ids=(), tombstone_ids=(),
        )

        class _Store:
            async def get(self, _path):
                return blob

        class _Gen:
            def generate_rollup(self, sid):
                return f"rollup/{sid}.sst"

        stub = SimpleNamespace(sst_path_gen=_Gen(), store=_Store())
        try:
            with scanstats.scan_stats() as st:
                lanes = await read_rollup(stub, rec)
            first = memtrace.verdict(st.mem)
            with scanstats.scan_stats() as st:
                again = await read_rollup(stub, rec)
            second = memtrace.verdict(st.mem)
        finally:
            evict_rollup(sst_id)
        assert set(lanes) == set(rolled.schema.names)
        assert first["per_stage"]["rollup_fill"]["view"] == 1
        assert "decode" in first["per_stage"]
        assert second["per_stage"] == {}  # pure cache hit
        assert again is lanes  # served by reference, not re-decoded

    @async_test
    async def test_ingest_write_reports_flush_encode(self):
        eng = await new_engine(MemStore())
        try:
            with scanstats.scan_stats() as st:
                await write_rows(eng, seed=4)
            v = memtrace.verdict(st.mem)
        finally:
            await eng.close()
        assert "flush_encode" in v["per_stage"], sorted(v["per_stage"])
        assert v["per_stage"]["flush_encode"].get("alloc_bytes", 0) > 0


# ---------------------------------------------------------------------------
# The scan pool's gauge through a real storage tree


class TestScanPoolGauge:
    @async_test
    async def test_pool_gauges_track_scan_fill_and_eviction(self):
        """The unified registry's refresh() sees the live reader's scan
        pool fill on a cold scan and drain when the SSTs are evicted."""
        eng = await new_engine(MemStore())
        try:
            before = GLOBAL_POOLS.refresh()["scan"]["bytes"]
            await write_rows(eng, seed=6)
            await scan_verdict(eng)
            reader = eng.parquet_reader
            after_cold = GLOBAL_POOLS.refresh()["scan"]
            assert after_cold["bytes"] > before
            assert after_cold["bytes"] - before == reader._blk_cache_bytes
            for sst_id in {k[0] for k in reader._blk_cache}:
                reader.evict_cached(sst_id)
            assert not reader._blk_cache
            assert GLOBAL_POOLS.refresh()["scan"]["bytes"] == before
        finally:
            await eng.close()
