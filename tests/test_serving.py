"""Serving-tier tests (horaedb_tpu/serving + storage/rollup.py).

The contract under test is the tentpole's honesty clause: every answer
the serving tier produces — result-cache hits, rollup-substituted range
queries — must be EXACTLY the answer a forced cold scan produces
(`HORAEDB_SERVING=off`), including after flushes, compactions, deletes,
and reopen. Sample values are integer-valued floats so float64 summation
is exact under any association order; the equality asserts are then
bit-exact, not approximate.
"""

from __future__ import annotations

import asyncio
import os
import re

import numpy as np
import pytest

from horaedb_tpu.engine import MetricEngine, QueryRequest
from horaedb_tpu.objstore import MemStore
from horaedb_tpu.serving import ServingTierConfig
from horaedb_tpu.serving.cache import RESULT_CACHE, ResultCache
from horaedb_tpu.storage import scanstats
from horaedb_tpu.storage import rollup as rollup_mod
from horaedb_tpu.storage.config import SchedulerConfig, StorageConfig
from horaedb_tpu.storage.types import TimeRange
from tests.conftest import async_test
from tests.test_engine import make_remote_write

MIN = 60_000
HOUR = 3_600_000
DAY = 24 * HOUR


@pytest.fixture(autouse=True)
def _clean_serving(monkeypatch):
    """Isolate the process-global serving state per test: the honesty
    switch unset, the result cache empty and at a known capacity."""
    monkeypatch.delenv("HORAEDB_SERVING", raising=False)
    RESULT_CACHE.clear()
    RESULT_CACHE.configure(64 << 20)
    yield
    RESULT_CACHE.clear()


def small_compactions() -> StorageConfig:
    """Two SSTs qualify a segment for compaction (default min is 5)."""
    cfg = StorageConfig()
    cfg.scheduler = SchedulerConfig(input_sst_min_num=2)
    return cfg


async def open_serving_engine(store, **kw):
    kw.setdefault("segment_duration_ms", HOUR)
    kw.setdefault("enable_compaction", True)
    kw.setdefault("config", small_compactions())
    return await MetricEngine.open("db", store, **kw)


async def compact_drain(eng) -> None:
    """Drive compaction to quiescence deterministically: pick directly
    (the trigger channel rides a background loop and can race a drain),
    wait out the recv-loop handoff + the executor, and repeat until no
    further pick lands (follow-on segments)."""
    sched = eng.data_table.compaction_scheduler
    for _ in range(64):
        picked = sched.pick_once()
        while sched._tasks.qsize() or sched.executor._inflight:
            await asyncio.sleep(0.001)
            await sched.executor.drain()
        if not picked:
            return
    raise AssertionError("compaction never quiesced")


async def seed_two_sst_segments(eng, hours: int = 3, hosts=("a", "b")):
    """Per hour-segment, two flushed SSTs of per-minute integer samples."""
    for half in (0, 1):
        series = []
        for h in hosts:
            samples = []
            for hr in range(hours):
                for m in range(30 * half, 30 * half + 30):
                    ts = hr * HOUR + m * MIN
                    samples.append((ts, float(hr * 100 + m)))
            series.append(({"__name__": "cpu", "host": h}, samples))
        await eng.write_payload(make_remote_write(series))
        await eng.flush()


def assert_same_answer(got, want) -> None:
    """Bit-exact equality across the two query result shapes."""
    if want is None or got is None:
        assert got is None and want is None
        return
    if hasattr(want, "equals"):  # pa.Table (raw rows)
        assert got.equals(want)
        return
    got_ids, got_grids = got
    want_ids, want_grids = want
    assert got_ids == want_ids
    assert set(got_grids) == set(want_grids)
    for k in want_grids:
        np.testing.assert_array_equal(
            np.asarray(got_grids[k]), np.asarray(want_grids[k]),
            err_msg=f"grid {k} diverged",
        )


async def forced_cold(eng, req: QueryRequest):
    """The oracle: the same query with every serving shortcut disabled."""
    os.environ["HORAEDB_SERVING"] = "off"
    try:
        return await eng.query(req)
    finally:
        del os.environ["HORAEDB_SERVING"]


QUERY_SHAPES = [
    # (name, request kwargs) — every read shape the engine's native
    # surface offers; PromQL rides the same query_raw/query_downsample
    # choke point underneath.
    ("raw_full", dict(start_ms=0, end_ms=3 * HOUR)),
    ("raw_filtered", dict(start_ms=0, end_ms=3 * HOUR,
                          filters=[(b"host", b"a")])),
    ("raw_limited", dict(start_ms=0, end_ms=3 * HOUR, limit=7)),
    ("ds_hour", dict(start_ms=0, end_ms=3 * HOUR, bucket_ms=HOUR)),
    ("ds_minute", dict(start_ms=0, end_ms=3 * HOUR, bucket_ms=5 * MIN)),
    ("ds_filtered", dict(start_ms=0, end_ms=3 * HOUR, bucket_ms=HOUR,
                         filters=[(b"host", b"b")])),
    ("ds_unaligned", dict(start_ms=0, end_ms=3 * HOUR, bucket_ms=7000)),
    ("ds_offset_range", dict(start_ms=HOUR, end_ms=2 * HOUR,
                             bucket_ms=15 * MIN)),
]


class TestBitExactVsForcedCold:
    @async_test
    async def test_every_query_shape_cold_warm_and_forced_off_agree(self):
        """For every query shape: the first (miss, computed) answer, the
        second (cache-hit) answer, and the HORAEDB_SERVING=off forced
        cold answer are identical — after flush AND after compaction
        (when rollup substitution kicks in for aligned shapes)."""
        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng)
            for phase in ("flushed", "compacted"):
                if phase == "compacted":
                    await compact_drain(eng)
                for name, kw in QUERY_SHAPES:
                    req = QueryRequest(metric=b"cpu", **kw)
                    first = await eng.query(req)
                    second = await eng.query(req)
                    cold = await forced_cold(eng, req)
                    assert_same_answer(first, cold), f"{phase}:{name}"
                    assert_same_answer(second, cold), f"{phase}:{name}"
        finally:
            await eng.close()

    @async_test
    async def test_post_delete_requery_exact(self):
        """A tombstone delete between queries: the re-query must never
        serve the pre-delete cached answer (key epoch + eager purge),
        and stays exact vs forced cold."""
        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng)
            req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=3 * HOUR,
                               bucket_ms=HOUR)
            before = await eng.query(req)
            await eng.delete_series(b"cpu", filters=[(b"host", b"a")],
                                    start_ms=0, end_ms=HOUR)
            after = await eng.query(req)
            cold = await forced_cold(eng, req)
            assert_same_answer(after, cold)
            # the delete actually changed the answer (host a, hour 0 gone)
            assert not np.array_equal(
                np.asarray(before[1]["count"]), np.asarray(after[1]["count"])
            )
            # and post-compaction (tombstone applied physically + rollups
            # rebuilt with it) the answer still agrees with forced cold
            await compact_drain(eng)
            again = await eng.query(req)
            assert_same_answer(again, await forced_cold(eng, req))
        finally:
            await eng.close()

    @async_test
    async def test_exemplars_ride_the_same_choke_point(self):
        from horaedb_tpu.pb import remote_write_pb2

        eng = await open_serving_engine(MemStore())
        try:
            wreq = remote_write_pb2.WriteRequest()
            ts = wreq.timeseries.add()
            for k, v in ((b"__name__", b"ex"), (b"host", b"a")):
                lab = ts.labels.add()
                lab.name = k
                lab.value = v
            for t, v in ((1000, 1.0), (2000, 2.0)):
                s = ts.samples.add()
                s.timestamp = t
                s.value = v
            ex = ts.exemplars.add()
            ex.value = 42.0
            ex.timestamp = 1500
            lab = ex.labels.add()
            lab.name = b"trace_id"
            lab.value = b"t1"
            await eng.write_payload(wreq.SerializeToString())
            await eng.flush()
            req = QueryRequest(metric=b"ex", start_ms=0, end_ms=10_000)
            first = await eng.query_exemplars(req)
            second = await eng.query_exemplars(req)
            os.environ["HORAEDB_SERVING"] = "off"
            try:
                cold = await eng.query_exemplars(req)
            finally:
                del os.environ["HORAEDB_SERVING"]
            assert_same_answer(first, cold)
            assert_same_answer(second, cold)
        finally:
            await eng.close()


class TestResultCacheFlow:
    @async_test
    async def test_miss_hit_then_every_mutation_invalidates(self):
        """The smoke_metrics storyline at engine level: miss -> hit ->
        write invalidates -> miss; plus compaction and delete as the
        other two funnel reasons, with counters moving."""
        from horaedb_tpu.serving import CACHE_REQUESTS, INVALIDATIONS

        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng, hours=1)
            req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=HOUR,
                               bucket_ms=HOUR)
            miss0 = CACHE_REQUESTS.labels("miss").value
            hit0 = CACHE_REQUESTS.labels("hit").value

            await eng.query(req)
            assert CACHE_REQUESTS.labels("miss").value == miss0 + 1
            await eng.query(req)
            assert CACHE_REQUESTS.labels("hit").value == hit0 + 1

            # flush invalidation: new data -> recompute (fresh answer)
            inv_flush0 = INVALIDATIONS.labels("flush").value
            await eng.write_payload(make_remote_write(
                [({"__name__": "cpu", "host": "a"}, [(30 * MIN + 1, 999.0)])]
            ))
            await eng.flush()
            assert INVALIDATIONS.labels("flush").value > inv_flush0
            got = await eng.query(req)
            assert CACHE_REQUESTS.labels("miss").value == miss0 + 2
            assert_same_answer(got, await forced_cold(eng, req))

            # compaction invalidation
            inv_compact0 = INVALIDATIONS.labels("compact").value
            await eng.query(req)  # warm it again
            await compact_drain(eng)
            assert INVALIDATIONS.labels("compact").value > inv_compact0
            await eng.query(req)
            assert CACHE_REQUESTS.labels("miss").value == miss0 + 3

            # delete invalidation
            inv_del0 = INVALIDATIONS.labels("delete").value
            await eng.query(req)
            await eng.delete_series(b"cpu", filters=[(b"host", b"a")],
                                    start_ms=0, end_ms=HOUR)
            assert INVALIDATIONS.labels("delete").value > inv_del0
            got = await eng.query(req)
            assert CACHE_REQUESTS.labels("miss").value == miss0 + 4
            assert_same_answer(got, await forced_cold(eng, req))
        finally:
            await eng.close()

    @async_test
    async def test_honesty_switch_bypasses_and_stores_nothing(self):
        from horaedb_tpu.serving import CACHE_REQUESTS

        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng, hours=1)
            bypass0 = CACHE_REQUESTS.labels("bypass").value
            os.environ["HORAEDB_SERVING"] = "off"
            try:
                req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=HOUR)
                await eng.query(req)
                await eng.query(req)
            finally:
                del os.environ["HORAEDB_SERVING"]
            assert CACHE_REQUESTS.labels("bypass").value >= bypass0 + 2
            assert RESULT_CACHE.resident_bytes == 0
            assert len(RESULT_CACHE._entries) == 0
        finally:
            await eng.close()

    @async_test
    async def test_disabled_tier_config(self):
        """ServingTierConfig(enabled=False): queries compute cold, no
        cache writes, no rollup emission at compaction."""
        eng = await open_serving_engine(
            MemStore(), serving=ServingTierConfig(enabled=False)
        )
        try:
            await seed_two_sst_segments(eng, hours=1)
            req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=HOUR)
            a = await eng.query(req)
            b = await eng.query(req)
            assert_same_answer(a, b)
            assert RESULT_CACHE.resident_bytes == 0
            await compact_drain(eng)
            assert eng.data_table.manifest.rollup_records() == {}
        finally:
            await eng.close()


class TestRollupEmission:
    @async_test
    async def test_compaction_emits_exact_records_per_resolution(self):
        """A full-segment compaction emits one artifact per configured
        resolution; the record's source set is exactly the segment's
        live SST set, and the artifact's sum/count/min/max lanes agree
        with a first-principles aggregation of the raw rows."""
        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng, hours=2)
            await compact_drain(eng)
            storage = eng.data_table
            records = storage.manifest.rollup_records()
            segs = {k[0] for k in records}
            ress = {k[1] for k in records}
            assert segs == {0, HOUR}
            assert ress == {MIN, HOUR}
            for (seg_start, res), rec in records.items():
                live = {
                    s.id for s in storage.manifest.find_ssts(
                        TimeRange(seg_start, seg_start + HOUR)
                    )
                }
                assert set(rec.source_sst_ids) == live
                assert rec.resolution_ms == res
                # artifact content: exact vs the raw rows of the segment
                lanes = await rollup_mod.read_rollup(storage, rec)
                raw = await forced_cold(eng, QueryRequest(
                    metric=b"cpu", start_ms=seg_start,
                    end_ms=seg_start + HOUR,
                ))
                ts = raw.column("ts").to_numpy()
                tsid = raw.column("tsid").to_numpy()
                val = raw.column("value").to_numpy()
                want: dict = {}
                for t, s, v in zip(ts, tsid, val):
                    key = (int(s), int(t) - int(t) % res)
                    agg = want.setdefault(key, [0.0, 0, np.inf, -np.inf])
                    agg[0] += v
                    agg[1] += 1
                    agg[2] = min(agg[2], v)
                    agg[3] = max(agg[3], v)
                got = {
                    (int(s), int(b)): [su, int(c), mn, mx]
                    for s, b, su, c, mn, mx in zip(
                        lanes["tsid"], lanes["ts"], lanes["sum"],
                        lanes["count"], lanes["min"], lanes["max"],
                    )
                }
                assert got == want
                assert rec.num_rows == len(want)
        finally:
            await eng.close()

    @async_test
    async def test_recompaction_supersedes_and_gc_reclaims(self):
        """A later compaction of the same segment (new data arrived)
        re-emits; the superseded record AND its artifact object are
        gone, and no unreferenced rollup object survives."""
        store = MemStore()
        eng = await open_serving_engine(store)
        try:
            await seed_two_sst_segments(eng, hours=1)
            await compact_drain(eng)
            storage = eng.data_table
            rec1 = dict(storage.manifest.rollup_records())
            assert rec1
            # two more SSTs into the same segment -> re-compactable
            for v in (7.0, 8.0):
                await eng.write_payload(make_remote_write(
                    [({"__name__": "cpu", "host": "a"},
                      [(int(v) * MIN + 17, v)])]
                ))
                await eng.flush()
            await compact_drain(eng)
            rec2 = dict(storage.manifest.rollup_records())
            assert set(rec2) == set(rec1)  # same (segment, resolution) slots
            for k in rec1:
                assert rec2[k].id > rec1[k].id
            live_objs = {
                storage.sst_path_gen.generate_rollup(r.sst_id)
                for r in rec2.values()
            }
            rollup_objs = {
                p for p in store._objects if "/rollup/" in p
                and p.endswith(".sst")
            }
            assert rollup_objs == live_objs
        finally:
            await eng.close()

    @async_test
    async def test_superseded_record_object_reclaimed_at_open(self):
        """A crashed supersede-delete leaves an OLDER record object for a
        slot a newer record owns. No later GC pass walks store objects —
        the load must drop the loser's object or it leaks forever."""
        import dataclasses

        from horaedb_tpu.storage.manifest import rollup_record_path

        store = MemStore()
        eng = await open_serving_engine(store)
        await seed_two_sst_segments(eng, hours=1)
        await compact_drain(eng)
        winner = next(iter(
            eng.data_table.manifest.rollup_records().values()
        ))
        stale = dataclasses.replace(winner, id=1, sst_id=999_999_998)
        stale_path = rollup_record_path("db/data", stale.id)
        await store.put(stale_path, stale.to_json())
        await eng.close()
        eng2 = await open_serving_engine(store)
        try:
            assert stale_path not in store._objects
            recs = eng2.data_table.manifest.rollup_records()
            key = (winner.segment_start, winner.resolution_ms)
            assert recs[key].id == winner.id  # the winner survived intact
        finally:
            await eng2.close()

    @async_test
    async def test_orphan_rollup_gc_on_reopen(self):
        """A rollup object with no record (crash between artifact PUT and
        record PUT) is reclaimed at open."""
        store = MemStore()
        eng = await open_serving_engine(store)
        await seed_two_sst_segments(eng, hours=1)
        await compact_drain(eng)
        orphan = "db/data/rollup/999999999.sst"
        await store.put(orphan, b"stranded-artifact")
        await eng.close()
        eng2 = await open_serving_engine(store)
        try:
            assert orphan not in store._objects
            # referenced artifacts survived the GC
            for r in eng2.data_table.manifest.rollup_records().values():
                path = eng2.data_table.sst_path_gen.generate_rollup(r.sst_id)
                assert path in store._objects
        finally:
            await eng2.close()


class TestRollupSubstitution:
    @async_test
    async def test_step_1h_over_30d_reads_bucket_count_scale_rows(self):
        """The acceptance criterion: an EXPLAIN'd range query at step=1h
        over 30 days reads bucket-count-scale rollup rows (one per
        series per active hour), not the raw per-minute rows — and the
        answer is bit-exact vs the forced-cold raw scan."""
        eng = await open_serving_engine(
            MemStore(), segment_duration_ms=DAY,
        )
        try:
            # 30 day-segments, two SSTs each: per-minute samples in each
            # day's hour 0 (60 raw rows/series/day -> 1 rollup row at 1h)
            for half in (0, 1):
                series = []
                for host in ("a", "b"):
                    samples = [
                        (d * DAY + m * MIN, float(d + m))
                        for d in range(30)
                        for m in range(30 * half, 30 * half + 30)
                    ]
                    series.append(({"__name__": "cpu", "host": host}, samples))
                await eng.write_payload(make_remote_write(series))
                await eng.flush()
            await compact_drain(eng)
            records = eng.data_table.manifest.rollup_records()
            assert {k[0] for k in records} == {d * DAY for d in range(30)}

            req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=30 * DAY,
                               bucket_ms=HOUR)
            with scanstats.scan_stats() as st:
                got = await eng.query(req)
            raw_rows = 2 * 30 * 60          # series x days x minutes
            rollup_rows = 2 * 30            # series x active hours
            assert st.counts.get("rollup_segments") == 30
            assert st.counts.get("rollup_rows_read") == rollup_rows
            assert st.counts.get("rollup_res_1h") == 30
            assert not st.counts.get("raw_segments")
            assert rollup_rows * 60 == raw_rows  # the scale the tier buys
            assert_same_answer(got, await forced_cold(eng, req))
            # cache hit on repeat replays the provenance (EXPLAIN on a
            # hit still names the substitution)
            with scanstats.scan_stats() as st2:
                again = await eng.query(req)
            assert st2.counts.get("serving_cache_hit") == 1
            assert st2.counts.get("rollup_segments") == 30
            assert_same_answer(again, got)
        finally:
            await eng.close()

    @async_test
    async def test_unaligned_grid_scans_raw(self):
        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng, hours=1)
            await compact_drain(eng)
            # anchor not a multiple of any resolution -> raw, still exact
            req = QueryRequest(metric=b"cpu", start_ms=17_000,
                               end_ms=HOUR, bucket_ms=MIN)
            with scanstats.scan_stats() as st:
                got = await eng.query(req)
            assert not st.counts.get("rollup_segments")
            assert st.counts.get("raw_segments", 0) >= 1
            assert_same_answer(got, await forced_cold(eng, req))
        finally:
            await eng.close()

    @async_test
    async def test_fresh_flush_forces_raw_until_recompaction(self):
        """A flush into a compacted segment breaks the source-set match:
        the planner must scan raw (no stale rollup), then substitute
        again after the next compaction folds the new SST in."""
        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng, hours=1)
            await compact_drain(eng)
            req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=HOUR,
                               bucket_ms=HOUR)
            with scanstats.scan_stats() as st:
                await eng.query(req)
            assert st.counts.get("rollup_segments") == 1

            await eng.write_payload(make_remote_write(
                [({"__name__": "cpu", "host": "a"}, [(5 * MIN + 3, 4444.0)])]
            ))
            await eng.flush()
            with scanstats.scan_stats() as st2:
                got = await eng.query(req)
            assert not st2.counts.get("rollup_segments")
            assert st2.counts.get("raw_segments", 0) >= 1
            cold = await forced_cold(eng, req)
            assert_same_answer(got, cold)
            # the new row is actually in the answer (not a stale rollup)
            assert float(np.asarray(got[1]["max"]).max()) == 4444.0

            await compact_drain(eng)
            with scanstats.scan_stats() as st3:
                again = await eng.query(req)
            assert st3.counts.get("rollup_segments") == 1
            assert_same_answer(again, await forced_cold(eng, req))
        finally:
            await eng.close()

    @async_test
    async def test_newer_tombstone_forces_raw_until_recompaction(self):
        """A delete AFTER the rollup build: the record's tombstone set no
        longer covers the live overlapping tombstones, so the planner
        scans raw (masked, exact). The next compaction re-emits with the
        delete applied and substitution resumes."""
        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng, hours=1)
            await compact_drain(eng)
            await eng.delete_series(b"cpu", filters=[(b"host", b"a")],
                                    start_ms=0, end_ms=30 * MIN)
            req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=HOUR,
                               bucket_ms=HOUR)
            with scanstats.scan_stats() as st:
                got = await eng.query(req)
            assert not st.counts.get("rollup_segments")
            assert_same_answer(got, await forced_cold(eng, req))

            # re-compaction applies the tombstone physically and re-emits:
            # substitution resumes, deleted rows stay deleted
            await eng.write_payload(make_remote_write(
                [({"__name__": "cpu", "host": "b"}, [(45 * MIN + 1, 5.0)])]
            ))
            await eng.flush()
            await compact_drain(eng)
            with scanstats.scan_stats() as st2:
                again = await eng.query(req)
            assert st2.counts.get("rollup_segments") == 1
            assert_same_answer(again, await forced_cold(eng, req))
        finally:
            await eng.close()

    @async_test
    async def test_unreadable_artifact_degrades_to_raw(self):
        """A rollup object lost from the store (or torn) costs speed,
        never correctness: the segment raw-scans, same answer."""
        store = MemStore()
        eng = await open_serving_engine(store)
        try:
            await seed_two_sst_segments(eng, hours=1)
            await compact_drain(eng)
            for rec in eng.data_table.manifest.rollup_records().values():
                path = eng.data_table.sst_path_gen.generate_rollup(rec.sst_id)
                await store.delete(path)
                # jaxlint: disable=J013 test clears the decoded cache
                rollup_mod.evict_rollup(rec.sst_id)
            req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=HOUR,
                               bucket_ms=HOUR)
            with scanstats.scan_stats() as st:
                got = await eng.query(req)
            assert not st.counts.get("rollup_segments")
            assert st.counts.get("raw_segments", 0) >= 1
            assert_same_answer(got, await forced_cold(eng, req))
        finally:
            await eng.close()


class TestResultCacheUnit:
    def test_lru_byte_bound_and_eviction(self):
        from horaedb_tpu.serving import CACHE_EVICTIONS

        c = ResultCache(1000)
        ev0 = CACHE_EVICTIONS.value
        for i in range(8):
            c.serving_put(bytes([i]), f"v{i}", 200, "t", {})
        assert c.resident_bytes <= 1000
        assert CACHE_EVICTIONS.value > ev0
        # oldest evicted, newest resident
        assert c.serving_get(bytes([0])) is None
        assert c.serving_get(bytes([7]))[0] == "v7"

    def test_oversized_entry_rejected(self):
        c = ResultCache(1000)
        c.serving_put(b"big", "v", 600, "t", {})  # > cap/4
        assert c.serving_get(b"big") is None
        assert c.resident_bytes == 0

    def test_invalidate_drops_only_the_root(self):
        c = ResultCache(10_000)
        c.serving_put(b"k1", "a", 10, "t1", {})
        c.serving_put(b"k2", "b", 10, "t1", {})
        c.serving_put(b"k3", "c", 10, "t2", {})
        assert c.serving_invalidate("t1", "flush") == 2
        assert c.serving_get(b"k1") is None
        assert c.serving_get(b"k2") is None
        assert c.serving_get(b"k3")[0] == "c"
        assert c.resident_bytes == 10

    def test_cached_arrays_are_frozen(self):
        c = ResultCache(10_000)
        arr = np.arange(4.0)
        c.serving_put(b"k", {"sum": arr}, arr.nbytes, "t", {})
        got, _notes = c.serving_get(b"k")
        with pytest.raises(ValueError):
            got["sum"][0] = 99.0

    def test_single_flight_collapses_concurrent_fills(self):
        async def run():
            c = ResultCache(10_000)
            fills = 0

            async def fill():
                nonlocal fills
                fills += 1
                await asyncio.sleep(0.02)
                return "value", 10, {"note": 1}

            results = await asyncio.gather(*(
                c.serving_single_flight(b"k", "t", fill) for _ in range(8)
            ))
            assert fills == 1
            assert all(v == "value" for v, _n, _l in results)
            leaders = [leader for _v, _n, leader in results]
            assert sum(leaders) == 1
            # followers replay the leader's notes
            assert all(n == {"note": 1} for _v, n, _l in results)

        asyncio.run(run())

    def test_single_flight_leader_failure_never_poisons_followers(self):
        async def run():
            c = ResultCache(10_000)
            calls = 0

            async def fill():
                nonlocal calls
                calls += 1
                if calls == 1:
                    await asyncio.sleep(0.01)
                    raise RuntimeError("leader died")
                return "ok", 5, {}

            tasks = [
                asyncio.create_task(c.serving_single_flight(b"k", "t", fill))
                for _ in range(3)
            ]
            done = await asyncio.gather(*tasks, return_exceptions=True)
            oks = [r for r in done if not isinstance(r, BaseException)]
            errs = [r for r in done if isinstance(r, BaseException)]
            assert len(errs) == 1  # the leader's own failure surfaces
            assert all(v == "ok" for v, _n, _l in oks)

        asyncio.run(run())


class TestBlockCacheUnderServing:
    @async_test
    async def test_repeat_scans_serve_cached_blocks_exactly(self):
        """Integration: with the result cache off every query really
        scans. The first answer is the cold one; the third identical
        query finds its decoded blocks in the reader's block cache and
        answers bit-exactly, against the first and against the
        forced-cold oracle."""
        from horaedb_tpu.common.size_ext import ReadableSize

        eng = await open_serving_engine(
            MemStore(),
            serving=ServingTierConfig(result_cache=ReadableSize.mb(0)),
        )
        try:
            await seed_two_sst_segments(eng, hours=1)
            await compact_drain(eng)
            req = QueryRequest(metric=b"cpu", start_ms=0, end_ms=HOUR)
            reader = eng.data_table.parquet_reader
            assert not reader._blk_cache
            first = await eng.query(req)
            cached = dict(reader._blk_cache)
            assert cached, "the cold scan cached no block"
            await eng.query(req)
            third = await eng.query(req)
            # the repeat scans decoded nothing anew: the same table
            # objects are still the cache's entries
            assert set(reader._blk_cache) == set(cached)
            assert all(reader._blk_cache[k] is t for k, t in cached.items())
            assert_same_answer(third, first)
            assert_same_answer(third, await forced_cold(eng, req))
        finally:
            await eng.close()


class TestServingConfigKeys:
    @pytest.mark.parametrize(
        "key,value", [("residency", "64MiB"), ("residency_admit_after", 2)],
    )
    def test_unknown_keys_refused_by_name(self, key, value):
        """A config file carrying a key the tier does not have is refused
        at boot, and the error names the key."""
        from horaedb_tpu.common.error import HoraeError

        with pytest.raises(HoraeError, match=re.escape(repr([key]))):
            ServingTierConfig.from_dict({"enabled": True, key: value})


class TestServingKeyContract:
    @async_test
    async def test_retention_floor_in_range_is_uncacheable(self):
        """The retention floor moves with the clock: a range it cuts into
        can never be cached (the masked row set is time-dependent)."""
        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng, hours=1)
            mgr = eng.sample_mgr
            rng = TimeRange(0, HOUR)
            assert mgr._serving_key(b"raw", 1, None, rng, None, None,
                                    False) is not None
            orig = eng.data_table.retention_floor
            eng.data_table.retention_floor = lambda: 30 * MIN
            try:
                assert mgr._serving_key(b"raw", 1, None, rng, None, None,
                                        False) is None
                # floor at/below the range start stays cacheable
                assert mgr._serving_key(
                    b"raw", 1, None, TimeRange(30 * MIN, HOUR), None, None,
                    False,
                ) is not None
            finally:
                eng.data_table.retention_floor = orig
        finally:
            await eng.close()

    @async_test
    async def test_key_distinguishes_every_plan_dimension(self):
        eng = await open_serving_engine(MemStore())
        try:
            await seed_two_sst_segments(eng, hours=1)
            mgr = eng.sample_mgr
            rng = TimeRange(0, HOUR)
            base = mgr._serving_key(b"ds", 1, (1, 2), rng, MIN, None, True)
            variants = [
                mgr._serving_key(b"raw", 1, (1, 2), rng, MIN, None, True),
                mgr._serving_key(b"ds", 2, (1, 2), rng, MIN, None, True),
                mgr._serving_key(b"ds", 1, (1, 3), rng, MIN, None, True),
                mgr._serving_key(b"ds", 1, (1, 2), TimeRange(0, 2 * HOUR),
                                 MIN, None, True),
                mgr._serving_key(b"ds", 1, (1, 2), rng, HOUR, None, True),
                mgr._serving_key(b"ds", 1, (1, 2), rng, MIN, 5, True),
                mgr._serving_key(b"ds", 1, (1, 2), rng, MIN, None, False),
            ]
            assert all(v is not None and v != base for v in variants)
            assert len({base, *variants}) == len(variants) + 1
        finally:
            await eng.close()
