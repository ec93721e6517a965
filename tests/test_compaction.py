"""Compaction tests (reference: picker.rs:201-236 + executor semantics)."""

import asyncio

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common.time_ext import ReadableDuration, now_ms
from horaedb_tpu.objstore import MemStore
from horaedb_tpu.storage import (
    ObjectBasedStorage,
    ScanRequest,
    SchedulerConfig,
    StorageConfig,
    TimeRange,
    WriteRequest,
)
from horaedb_tpu.storage.compaction.picker import TimeWindowCompactionStrategy
from horaedb_tpu.storage.sst import FileMeta, SstFile
from tests.conftest import async_test
from tests.test_storage import SEGMENT_MS, collect, make_batch, make_schema

HOUR = 3_600_000


def sst(i, start, size=100, rows=10):
    return SstFile(
        id=i,
        meta=FileMeta(max_sequence=i, num_rows=rows, size=size,
                      time_range=TimeRange(start, start + 10)),
    )


class TestPicker:
    def make_picker(self, min_num=2, max_num=30, max_size=1 << 30):
        return TimeWindowCompactionStrategy(
            segment_duration_ms=HOUR,
            new_sst_max_size=max_size,
            input_sst_max_num=max_num,
            input_sst_min_num=min_num,
        )

    def test_picks_newest_segment_first(self):
        p = self.make_picker()
        files = [sst(1, 0), sst(2, 10), sst(3, HOUR), sst(4, HOUR + 10)]
        task = p.pick_candidate(files, None)
        assert sorted(f.id for f in task.inputs) == [3, 4]
        assert all(f.is_compaction() for f in task.inputs)

    def test_min_num_not_met(self):
        p = self.make_picker(min_num=5)
        files = [sst(i, 0) for i in range(4)]
        assert p.pick_candidate(files, None) is None

    def test_in_compaction_files_excluded(self):
        p = self.make_picker()
        files = [sst(1, 0), sst(2, 0), sst(3, 0)]
        files[0].mark_compaction()
        task = p.pick_candidate(files, None)
        assert sorted(f.id for f in task.inputs) == [2, 3]

    def test_smallest_files_first_and_size_budget(self):
        p = self.make_picker(min_num=2, max_size=100)
        # budget = 110; sizes 10,20,90 -> picks 10,20 (90 would exceed)
        files = [sst(1, 0, size=90), sst(2, 0, size=10), sst(3, 0, size=20)]
        task = p.pick_candidate(files, None)
        assert sorted(f.id for f in task.inputs) == [2, 3]

    def test_max_num_cap(self):
        p = self.make_picker(min_num=2, max_num=3)
        files = [sst(i, 0, size=1) for i in range(10)]
        task = p.pick_candidate(files, None)
        assert len(task.inputs) == 3

    def test_ttl_expired_ride_along(self):
        p = self.make_picker()
        old = [sst(1, 0), sst(2, 0)]
        fresh = [sst(3, HOUR * 10), sst(4, HOUR * 10)]
        task = p.pick_candidate(old + fresh, expire_before_ms=HOUR)
        assert sorted(f.id for f in task.expireds) == [1, 2]
        assert sorted(f.id for f in task.inputs) == [3, 4]

    def test_expired_only_never_forms_task(self):
        """Reference quirk preserved (picker.rs:92-95)."""
        p = self.make_picker()
        old = [sst(1, 0), sst(2, 0)]
        assert p.pick_candidate(old, expire_before_ms=HOUR * 100) is None


class TestExecutor:
    @async_test
    async def test_end_to_end_compaction(self):
        store = MemStore()
        cfg = StorageConfig(
            scheduler=SchedulerConfig(
                schedule_interval=ReadableDuration.millis(50),
                input_sst_min_num=2,
            )
        )
        eng = await ObjectBasedStorage.try_new(
            "db", store, make_schema(), 2, SEGMENT_MS,
            config=cfg, start_background_merger=False,
        )
        schema = make_schema()
        for i in range(4):
            await eng.write(
                WriteRequest(
                    make_batch(schema, [1, 2 + i], [0, 0], [10, 20], [float(i), 100.0 + i]),
                    TimeRange(10, 21),
                )
            )
        assert len(eng.manifest.all_ssts()) == 4
        sched = eng.compaction_scheduler
        # the 50ms background picker may legitimately win the race and mark
        # the files first — don't assert this manual pick succeeded, just
        # that SOME pick leads to convergence
        sched.pick_once()
        # generous deadline: the task must travel pick -> queue -> recv loop
        # -> executor before the manifest shrinks (drain() alone can race a
        # task still sitting in the queue)
        for _ in range(750):
            await asyncio.sleep(0.02)
            if len(eng.manifest.all_ssts()) == 1:
                break
        await sched.executor.drain()
        ssts = eng.manifest.all_ssts()
        assert len(ssts) == 1
        # merged SST: dedup kept newest value for pk (1,0)
        t = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        row0 = t.filter(pa.compute.equal(t.column("pk1"), 1))
        assert row0.column("value").to_pylist() == [3.0]
        assert t.num_rows == 5  # pks: (1,0),(2,0),(3,0),(4,0),(5,0)
        # old files physically deleted, only the new SST remains
        data_objs = await store.list("db/data")
        assert len(data_objs) == 1
        await eng.close()

    @async_test
    async def test_ttl_expiry_end_to_end(self):
        """Expired SSTs ride along a qualifying pick and get deleted from
        both manifest and store (picker TTL + executor delete ordering)."""
        from horaedb_tpu.common.time_ext import now_ms

        store = MemStore()
        cfg = StorageConfig(
            scheduler=SchedulerConfig(
                input_sst_min_num=2,
                ttl=ReadableDuration.hours(1),
            )
        )
        eng = await ObjectBasedStorage.try_new(
            "db", store, make_schema(), 2, SEGMENT_MS,
            config=cfg, start_background_merger=False,
        )
        schema = make_schema()
        # ancient data (epoch ~0): far beyond the 1h TTL
        await eng.write(
            WriteRequest(make_batch(schema, [1], [0], [10], [1.0]), TimeRange(10, 11))
        )
        # fresh segment with enough files to qualify a pick
        t = now_ms()
        seg_start = t - t % SEGMENT_MS
        for i in range(2):
            await eng.write(
                WriteRequest(
                    make_batch(schema, [i], [0], [t], [float(i)]),
                    TimeRange(seg_start, seg_start + 1),
                )
            )
        assert len(eng.manifest.all_ssts()) == 3
        sched = eng.compaction_scheduler
        assert sched.pick_once()
        for _ in range(200):
            await asyncio.sleep(0.02)
            if len(eng.manifest.all_ssts()) == 1:
                break
        await sched.executor.drain()
        ssts = eng.manifest.all_ssts()
        assert len(ssts) == 1  # 2 fresh merged into 1; expired dropped
        t2 = await collect(eng, ScanRequest(range=TimeRange(0, 2**60)))
        assert 10 not in t2.column("ts").to_pylist()  # ancient row gone
        assert t2.num_rows == 2  # both fresh rows survive
        assert len(await store.list("db/data")) == 1
        await eng.close()

    @async_test
    async def test_memory_gate_rejects_oversize_task(self):
        from horaedb_tpu.storage.compaction import Task
        from horaedb_tpu.storage.compaction.executor import Executor
        from horaedb_tpu.common.error import HoraeError

        ex = Executor(storage=None, manifest=None, mem_limit=100, trigger=asyncio.Queue(1))
        big = [sst(1, 0, size=80), sst(2, 0, size=80)]
        for f in big:
            f.mark_compaction()
        task = Task(inputs=big)
        with pytest.raises(HoraeError, match="memory usage too high"):
            ex.pre_check(task)
        # a rejected task never charged the budget; on_failure must not
        # refund it into the negative (that would defeat the gate)
        ex.on_failure(task)
        assert ex._inused_memory == 0

    @async_test
    async def test_failure_unmarks_ssts(self):
        from horaedb_tpu.storage.compaction import Task
        from horaedb_tpu.storage.compaction.executor import Executor

        ex = Executor(storage=None, manifest=None, mem_limit=10_000, trigger=asyncio.Queue(1))
        files = [sst(1, 0), sst(2, 0)]
        for f in files:
            f.mark_compaction()
        task = Task(inputs=files)
        ex.pre_check(task)
        ex.on_failure(task)
        assert ex._inused_memory == 0
        assert not any(f.is_compaction() for f in files)


class TestShardedOutput:
    @async_test
    async def test_large_output_shards_and_scans_identically(self):
        """Outputs above output_shard_rows split into pk-contiguous shard
        SSTs (concurrent encodes); scans return the same rows, and the
        shard count stays below input_sst_min_num so a fully-compacted
        segment never re-picks its own output."""
        store = MemStore()
        cfg = StorageConfig(
            scheduler=SchedulerConfig(
                schedule_interval=ReadableDuration.secs(3600),
                input_sst_min_num=3,
                output_shard_rows=100,  # tiny: force sharding
            )
        )
        eng = await ObjectBasedStorage.try_new(
            "db", store, make_schema(), 2, SEGMENT_MS,
            config=cfg, start_background_merger=False,
            enable_compaction_scheduler=True,
        )
        schema = make_schema()
        rng = np.random.default_rng(7)
        for i in range(4):
            pk1 = np.sort(rng.integers(0, 500, 200))
            await eng.write(
                WriteRequest(
                    pa.RecordBatch.from_pydict(
                        {
                            "pk1": pk1,
                            "pk2": np.zeros(200, dtype=np.int64),
                            "ts": np.full(200, 10, dtype=np.int64),
                            "value": rng.normal(size=200),
                        },
                        schema=schema,
                    ),
                    TimeRange(10, 11),
                )
            )
        before = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        sched = eng.compaction_scheduler
        sched.pick_once()
        for _ in range(500):
            await asyncio.sleep(0.02)
            if len(eng.manifest.all_ssts()) < 4:
                break
        await sched.executor.drain()
        ssts = eng.manifest.all_ssts()
        # sharded: more than one output, but under the re-pick threshold
        assert 1 < len(ssts) < cfg.scheduler.input_sst_min_num
        # each shard is pk-disjoint from the next (contiguous slices of the
        # sorted merged output): last pk of shard i < first pk of shard i+1
        ordered = sorted(ssts, key=lambda s: s.id)
        bounds = []
        for s in ordered:
            t = await eng.parquet_reader.read_sst(s, ["pk1", "pk2"], None)
            pks = list(zip(t.column("pk1").to_pylist(), t.column("pk2").to_pylist()))
            assert pks == sorted(pks)
            bounds.append((pks[0], pks[-1]))
        for (_, last), (first, _) in zip(bounds, bounds[1:]):
            assert last < first
        total_rows = sum(s.meta.num_rows for s in ssts)
        after = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert after.equals(before)
        assert total_rows == after.num_rows
        # re-pick must find nothing (shard count below min)
        picks = TimeWindowCompactionStrategy(
            segment_duration_ms=SEGMENT_MS,
            new_sst_max_size=cfg.scheduler.new_sst_max_size.as_bytes(),
            input_sst_max_num=cfg.scheduler.input_sst_max_num,
            input_sst_min_num=cfg.scheduler.input_sst_min_num,
        ).pick_candidate(ssts, expire_before_ms=None)
        assert picks is None or not picks.inputs
        await eng.close()


class TestScopedCompaction:
    @async_test
    async def test_time_range_scope_limits_pick(self):
        """CompactRequest.time_range compacts only the overlapping segment;
        other segments' SSTs stay untouched (beyond the reference's empty
        CompactRequest)."""
        from horaedb_tpu.storage.read import CompactRequest

        store = MemStore()
        cfg = StorageConfig(
            scheduler=SchedulerConfig(
                schedule_interval=ReadableDuration.secs(3600),  # tick never fires
                input_sst_min_num=2,
            )
        )
        eng = await ObjectBasedStorage.try_new(
            "db", store, make_schema(), 2, SEGMENT_MS,
            config=cfg, start_background_merger=False,
        )
        schema = make_schema()
        # 3 SSTs in segment 0, 3 in segment 1
        for seg in range(2):
            base = seg * SEGMENT_MS
            for i in range(3):
                await eng.write(
                    WriteRequest(
                        make_batch(schema, [1, 2 + i], [0, 0],
                                   [base + 10, base + 20], [1.0, 2.0]),
                        TimeRange(base + 10, base + 21),
                    )
                )
        assert len(eng.manifest.all_ssts()) == 6
        await eng.compact(CompactRequest(time_range=TimeRange(0, SEGMENT_MS)))
        for _ in range(500):
            await asyncio.sleep(0.02)
            if len(eng.manifest.all_ssts()) <= 4:
                break
        await eng.compaction_scheduler.executor.drain()
        ssts = eng.manifest.all_ssts()
        seg0 = [s for s in ssts if s.meta.time_range.start < SEGMENT_MS]
        seg1 = [s for s in ssts if s.meta.time_range.start >= SEGMENT_MS]
        assert len(seg0) == 1      # scoped segment compacted
        assert len(seg1) == 3      # out-of-scope segment untouched
        await eng.close()


class TestDrain:
    @async_test
    async def test_drain_entered_before_a_finished_tasks_callback_returns(self):
        """A task leaves `_inflight` in its done callback, one loop turn
        after its last step. A drain that starts in between (the test
        helpers' poll does, when a timer fires in that same turn) used to
        await the finished task without ever yielding, so the callback
        never ran: the watchdog's hang in tests/test_serving.py."""
        from horaedb_tpu.storage.compaction.executor import Executor

        ex = Executor(None, None, 1 << 30, asyncio.Queue())

        async def nothing():
            return None

        task = asyncio.create_task(nothing())
        ex._inflight.add(task)
        task.add_done_callback(ex._inflight.discard)
        # both wake-ups sit in one turn of the loop: the task's only step,
        # then this coroutine, before any done callback
        await asyncio.sleep(0)
        assert task.done() and task in ex._inflight
        await ex.drain()
        assert not ex._inflight
