"""The one stage funnel (storage/scanstats.py) and what PR 27 hung on it:
every stage of every family goes to the family's histogram, the active
span's `stages`, the per-query collector and a profiler annotation named
`<family>.<stage>`; the write path and the compaction run through it;
kernels carry their label; every XLA compile is counted; the loop's lag
is measured; the scan-path counter counts routes."""

import asyncio
import logging
import threading
import time

import numpy as np
import pytest

from horaedb_tpu.common import tracing, xprof
from horaedb_tpu.common.xprof import xjit
from horaedb_tpu.objstore import MemStore
from horaedb_tpu.ops import filter as F
from horaedb_tpu.server.metrics import GLOBAL_METRICS
from horaedb_tpu.storage import (
    ObjectBasedStorage,
    ScanRequest,
    SchedulerConfig,
    StorageConfig,
    TimeRange,
    WriteRequest,
    scanstats,
)
from tests.conftest import async_test
from tests.test_storage import SEGMENT_MS, collect, make_batch, make_schema

# the trace reduction's pattern for host events that are waits: a stage
# that is work must not match it, a stage that is a wait must
from bench_chip.trace.reduce import WAITS  # noqa: E402


def hist(family: str, **labels) -> tuple[int, float]:
    """(count, sum) of one histogram child, 0 where it was never made."""
    count = total = 0.0
    want = tuple(sorted(labels.items()))
    for name, _kind, sample, key, value in GLOBAL_METRICS.snapshot_samples():
        if name != family or tuple(sorted(k for k in key if k[0] != "le")) != want:
            continue
        if sample == family + "_count":
            count = value
        elif sample == family + "_sum":
            total = value
    return int(count), total


def counter(family: str, **labels) -> float:
    want = tuple(sorted(labels.items()))
    for name, _kind, _sample, key, value in GLOBAL_METRICS.snapshot_samples():
        if name == family and tuple(sorted(key)) == want:
            return value
    return 0.0


@pytest.fixture
def annotations(monkeypatch):
    """Names of the profiler annotations opened, the class patched where
    the funnel and xjit look it up."""
    names: list[str] = []

    class Recording:
        def __init__(self, name, **_kw):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(scanstats, "TraceAnnotation", Recording)
    monkeypatch.setattr(xprof, "TraceAnnotation", Recording)
    return names


class TestFunnel:
    @pytest.mark.parametrize("family,stage,histogram,labels", [
        (scanstats.SCAN, "host_prep", "horaedb_scan_stage_seconds", {"stage": "host_prep"}),
        # scan-internal names fold into the operator's lanes on /metrics
        (scanstats.SCAN, "device_merge", "horaedb_scan_stage_seconds", {"stage": "kernel"}),
        (scanstats.flush_family("t/funnel"), "encode", "horaedb_flush_stage_seconds",
         {"table": "t/funnel", "stage": "encode"}),
        (scanstats.COMPACTION, "commit", "horaedb_compaction_stage_seconds", {"stage": "commit"}),
        (scanstats.COMPACTION_SST, "encode", "horaedb_compaction_stage_seconds",
         {"stage": "sst_encode"}),
    ])
    def test_four_sinks(self, annotations, family, stage, histogram, labels):
        n0, s0 = hist(histogram, **labels)
        tracing.configure(sample=1.0)
        with tracing.trace("funnel-test") as t, scanstats.scan_stats() as st:
            with family.stage(stage) as cell:
                time.sleep(0.01)
        n1, s1 = hist(histogram, **labels)
        name = labels["stage"] if family is not scanstats.SCAN else stage
        assert (n1 - n0, round(s1 - s0, 9)) == (1, round(cell.seconds, 9))
        assert cell.seconds >= 0.01
        assert st.seconds[name] == cell.seconds
        assert t.spans[0].attrs["stages"][name] == pytest.approx(cell.seconds, abs=1e-6)
        assert annotations == [f"{family.name}.{name}"]
        assert not WAITS.search(annotations[0])

    def test_mark_is_the_annotation_alone(self, annotations):
        before = hist("horaedb_compaction_stage_seconds", stage="sst_encode")
        with scanstats.COMPACTION_SST.mark("encode"):
            pass
        assert annotations == ["compaction.sst_encode"]
        assert hist("horaedb_compaction_stage_seconds", stage="sst_encode") == before

    def test_a_worker_thread_is_named_for_the_profiler(self):
        """Every Python thread is born `python3` and the profiler names a
        line after its thread: a worker takes its Python name before its
        first annotation, the main thread (the process) keeps its own."""
        import os
        import threading

        if not os.path.isdir("/proc/self/task"):
            pytest.skip("thread names are set through prctl, on Linux")

        def comm() -> str:
            with open(f"/proc/self/task/{threading.get_native_id()}/comm") as f:
                return f.read().strip()

        names = {}
        main_before = comm()

        def body(key):
            names[key] = comm()

        def worker():
            scanstats.SCAN.on_worker("io_decode", body, "marked")
            with scanstats.COMPACTION.stage("commit"):
                body("staged")
            scanstats.name_thread("horaedb-loop")  # as the server names its loop's
            body("given")

        t = threading.Thread(target=worker, name="funnel-worker-thread-7")
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        assert names == {"marked": "worker-thread-7", "staged": "worker-thread-7",
                         "given": "horaedb-loop"}
        with scanstats.SCAN.stage("host_prep"):
            assert comm() == main_before

    @async_test
    async def test_a_wait_is_named_a_wait(self, annotations):
        """The parser-pool wait matches the reduction's WAITS pattern, the
        parse does not: an idle gap is never named after a wait."""
        from horaedb_tpu.ingest.pooled_parser import STAGES, ParserPool
        from tests.test_engine import make_remote_write

        n0, _ = hist("horaedb_ingest_parse_seconds")
        w0, _ = hist("horaedb_ingest_pool_wait_seconds")
        payload = make_remote_write([({"__name__": "funnel_m"}, [(1000, 1.0)])])
        parsed = await ParserPool().decode(payload)
        assert parsed.n_samples == 1
        assert STAGES.name == "ingest"
        assert hist("horaedb_ingest_parse_seconds")[0] == n0 + 1
        assert hist("horaedb_ingest_pool_wait_seconds")[0] == w0 + 1
        # the worker's own mark carries the stage's name (it may run on
        # a thread of its own, so the list's order is not pinned)
        assert sorted(annotations) == ["ingest.parse", "ingest.parse", "ingest.pool_wait"]
        assert WAITS.search("ingest.pool_wait") and not WAITS.search("ingest.parse")


def big_batch(schema, rows: int, seed: int):
    rng = np.random.default_rng(seed)
    return make_batch(schema, rng.integers(0, 1 << 40, rows), rng.integers(0, 8, rows),
                      rng.integers(10, 1000, rows), rng.random(rows))


FLUSH = "horaedb_flush_stage_seconds"


class TestWritePath:
    @pytest.mark.parametrize("rows,stages,least", [
        # one encode on a worker, one put
        (150_000, ("sort", "encode", "upload", "sidecar", "manifest"), 0.9),
        # past 16 MiB the encode streams into the put and rides in `upload`;
        # starting the producer and joining it lie between the stages
        (400_000, ("sort", "upload", "sidecar", "manifest"), 0.75),
    ])
    @async_test
    async def test_unbuffered_write_leaves_its_stages(self, rows, stages, least):
        """Every write, the shipped unbuffered path included, observes
        sort / encode / upload / manifest, and together they are the
        write: within a tenth of horaedb_storage_write_seconds."""
        root = f"funnel/write{rows}"
        eng = await ObjectBasedStorage.try_new(
            root, MemStore(), make_schema(), 2, SEGMENT_MS,
            enable_compaction_scheduler=False, start_background_merger=False,
        )
        tracing.configure(sample=1.0)
        shares = []
        for seed in range(3):
            before = {s: hist(FLUSH, table=root, stage=s) for s in stages}
            w0 = hist("horaedb_storage_write_seconds", table=root)
            with tracing.trace("write-test") as t:
                await eng.write(WriteRequest(big_batch(make_schema(), rows, seed),
                                             TimeRange(10, 1000)))
            total = 0.0
            for s in stages:
                n, secs = hist(FLUSH, table=root, stage=s)
                assert n - before[s][0] == 1, s
                total += secs - before[s][1]
            n, secs = hist("horaedb_storage_write_seconds", table=root)
            assert n - w0[0] == 1 and total <= secs - w0[1]
            shares.append(total / (secs - w0[1]))
            # the stages ride on the storage_write span
            span = next(s for s in t.spans if s.name == "storage_write")
            assert set(stages) <= set(span.attrs["stages"])
        # what lies between the stages is the loop's own scheduling, which a
        # loaded test machine stretches: the best of three writes is the claim
        assert max(shares) >= least, shares
        await eng.close()

    @async_test
    async def test_a_compactions_write_is_not_a_flush(self, caplog):
        """A compaction's write_sst goes to the compaction family
        (`sst_*`), nothing of it to the flush family; the rows it took in
        are counted, and the finished task leaves one INFO line."""
        root = "funnel/compact"
        cfg = StorageConfig(scheduler=SchedulerConfig(input_sst_min_num=2))
        eng = await ObjectBasedStorage.try_new(
            root, MemStore(), make_schema(), 2, SEGMENT_MS,
            config=cfg, start_background_merger=False,
        )
        schema = make_schema()
        for i in range(4):
            await eng.write(WriteRequest(
                make_batch(schema, [1, 2 + i], [0, 0], [10, 20], [float(i), 100.0 + i]),
                TimeRange(10, 21)))
        inputs = eng.manifest.all_ssts()
        rows_in = sum(f.meta.num_rows for f in inputs)
        assert rows_in == 8
        flush0 = {s: hist(FLUSH, table=root, stage=s)[0]
                  for s in ("sort", "encode", "upload", "sidecar", "manifest")}
        comp0 = {s: hist("horaedb_compaction_stage_seconds", stage=s)[0]
                 for s in ("scan", "encode", "commit", "cleanup", "sst_encode", "sst_upload")}
        in0 = counter("horaedb_compaction_rows_total", dir="in")
        out0 = counter("horaedb_compaction_rows_total", dir="out")
        tracing.configure(sample=1.0)
        with caplog.at_level(logging.INFO, logger="horaedb_tpu.storage.compaction.executor"):
            eng.compaction_scheduler.pick_once()
            for _ in range(750):
                await asyncio.sleep(0.02)
                if len(eng.manifest.all_ssts()) == 1:
                    break
            await eng.compaction_scheduler.executor.drain()
        assert len(eng.manifest.all_ssts()) == 1
        for s, n in flush0.items():
            assert hist(FLUSH, table=root, stage=s)[0] == n, s
        for s, n in comp0.items():
            assert hist("horaedb_compaction_stage_seconds", stage=s)[0] == n + 1, s
        assert counter("horaedb_compaction_rows_total", dir="in") - in0 == rows_in
        assert counter("horaedb_compaction_rows_total", dir="out") - out0 == 5
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("compaction done:")]
        assert len(lines) == 1, lines
        assert "inputs=4" in lines[0] and "rows_in=8 rows_out=5" in lines[0]
        for s in ("scan", "encode", "commit", "cleanup"):
            assert f"'{s}':" in lines[0]
        root_span = next(
            tr for tr in tracing.recent(50) if tr["name"] == "compaction")
        tree = tracing.get(root_span["trace_id"])
        attrs = tree["root"]["attrs"]
        assert (attrs["rows_in"], attrs["rows_out"]) == (8, 5) and attrs["bytes_out"] > 0
        assert {"scan", "encode", "commit", "cleanup"} <= set(attrs["stages"])
        await eng.close()

    @async_test
    async def test_scan_path_counts_routes_not_folds(self):
        """horaedb_scan_path_total: one count a segment scan, named as
        EXPLAIN's scan_paths names the route."""
        eng = await ObjectBasedStorage.try_new(
            "funnel/route", MemStore(), make_schema(), 2, SEGMENT_MS,
            enable_compaction_scheduler=False, start_background_merger=False,
        )
        schema = make_schema()
        for i in range(2):
            await eng.write(WriteRequest(
                make_batch(schema, [1, 2 + i], [0, 0], [10, 20], [float(i), 1.0]),
                TimeRange(10, 21)))
        names = ("host_merge", "device_merge_packed", "device_merge", "device_merge_sharded")
        before = {n: counter("horaedb_scan_path_total", path=n) for n in names}
        with scanstats.scan_stats() as st:
            await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        routes = sorted(k[len("path_"):] for k in st.counts if k.startswith("path_"))
        assert len(routes) == 1 and routes[0] in names
        moved = {n: counter("horaedb_scan_path_total", path=n) - before[n] for n in names}
        assert moved == {n: (1.0 if n == routes[0] else 0.0) for n in names}
        await eng.close()


class TestMergeOffTheLoop:
    """A segment scan's merge (host_prep, the planner's merge with its wait
    on the device, materialize) is ONE call on a worker thread: the loop
    starts it and takes its batches."""

    SLOW_S = 0.3
    STAGES = ("host_prep", "materialize", scanstats.MERGE_WAIT)

    @staticmethod
    def scan_hists() -> dict:
        return {s: hist("horaedb_scan_stage_seconds", stage=s)
                for s in ("host_prep", "host_merge", "kernel", "materialize",
                          scanstats.MERGE_WAIT)}

    @staticmethod
    async def under_heartbeat(kind: str, work) -> tuple[float, object]:
        """Run `work()` beside a heartbeat; how late the heartbeat was: its
        worst wake-up (a 10 ms task of the test's own), or what the
        server's `loop_lag_heartbeat` added to horaedb_loop_lag_seconds."""
        from horaedb_tpu.server.main import loop_lag_heartbeat

        loop = asyncio.get_running_loop()
        worst = [0.0]

        async def own() -> None:
            while True:
                due = loop.time() + 0.01
                await asyncio.sleep(0.01)
                worst[0] = max(worst[0], loop.time() - due)

        beat = asyncio.create_task(own() if kind == "own" else loop_lag_heartbeat())
        await asyncio.sleep(0.05)  # the heartbeat is running
        _, s0 = hist("horaedb_loop_lag_seconds")
        worst[0] = 0.0
        try:
            out = await work()
            await asyncio.sleep(0.03)  # a wake-up held back would land here
        finally:
            beat.cancel()
        late = worst[0] if kind == "own" else hist("horaedb_loop_lag_seconds")[1] - s0
        return late, out

    @pytest.mark.parametrize("heartbeat", ["own", "server"])
    @async_test
    async def test_the_loop_stays_free_and_the_stages_arrive(self, monkeypatch, heartbeat):
        from horaedb_tpu.storage import read as read_mod

        real = read_mod._plan_and_merge
        ran_on: list[str] = []

        def slow(*args, **kwargs):
            # synchronous, as the wait on the device is
            ran_on.append(threading.current_thread().name)
            time.sleep(self.SLOW_S)  # jaxlint: disable=J018 the merge is slow on purpose
            return real(*args, **kwargs)

        monkeypatch.setattr(read_mod, "_plan_and_merge", slow)
        root = f"funnel/offloop-{heartbeat}"
        cfg = StorageConfig(scheduler=SchedulerConfig(input_sst_min_num=2))
        eng = await ObjectBasedStorage.try_new(
            root, MemStore(), make_schema(), 2, SEGMENT_MS,
            config=cfg, start_background_merger=False,
        )
        schema = make_schema()

        async def four_ssts():
            for i in range(4):
                await eng.write(WriteRequest(
                    make_batch(schema, [1, 2 + i], [0, 0], [10, 20], [float(i), 100.0 + i]),
                    TimeRange(10, 21)))

        async def raw_scan():
            with tracing.trace("offloop-scan") as t, scanstats.scan_stats() as st:
                table = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
            return t, st, table

        async def compaction():
            eng.compaction_scheduler.pick_once()
            for _ in range(750):
                await asyncio.sleep(0.02)
                if len(eng.manifest.all_ssts()) == 1:
                    break
            await eng.compaction_scheduler.executor.drain()

        async def free_loop(work, prepare=None):
            """`work` beside the heartbeat, until one run finds the loop
            free: a loaded test machine makes a heartbeat late now and then
            on its own, a merge on the loop makes it late by SLOW_S every
            time. What the last run left behind is what the caller checks."""
            for _ in range(4):
                if prepare is not None:
                    await prepare()
                ran_on.clear()
                before = self.scan_hists()
                late, out = await self.under_heartbeat(heartbeat, work)
                if late < 0.1:
                    break
            assert late < 0.1, f"the loop was held {late:.3f} s by a {self.SLOW_S} s merge"
            assert len(ran_on) == 1 and ran_on[0].startswith("asyncio_"), ran_on
            return before, self.scan_hists(), out

        await four_ssts()
        tracing.configure(sample=1.0)
        # -- the raw scan: the loop is free, all three sinks are fed ----------
        before, after, (t, st, table) = await free_loop(raw_scan)
        assert table.num_rows == 5
        merged = [s for s in ("host_merge", "kernel") if after[s][0] > before[s][0]]
        assert len(merged) == 1, (before, after)
        for s in self.STAGES:
            assert after[s][0] > before[s][0], s
        # the await counted once a merge, and holds the merge's stages
        assert after[scanstats.MERGE_WAIT][0] - before[scanstats.MERGE_WAIT][0] == 1
        assert st.counts[scanstats.MERGE_WAIT] == 1
        inner = {"host_merge": "host_merge", "kernel": "device_merge"}[merged[0]]
        assert {"io_decode", inner, *self.STAGES} <= set(st.seconds)
        assert st.seconds[scanstats.MERGE_WAIT] >= self.SLOW_S + st.seconds["materialize"]
        # ... and is in no sum of lanes twice
        lanes = st.attribution()["lanes_s"]
        assert sum(lanes.values()) == pytest.approx(
            sum(v for k, v in st.seconds.items() if k != scanstats.MERGE_WAIT), abs=1e-5)
        (span,) = [sp for sp in t.spans if sp.name == "scan_segment"]
        assert {"io_decode", inner, *self.STAGES} <= set(span.attrs["stages"])

        # -- the compaction: the same call, from the executor's task ---------
        # (a run that has to be made again finds the last one's output and
        # four new files to merge)
        before, after, _ = await free_loop(compaction, prepare=four_ssts)
        assert len(eng.manifest.all_ssts()) == 1
        assert after[scanstats.MERGE_WAIT][0] - before[scanstats.MERGE_WAIT][0] == 1
        for s in self.STAGES:
            assert after[s][0] > before[s][0], s
        root_span = next(tr for tr in tracing.recent(50) if tr["name"] == "compaction")
        tree = tracing.get(root_span["trace_id"])
        assert tree["root"]["attrs"]["stages"]["scan"] >= self.SLOW_S
        (seg,) = [c for c in tree["root"]["children"] if c["name"] == "scan_segment"]
        assert {"host_prep", "materialize", scanstats.MERGE_WAIT} <= set(seg["attrs"]["stages"])
        await eng.close()


    @async_test
    async def test_a_decode_hop_is_worth_a_batch_of_rows(self, monkeypatch):
        """Small SSTs share a thread hop up to a batch of rows between
        them, a larger one decodes on a thread of its own, and the merge is
        one hop more: three files of two rows and one of 9,192 are three
        hops, not five."""
        from horaedb_tpu.storage.read import DEFAULT_SCAN_BATCH_SIZE, ParquetReader

        eng = await ObjectBasedStorage.try_new(
            "funnel/hops", MemStore(), make_schema(), 2, SEGMENT_MS,
            enable_compaction_scheduler=False, start_background_merger=False,
        )
        schema = make_schema()
        for i in range(3):
            await eng.write(WriteRequest(
                make_batch(schema, [1, 2 + i], [0, 0], [10, 20], [float(i), 100.0 + i]),
                TimeRange(10, 21)))
        big = DEFAULT_SCAN_BATCH_SIZE + 1000
        await eng.write(WriteRequest(big_batch(schema, big, 5), TimeRange(10, 1000)))
        hops: list[str] = []
        real = asyncio.to_thread

        async def counting(fn, *args, **kwargs):
            hops.append(getattr(fn, "__qualname__", repr(fn)))
            return await real(fn, *args, **kwargs)

        monkeypatch.setattr(asyncio, "to_thread", counting)
        with scanstats.scan_stats() as st:
            table = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert table.num_rows >= big
        scan_hops = [h for h in hops if h.startswith(ParquetReader.__name__ + ".")]
        assert sorted(scan_hops) == [
            "ParquetReader._decode_segment.<locals>.decode_job",
            "ParquetReader._decode_segment.<locals>.decode_job",
            "ParquetReader._merge_segment",
        ], hops
        assert st.counts["ssts_read"] == 4 and st.counts["io_decode"] == 1
        await eng.close()


class TestFoldOffTheLoop:
    """A segment's aggregate pushdown (host_prep, the packed sort, the fold
    with its wait on the device) is ONE call on a worker thread: the loop
    opens the SSTs, awaits the reads, starts the fold and takes its grids."""

    SLOW_S = 0.3
    SERIES = 4

    @staticmethod
    async def engine(root: str, store=None):
        return await ObjectBasedStorage.try_new(
            root, store or MemStore(), make_schema(), 3, SEGMENT_MS,
            enable_compaction_scheduler=False, start_background_merger=False,
        )

    @classmethod
    async def write_ssts(cls, eng, files: int, rows: int) -> None:
        """`files` SSTs of `rows` rows each: series pk1 in [0, SERIES), pk2
        pinned (the packed route's contract), every (series, ts) once."""
        schema = make_schema()
        for i in range(files):
            n = np.arange(i * rows, (i + 1) * rows)
            await eng.write(WriteRequest(
                make_batch(schema, n % cls.SERIES, np.zeros(rows), 10 + n // cls.SERIES,
                           n.astype(np.float64)),
                TimeRange(10, 10 + files * rows)))

    @classmethod
    def pushdown(cls, eng, ssts, packed_ok: bool = True, predicate=None, **kw):
        return eng.parquet_reader.scan_segment_downsample(
            ssts, predicate, "ts", "value", "pk1", np.arange(cls.SERIES), 0, 2000, 8,
            packed_ok=packed_ok, **kw)

    @pytest.mark.parametrize("heartbeat", ["own", "server"])
    @async_test
    async def test_the_loop_stays_free_and_the_stages_arrive(self, monkeypatch, heartbeat):
        from horaedb_tpu.ops import aggregate as agg_ops

        real = agg_ops.fold_sorted
        ran_on: list[str] = []

        def slow(*args, **kwargs):
            # synchronous, as the wait on the device is
            ran_on.append(threading.current_thread().name)
            time.sleep(self.SLOW_S)  # jaxlint: disable=J018 the fold is slow on purpose
            return real(*args, **kwargs)

        monkeypatch.setattr(agg_ops, "fold_sorted", slow)
        # the lane the CPU's calibration picks, pinned: a micro-A/B taken on a
        # loaded machine now and then picks a program, whose first run compiles
        monkeypatch.setenv("HORAEDB_AGG_IMPL", "reduceat")
        eng = await self.engine(f"funnel/fold-offloop-{heartbeat}")
        await self.write_ssts(eng, files=4, rows=500)
        ssts = sorted(eng.manifest.all_ssts(), key=lambda f: f.id)
        names = ("io_decode", "host_prep", "pack_sort", "fold_prep", "fold_host",
                 "transfer", "kernel", scanstats.FOLD_WAIT)

        async def two_segments():
            # two calls at once under one span and one collector, as a
            # query's two segments are
            with tracing.trace("offloop-fold") as t, scanstats.scan_stats() as st:
                grids = await asyncio.gather(
                    self.pushdown(eng, ssts[:2]), self.pushdown(eng, ssts[2:]))
            return t, st, grids

        tracing.configure(sample=1.0)
        for _ in range(4):  # a loaded machine is late now and then on its own
            ran_on.clear()
            before = {s: hist("horaedb_scan_stage_seconds", stage=s) for s in names}
            late, (t, st, grids) = await TestMergeOffTheLoop.under_heartbeat(
                heartbeat, two_segments)
            if late < 0.1:
                break
        assert late < 0.1, f"the loop was held {late:.3f} s by a {self.SLOW_S} s fold"
        assert len(ran_on) == 2 and all(n.startswith("asyncio_") for n in ran_on), ran_on
        assert sum(g["count"].sum() for g in grids) == 2000
        after = {s: hist("horaedb_scan_stage_seconds", stage=s) for s in names}
        # the fold's own lanes: the host lane, or the program's four
        device = {"fold_prep", "fold_h2d", "fold_kernel", "fold_d2h"}
        ran = {"fold_prep", "fold_host"} if "fold_host" in st.seconds else device
        inner = {"host_prep", "pack_sort", *ran}
        assert {"io_decode", scanstats.FOLD_WAIT, *inner} == set(st.seconds)
        lane = {"fold_h2d": "transfer", "fold_d2h": "transfer", "fold_kernel": "kernel"}
        for s in inner | {"io_decode"}:  # histogram, collector, span: all three
            assert after[lane.get(s, s)][0] > before[lane.get(s, s)][0], s
            assert st.counts[s] == 2, s
        (root,) = [sp for sp in t.spans if sp.name == "offloop-fold"]
        assert set(root.attrs["stages"]) == inner | {"io_decode"}
        # the await: counted once a segment, holds the call's stages, and is
        # in no sum of lanes, the span's among them
        assert after[scanstats.FOLD_WAIT][0] - before[scanstats.FOLD_WAIT][0] == 2
        assert st.counts[scanstats.FOLD_WAIT] == 2
        assert st.seconds[scanstats.FOLD_WAIT] >= sum(st.seconds[s] for s in inner)
        assert st.seconds[scanstats.FOLD_WAIT] >= 2 * self.SLOW_S
        work = sum(v for k, v in st.seconds.items() if k != scanstats.FOLD_WAIT)
        assert sum(st.attribution()["lanes_s"].values()) == pytest.approx(work, abs=1e-5)
        assert sum(root.attrs["stages"].values()) == pytest.approx(work, abs=1e-4)
        assert WAITS.search("scan." + scanstats.FOLD_WAIT)
        assert not WAITS.search("scan.pack_sort")
        await eng.close()

    @async_test
    async def test_thirty_small_ssts_decode_in_hops_of_a_batch_of_rows(self, monkeypatch):
        """As a materialising scan's: thirty files of 1,100 rows share a
        hop up to a batch of rows between them (seven a hop: five hops),
        and the fold is one hop more."""
        from horaedb_tpu.storage.read import DEFAULT_SCAN_BATCH_SIZE, ParquetReader

        eng = await self.engine("funnel/fold-hops")
        await self.write_ssts(eng, files=30, rows=1100)
        ssts = sorted(eng.manifest.all_ssts(), key=lambda f: f.id)
        assert len(ssts) == 30 and 7 * 1100 <= DEFAULT_SCAN_BATCH_SIZE < 8 * 1100
        hops: list[str] = []
        real = asyncio.to_thread

        async def counting(fn, *args, **kwargs):
            hops.append(getattr(fn, "__qualname__", repr(fn)))
            return await real(fn, *args, **kwargs)

        monkeypatch.setattr(asyncio, "to_thread", counting)
        with scanstats.scan_stats() as st:
            grids = await self.pushdown(eng, ssts)
        assert grids["count"].sum() == 33_000
        scan_hops = [h for h in hops if h.startswith(ParquetReader.__name__ + ".")]
        assert sorted(scan_hops) == [
            *["ParquetReader._decode_segment.<locals>.decode_job"] * 5,
            "ParquetReader._fold_segment",
        ], hops
        assert st.counts["ssts_read"] == 30 and st.counts["io_decode"] == 1
        await eng.close()

    @async_test
    async def test_the_works_names_are_opened_by_the_workers(self, monkeypatch):
        """What the loop's thread opens of a pushdown are the two stages
        whose bodies await: `io_decode`, as a materialising scan's loop
        does, and `fold_wait`, a wait by name. Every stage of the work
        itself is opened by the worker that runs it."""
        eng = await self.engine("funnel/fold-annotations")
        await self.write_ssts(eng, files=2, rows=500)
        opened_by: dict[str, set] = {}

        class ByThread:
            def __init__(self, name, **_kw):
                opened_by.setdefault(name, set()).add(threading.current_thread().name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(scanstats, "TraceAnnotation", ByThread)
        await self.pushdown(eng, eng.manifest.all_ssts())
        loop_thread = threading.current_thread().name
        on_loop = {n for n, who in opened_by.items() if loop_thread in who}
        assert on_loop == {"scan.io_decode", "scan.fold_wait"}
        assert WAITS.search("scan.fold_wait")
        on_workers = {n for n, who in opened_by.items() if who - {loop_thread}}
        work = {"scan.io_decode", "scan.host_prep", "scan.pack_sort", "scan.fold_prep"}
        assert work <= on_workers
        assert not any(WAITS.search(n) for n in on_workers)
        await eng.close()

    # every row of `write_ssts` passes: the selection prunes nothing
    EVERY_ROW = F.Compare("ts", "ge", 10)

    @pytest.mark.parametrize("store", ["mem", "local"])
    @async_test
    async def test_the_footer_is_walked_once_an_sst_and_never_on_the_loop(
            self, monkeypatch, tmp_path, store):
        """Over a store that hands out local files and one that hands out
        bytes: `_select_row_groups` runs once an SST a query, on a worker,
        cold (the parquet file's own footer) and warm (the cached footer
        in front of the block cache's probe); the footer's metadata objects
        are walked by the cold pass alone, which leaves the min/max lanes
        with the cached footer, and they go with it."""
        from horaedb_tpu.objstore import LocalStore
        from horaedb_tpu.storage import read as read_mod

        eng = await self.engine(
            f"funnel/fold-footer-{store}",
            LocalStore(str(tmp_path)) if store == "local" else MemStore())
        await self.write_ssts(eng, files=3, rows=600)
        ssts = sorted(eng.manifest.all_ssts(), key=lambda f: f.id)
        reader = eng.parquet_reader
        calls: dict[str, list[str]] = {"select": [], "walk": []}

        def counted(kind, real):
            def call(*args):
                calls[kind].append(threading.current_thread().name)
                return real(*args)
            return call

        monkeypatch.setattr(read_mod, "_select_row_groups",
                            counted("select", read_mod._select_row_groups))
        monkeypatch.setattr(read_mod, "_row_group_stats",
                            counted("walk", read_mod._row_group_stats))
        loop_thread = threading.current_thread().name

        async def one_pass(walked: int, **kw) -> dict:
            for threads in calls.values():
                threads.clear()
            with scanstats.scan_stats() as st:
                grids = await self.pushdown(eng, ssts, predicate=self.EVERY_ROW, **kw)
            assert len(calls["select"]) == 3 and len(calls["walk"]) == walked, calls
            assert loop_thread not in calls["select"] + calls["walk"], calls
            assert st.counts["ssts_read"] == 3
            assert st.counts.get("footer_walks", 0) == walked
            assert st.counts.get("footer_lanes", 0) == 3 - walked
            return grids

        answers = [await one_pass(3), await one_pass(0), await one_pass(0)]
        assert all(reader._meta_cache[s.id].lanes is not None for s in ssts)
        # the lanes go with the footer, and the next read of that SST walks
        reader.evict_cached(ssts[0].id)
        assert ssts[0].id not in reader._meta_cache
        answers.append(await one_pass(1))
        # outside the block cache a read walks and keeps nothing
        kept = {i: f.lanes for i, f in reader._meta_cache.items()}
        answers.append(await one_pass(3, use_block_cache=False))
        assert {i: f.lanes for i, f in reader._meta_cache.items()} == kept
        assert answers[0]["count"].sum() == 1800
        for again in answers[1:]:
            for k, g in answers[0].items():
                np.testing.assert_array_equal(again[k], g)
        # the raw scan opens its SSTs through the same call; with no
        # predicate nothing of the footer's metadata is read at all
        for threads in calls.values():
            threads.clear()
        with scanstats.scan_stats() as st:
            table = await collect(eng, ScanRequest(range=TimeRange(0, SEGMENT_MS)))
        assert table.num_rows == 1800
        assert len(calls["select"]) == 3 and not calls["walk"], calls
        assert loop_thread not in calls["select"]
        assert "footer_walks" not in st.counts and "footer_lanes" not in st.counts
        await eng.close()

    @async_test
    async def test_footer_prunes_are_counted_by_what_served_them(self):
        """horaedb_scan_footer_prunes_total{served}: a cold pushdown over
        three SSTs walks three footers, two warm ones prune from their
        lanes; the query's own collector shows the same."""
        eng = await self.engine("funnel/fold-footer-counter")
        await self.write_ssts(eng, files=3, rows=600)
        ssts = eng.manifest.all_ssts()
        family = "horaedb_scan_footer_prunes_total"
        before = {k: counter(family, served=k) for k in ("walk", "lanes")}
        per_query = []
        for _ in range(3):
            with scanstats.scan_stats() as st:
                await self.pushdown(eng, ssts, predicate=self.EVERY_ROW)
            per_query.append((st.counts.get("footer_walks", 0), st.counts.get("footer_lanes", 0)))
        assert per_query == [(3, 0), (0, 3), (0, 3)]
        moved = {k: counter(family, served=k) - before[k] for k in before}
        assert moved == {"walk": 3, "lanes": 6}
        # without a predicate nothing is pruned and nothing counted
        await self.pushdown(eng, ssts)
        assert {k: counter(family, served=k) - before[k] for k in before} == moved
        await eng.close()


class TestKernelsAndCompiles:
    def test_the_program_is_named_after_the_label(self, annotations):
        def kernel(x):
            return x * 2.0

        f = xjit(kernel, kernel="xp_name")
        text = f.lower(np.zeros(4, np.float32)).as_text()
        assert "jit_xp_name" in text and "jit_kernel" not in text
        f(np.zeros(4, np.float32))
        assert annotations == ["xjit.xp_name"]

    def test_every_compile_is_counted_and_lands_once(self):
        """An eager jnp compile raises horaedb_xla_compile_seconds and the
        request's compile lane; a compile inside an XJit call is in the
        lane once (from the call's own record), not twice."""
        import jax.numpy as jnp

        xprof.register_metrics()
        n0, s0 = hist("horaedb_xla_compile_seconds")
        t0 = xprof.xla_totals()
        with scanstats.scan_stats() as st:
            # a shape no other test uses: a fresh eager compile
            jnp.sort(jnp.arange(1237, dtype=jnp.float32) * 3.0).block_until_ready()
        n1, s1 = hist("horaedb_xla_compile_seconds")
        assert n1 > n0 and s1 > s0
        assert st.counts["compile"] == n1 - n0
        assert st.seconds["compile"] == pytest.approx(s1 - s0)
        assert xprof.xla_totals()["compiles"] - t0["compiles"] == n1 - n0

        @xjit(kernel="xp_once")
        def f(x):
            return jnp.cumsum(x * 5.0)

        with scanstats.scan_stats() as st2:
            f(np.arange(1239, dtype=np.float32)).block_until_ready()
        n2, _ = hist("horaedb_xla_compile_seconds")
        assert n2 == n1 + 1
        assert st2.counts["compile"] == 1
        (entry,) = xprof.kernel_entries(["xp_once"])
        assert st2.seconds["compile"] == pytest.approx(entry["compile_seconds"], abs=1e-6)


class TestServerSurface:
    async def client(self, tmp_path):
        from tests.test_server import make_client

        return await make_client(tmp_path)

    @async_test
    async def test_loop_lag_is_measured(self, tmp_path):
        client = await self.client(tmp_path)
        try:
            await asyncio.sleep(0.1)  # the heartbeat is running
            n0, s0 = hist("horaedb_loop_lag_seconds")
            assert n0 >= 2
            time.sleep(0.2)  # jaxlint: disable=J018 the test holds the loop on purpose
            await asyncio.sleep(0.05)
            _, s1 = hist("horaedb_loop_lag_seconds")
            assert 0.15 <= s1 - s0 <= 0.5
        finally:
            await client.close()

    @async_test
    async def test_kernels_count_every_xla_compile(self, tmp_path):
        import jax.numpy as jnp

        client = await self.client(tmp_path)
        try:
            x0 = (await (await client.get("/debug/kernels")).json())["xla"]
            assert set(x0) == {"compiles", "compile_seconds", "cache_hits"}
            jnp.sort(jnp.arange(1241, dtype=jnp.float32) * 7.0).block_until_ready()
            x1 = (await (await client.get("/debug/kernels")).json())["xla"]
            assert x1["compiles"] > x0["compiles"]
            assert x1["compile_seconds"] > x0["compile_seconds"]
        finally:
            await client.close()

    @async_test
    async def test_the_server_owns_profiler_and_device_memory(self, tmp_path):
        """POST /debug/profile/start|stop around one write leaves a trace
        whose host lanes carry the funnel's names; /debug/memory has the
        device's own statistics (none on the CPU)."""
        from jax.profiler import ProfileData
        from tests.test_engine import make_remote_write

        client = await self.client(tmp_path)
        try:
            mem = await (await client.get("/debug/memory")).json()
            assert isinstance(mem["device"], dict)
            r = await client.post("/debug/profile/stop")
            assert r.status == 409
            r = await client.post("/debug/profile/start")
            assert r.status == 400
            out = tmp_path / "prof"
            r = await client.post(f"/debug/profile/start?dir={out}")
            assert r.status == 200 and (await r.json())["start_call_s"] >= 0
            r = await client.post(f"/debug/profile/start?dir={out}")
            assert r.status == 409
            payload = make_remote_write(
                [({"__name__": "profm", "host": "a"}, [(1000, 1.0)])])
            assert (await client.post("/api/v1/write", data=payload)).status == 200
            r = await client.post("/debug/profile/stop")
            assert r.status == 200 and (await r.json())["stop_call_s"] >= 0
            (pb,) = list(out.rglob("*.xplane.pb"))
            names = {e.name for plane in ProfileData.from_file(str(pb)).planes
                     for line in plane.lines for e in line.events}
            assert {"ingest.parse", "flush.encode", "flush.upload",
                    "flush.manifest"} <= names, sorted(n for n in names if "." in n)[:40]
        finally:
            await client.close()
