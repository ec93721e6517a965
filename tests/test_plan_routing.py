"""Materializing-scan planner routing + the link measurement it rests on.

The cost model (read.py::_plan_and_merge) routes each merge to host SIMD or
the device kernel based on MEASURED link numbers; these tests pin the two
regimes the planner exists for (a fast link must pick the device route, a
slow one the host) and the measurement's contract: made once per process,
and a failure raises to the scan instead of being planned around.
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.storage import scanstats
from horaedb_tpu.storage.config import UpdateMode
from horaedb_tpu.storage.read import _LinkProfile, _plan_and_merge
from horaedb_tpu.storage.types import StorageSchema
from tests.conftest import async_test

SLOW_LINK = {"h2d_bw": 1e6, "d2h_bw": 1e6, "dispatch_s": 1.0,
             "sort_s_per_row": 1.2e-6}
FAST_LINK = {"h2d_bw": 1e10, "d2h_bw": 1e10, "dispatch_s": 1e-5,
             "sort_s_per_row": 4e-9}


def _make_inputs(n: int = 200_000, shuffled: bool = True):
    schema = StorageSchema.try_new(
        pa.schema([("pk", pa.int64()), ("v", pa.float64())]), 1,
        UpdateMode.OVERWRITE,
    )
    rng = np.random.default_rng(7)
    pk = rng.integers(0, n // 4, n, dtype=np.int64)
    if not shuffled:
        pk = np.sort(pk)
    cols = {
        "pk": pk,
        "__seq__": np.full(n, 3, dtype=np.uint64),
        "v": rng.normal(size=n),
    }
    return schema, n, cols


def _run(schema, n, cols):
    return _plan_and_merge(
        schema, n, lambda name: cols[name], None, lambda: None, False,
        lambda name: cols[name].dtype.itemsize,
    )


def _routes(st: scanstats.ScanStats) -> set:
    return {k for k in st.counts if k.startswith("path_")}


class TestPlannerRouting:
    def test_fast_link_picks_device_route(self, monkeypatch):
        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))
        schema, n, cols = _make_inputs()
        with scanstats.scan_stats() as st:
            idx = _run(schema, n, cols)
        assert "path_device_merge" in _routes(st), st.counts
        # result correctness: keep-last per pk, sorted by pk
        assert np.all(np.diff(cols["pk"][idx]) > 0)

    def test_slow_link_picks_host_route(self, monkeypatch):
        # a measured link of ~1 MB/s and 1 s dispatch: every device route
        # loses the cost compare
        monkeypatch.setattr(_LinkProfile, "_cached", dict(SLOW_LINK))
        schema, n, cols = _make_inputs()
        with scanstats.scan_stats() as st:
            idx = _run(schema, n, cols)
        assert _routes(st) == {"path_host_merge"}, st.counts
        assert np.all(np.diff(cols["pk"][idx]) > 0)

    def test_both_routes_agree(self, monkeypatch):
        schema, n, cols = _make_inputs(n=50_000)
        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))
        monkeypatch.setenv("HORAEDB_SCAN_PATH", "device")
        dev = _run(schema, n, cols)
        monkeypatch.setenv("HORAEDB_SCAN_PATH", "host")
        host = _run(schema, n, cols)
        np.testing.assert_array_equal(dev, host)

    def test_presorted_input_stays_on_host_even_on_fast_link(self, monkeypatch):
        """A compacted segment is already in (pk, seq) order; the host path
        is O(n) with zero transfer — no device route can beat it."""
        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))
        schema, n, cols = _make_inputs(shuffled=False)
        with scanstats.scan_stats() as st:
            _run(schema, n, cols)
        assert _routes(st) == {"path_host_merge"}, st.counts


class TestChunkedDeviceDoubleBuffer:
    @async_test
    async def test_chunked_scan_device_route_matches_host(
        self, monkeypatch, tmp_path
    ):
        """The hierarchical scan's deferred device merges (chunk i's kernel
        overlapping chunk i+1's decode+pack) must produce exactly the host
        route's rows — across multiple chunks and a predicate."""
        import pyarrow as pa_mod

        from horaedb_tpu.objstore import LocalStore
        from horaedb_tpu.ops import filter as F
        from horaedb_tpu.storage import (
            ObjectBasedStorage,
            TimeRange,
            WriteRequest,
        )
        from horaedb_tpu.storage.config import StorageConfig
        from horaedb_tpu.storage.read import ScanRequest

        schema = pa_mod.schema(
            [("pk", pa_mod.int64()), ("ts", pa_mod.int64()),
             ("v", pa_mod.float64())]
        )
        store = LocalStore(str(tmp_path / "store"))
        eng = await ObjectBasedStorage.try_new(
            "db", store, schema, num_primary_keys=2,
            segment_duration_ms=3_600_000,
            config=StorageConfig(scan_block_rows=2_000),
            enable_compaction_scheduler=False,
            start_background_merger=False,
        )
        rng = np.random.default_rng(11)
        for i in range(6):  # 6 SSTs x 1500 rows -> multiple chunks
            batch = pa_mod.RecordBatch.from_pydict({
                "pk": rng.integers(0, 500, 1500),
                "ts": rng.integers(0, 3_600_000, 1500),
                "v": np.full(1500, float(i)),
            }, schema=schema)
            await eng.write(WriteRequest(batch, TimeRange(0, 3_600_000)))

        async def collect() -> list:
            rows = []
            async for b in eng.scan(ScanRequest(
                range=TimeRange(0, 3_600_000),
                predicate=F.Compare("pk", "lt", 400),
            )):
                rows.extend(zip(b["pk"].to_pylist(), b["ts"].to_pylist(),
                                b["v"].to_pylist()))
            return rows

        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))
        monkeypatch.setenv("HORAEDB_SCAN_PATH", "device")
        with scanstats.scan_stats() as st:
            dev_rows = await collect()
        assert "path_device_merge" in st.counts or \
            "path_device_merge_packed" in st.counts, st.counts
        monkeypatch.setenv("HORAEDB_SCAN_PATH", "host")
        host_rows = await collect()
        assert dev_rows == host_rows and len(dev_rows) > 0
        await eng.close()


class TestMergesAtOnce:
    """A segment scan's merge runs on a worker thread, so several run at
    once: what they share (the planner's rolling host estimate, the kernel
    builders' caches, the collectors) must take it."""

    @pytest.mark.parametrize("path", ["host", "device"])
    @async_test
    async def test_concurrent_segment_scans_equal_sequential(self, monkeypatch, path):
        import asyncio
        import math

        from horaedb_tpu.objstore import MemStore
        from horaedb_tpu.ops import filter as F
        from horaedb_tpu.storage import ObjectBasedStorage, TimeRange, WriteRequest
        from horaedb_tpu.storage.read import ParquetReader, _HostCalib

        schema = pa.schema([("pk", pa.int64()), ("ts", pa.int64()), ("v", pa.float64())])
        eng = await ObjectBasedStorage.try_new(
            f"db-at-once-{path}", MemStore(), schema, num_primary_keys=2,
            segment_duration_ms=3_600_000,
            enable_compaction_scheduler=False, start_background_merger=False,
        )
        rng = np.random.default_rng(13)
        rows = 20_000
        for i in range(12):  # the same keys again and again: every SST overlaps
            batch = pa.RecordBatch.from_pydict({
                "pk": rng.integers(0, 3_000, rows),
                "ts": rng.integers(0, 200, rows),
                "v": np.full(rows, float(i)),
            }, schema=schema)
            await eng.write(WriteRequest(batch, TimeRange(0, 3_600_000)))
        ssts = sorted(eng.manifest.all_ssts(), key=lambda f: f.id)
        assert len(ssts) == 12
        # 8 scans over overlapping sets of 5 SSTs (100,000 rows each: past
        # _HostCalib.MIN_ROWS, so every host merge is an observation),
        # every other one under a predicate
        calls = [(ssts[i:i + 5], F.Compare("pk", "lt", 2_000) if i % 2 else None)
                 for i in range(8)]
        reader = eng.parquet_reader

        async def scan(subset, predicate) -> pa.Table:
            return pa.Table.from_batches(
                await reader.scan_segment(subset, predicate, None, True))

        in_flight, peak, lock = [0], [0], threading.Lock()
        real = ParquetReader._merge_segment

        def counted(self, *args):
            with lock:
                in_flight[0] += 1
                peak[0] = max(peak[0], in_flight[0])
            try:
                return real(self, *args)
            finally:
                with lock:
                    in_flight[0] -= 1

        monkeypatch.setattr(ParquetReader, "_merge_segment", counted)
        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))
        monkeypatch.setenv("HORAEDB_SCAN_PATH", path)
        _HostCalib.reset()
        try:
            one_by_one = [await scan(*c) for c in calls]
            assert peak[0] == 1
            with scanstats.scan_stats() as st:
                at_once = await asyncio.gather(*(scan(*c) for c in calls))
            assert peak[0] >= 2, "the merges did not overlap"
            for want, got in zip(one_by_one, at_once):
                assert want.num_rows > 0 and got.equals(want)
            # one collector took the stages of all eight, none lost
            assert st.counts[scanstats.MERGE_WAIT] == 8
            assert st.counts["materialize"] == 8
            route = "path_host_merge" if path == "host" else "path_device_merge"
            assert sum(v for k, v in st.counts.items() if k.startswith(route)) == 8
            est = _HostCalib.sort_s_per_row()
            assert math.isfinite(est) and est > 0
            assert math.isfinite(_HostCalib.eval_s_per_row()) and _HostCalib.eval_s_per_row() > 0
        finally:
            _HostCalib.reset()
            await eng.close()


    def test_what_the_workers_share_loses_no_update(self):
        """16 threads (more than cores) under a short switch interval fold
        into one collector, one ledger and the planner's estimate: every
        count arrives, and the estimate stays a convex mix of what it saw."""
        import sys

        from horaedb_tpu.common import memtrace
        from horaedb_tpu.storage.read import _HostCalib

        if memtrace.mode() == "off":
            pytest.skip("no ledger under HORAEDB_MEMTRACE=off")
        threads, rounds = 16, 2_000
        st = scanstats.ScanStats()
        ledger = memtrace.MemLedger()
        start = threading.Barrier(threads)

        def work():
            start.wait(timeout=30)
            for _ in range(rounds):
                st.add("host_merge", 0.5)
                st.count("path_host_merge")
                ledger.add("host_prep", "copy", 3)
                _HostCalib.observe_sort(100_000, 0.03)  # 300 ns a row

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        _HostCalib.reset()
        try:
            ts = [threading.Thread(target=work) for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
            n = threads * rounds
            assert st.counts["host_merge"] == st.counts["path_host_merge"] == n
            assert st.seconds["host_merge"] == 0.5 * n
            assert ledger.events[("host_prep", "copy")] == [n, 3 * n]
            assert 200e-9 <= _HostCalib.sort_s_per_row() <= 300e-9 * (1 + 1e-9)
        finally:
            sys.setswitchinterval(old)
            _HostCalib.reset()


class TestFoldsAtOnce:
    """A segment's aggregate pushdown folds on a worker thread, so eight
    callers' folds run at once: the answers are the sequential ones bit for
    bit, a deadline still ends a call, and what the folds share (the row
    classes, the dispatcher's record) takes it."""

    SERIES = 50

    @classmethod
    async def overlapping(cls, root: str):
        """An engine over 12 SSTs that write the same (series, ts) cells
        again and again (pk2 pinned: the packed route's contract), so that
        every subset of them dedups across its files."""
        from horaedb_tpu.objstore import MemStore
        from horaedb_tpu.storage import ObjectBasedStorage, TimeRange, WriteRequest

        schema = pa.schema([("pk1", pa.int64()), ("pk2", pa.int64()),
                            ("ts", pa.int64()), ("value", pa.float64())])
        eng = await ObjectBasedStorage.try_new(
            root, MemStore(), schema, num_primary_keys=3,
            segment_duration_ms=3_600_000,
            enable_compaction_scheduler=False, start_background_merger=False,
        )
        rng = np.random.default_rng(17)
        rows = 6_000
        for _ in range(12):
            cell = rng.choice(cls.SERIES * 400, rows, replace=False)
            batch = pa.RecordBatch.from_pydict({
                "pk1": cell % cls.SERIES, "pk2": np.zeros(rows, np.int64),
                "ts": cell // cls.SERIES * 10, "value": rng.normal(size=rows) * 1e3,
            }, schema=schema)
            await eng.write(WriteRequest(batch, TimeRange(0, 3_600_000)))
        ssts = sorted(eng.manifest.all_ssts(), key=lambda f: f.id)
        assert len(ssts) == 12
        return eng, ssts

    @classmethod
    def pushdown(cls, eng, subset, predicate, packed_ok):
        return eng.parquet_reader.scan_segment_downsample(
            subset, predicate, "ts", "value", "pk1", np.arange(0, cls.SERIES, 2),
            0, 500, 8, packed_ok=packed_ok)

    @pytest.mark.parametrize("packed_ok", [True, False], ids=["packed", "fused"])
    @async_test
    async def test_concurrent_pushdowns_equal_sequential(self, monkeypatch, packed_ok):
        import asyncio

        from horaedb_tpu.ops import filter as F
        from horaedb_tpu.storage.read import ParquetReader

        eng, ssts = await self.overlapping(f"db-folds-at-once-{packed_ok}")
        # 8 pushdowns over overlapping sets of 5 SSTs, half of the series
        # asked for, every other call under a predicate
        calls = [(ssts[i:i + 5], F.Compare("ts", "lt", 3_000) if i % 2 else None)
                 for i in range(8)]
        in_flight, peak, lock = [0], [0], threading.Lock()
        real = ParquetReader._fold_segment

        def counted(self, *args):
            with lock:
                in_flight[0] += 1
                peak[0] = max(peak[0], in_flight[0])
            try:
                time.sleep(0.02)  # jaxlint: disable=J018 long enough to meet the next
                return real(self, *args)
            finally:
                with lock:
                    in_flight[0] -= 1

        monkeypatch.setattr(ParquetReader, "_fold_segment", counted)
        try:
            one_by_one = [await self.pushdown(eng, *c, packed_ok) for c in calls]
            assert peak[0] == 1
            with scanstats.scan_stats() as st:
                at_once = await asyncio.gather(
                    *(self.pushdown(eng, *c, packed_ok) for c in calls))
            assert peak[0] >= 2, "the folds did not overlap"
            for want, got in zip(one_by_one, at_once):
                assert want["count"].sum() > 0 and set(got) == {"sum", "count", "min", "max"}
                for k in want:  # bit for bit: the same rows, program and order
                    np.testing.assert_array_equal(got[k], want[k], err_msg=k)
            # one collector took the stages of all eight, none lost
            assert st.counts[scanstats.FOLD_WAIT] == 8
            assert st.counts["host_prep"] == 8
            assert st.counts.get("pack_sort", 0) == (8 if packed_ok else 0)
            assert st.counts.get("device_merge", 0) == (0 if packed_ok else 8)
        finally:
            await eng.close()

    @async_test
    async def test_a_deadline_that_expires_mid_call_still_raises(self, monkeypatch):
        """The budget runs out while the worker sorts: the check before the
        fold's dispatch raises there, and the caller gets what it got when
        the tail ran on the loop."""
        from horaedb_tpu.common import deadline as deadline_ctx
        from horaedb_tpu.common.error import DeadlineExceeded
        from horaedb_tpu.ops import aggregate as agg_ops
        from horaedb_tpu.storage.read import ParquetReader

        eng, ssts = await self.overlapping("db-folds-deadline")
        now = [0.0]
        real = ParquetReader._packed_downsample_pass
        folds = []

        def sort_past_the_budget(self, *args):
            out = real(self, *args)
            now[0] = 5.0
            return out

        monkeypatch.setattr(ParquetReader, "_packed_downsample_pass", sort_past_the_budget)
        monkeypatch.setattr(agg_ops, "fold_sorted", lambda *a, **k: folds.append(1))
        try:
            with deadline_ctx.deadline_scope(deadline_ctx.Deadline(1.0, clock=lambda: now[0])), \
                    scanstats.scan_stats() as st:
                with pytest.raises(DeadlineExceeded) as err:
                    await self.pushdown(eng, ssts[:3], None, True)
            assert err.value.at == "device_lane" and not folds
            # the three SSTs rewrite each other's cells: the pass that ran
            # past the budget is the full one, its dedup step included
            assert st.counts.get("pack_dedup") == 1
        finally:
            await eng.close()

    def test_what_the_folds_share_loses_no_update(self):
        """16 threads (more than cores) under a short switch interval pick
        row classes and record the dispatcher's choice: every class is
        kept once and in order, a pick never shrinks, every record counts."""
        import sys

        from horaedb_tpu.ops import agg_registry
        from horaedb_tpu.ops.aggregate import _MAX_RIDE_ROWS, _RowClasses
        from tests.test_stage_funnel import counter

        threads, rounds = 16, 500
        classes = _RowClasses()
        naturals = [1 << p for p in range(9, 22)]  # 512 .. 2^21: past the ride limit too
        start = threading.Barrier(threads)
        wrong: list[tuple] = []

        def work(seed: int):
            rng = np.random.default_rng(seed)
            start.wait(timeout=30)
            for _ in range(rounds):
                natural = int(rng.choice(naturals))
                key = ("grid", int(rng.integers(0, 3)))
                got = classes.pick(key, natural)
                if got < natural or (got != natural and got > _MAX_RIDE_ROWS):
                    wrong.append((natural, got))
                agg_registry.record_choice("reduceat")

        before = counter("horaedb_agg_impl_total", impl="reduceat")
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
        finally:
            sys.setswitchinterval(old)
        assert not wrong, wrong[:5]
        assert len(classes._rows) == 3
        for have in classes._rows.values():
            assert have == sorted(set(have)) and set(have) <= set(naturals)
            # a class at or under the limit is ridden by every smaller pick:
            # only the first such class and the ones above the limit remain
            assert sum(1 for r in have if r <= _MAX_RIDE_ROWS) >= 1
        assert counter("horaedb_agg_impl_total", impl="reduceat") - before == threads * rounds


class TestMergeShapeClasses:
    def test_merge_rows_are_power_of_two_classes(self):
        from horaedb_tpu.storage.read import _merge_rows

        assert [_merge_rows(n) for n in (1, 8192, 8193, 300_000, 720_000)] == \
            [8192, 8192, 16384, 524288, 1048576]

    def test_fan_in_is_an_operand_of_one_compiled_packed_merge(self):
        """Merges whose seq rank needs 1, 3 or 5 bits share one program per
        row class (the TPU pays ~30 s per compiled sort), and still dedup
        on the right boundary."""
        from horaedb_tpu.storage.read import (
            _build_packed_index_kernel,
            _packed_merge_kernel,
        )

        before = _packed_merge_kernel(True).stats()["compiles"]
        for width in (1, 3, 5):
            # two rows per pk group, distinct seq ranks: keep the later one
            pk = np.repeat(np.arange(4096, dtype=np.uint64), 2)
            rank = np.tile(np.array([0, 1], dtype=np.uint64), 4096)
            packed = (pk << np.uint64(width)) | rank
            idx, kept = _build_packed_index_kernel(width, True)(packed[::-1].copy(), 8192)
            got = packed[::-1][np.asarray(idx[: int(kept)])]
            np.testing.assert_array_equal(got, packed[1::2])
        assert _packed_merge_kernel(True).stats()["compiles"] - before <= 1


class TestLinkProfile:
    """One in-process measurement, made once, whose failure raises."""

    def _reset(self, monkeypatch, measure):
        monkeypatch.setattr(_LinkProfile, "_measure", staticmethod(measure))
        monkeypatch.setattr(_LinkProfile, "_cached", None)

    def test_measured_once_and_kept(self, monkeypatch):
        real = {"h2d_bw": 7e9, "d2h_bw": 7e9, "dispatch_s": 1e-4,
                "sort_s_per_row": 25e-9}
        calls = []

        def measure():
            calls.append(1)
            return dict(real)

        self._reset(monkeypatch, measure)
        assert _LinkProfile.get() == real
        assert _LinkProfile.get() == real
        assert len(calls) == 1

    def test_concurrent_first_scans_share_one_measurement(self, monkeypatch):
        real = {"h2d_bw": 6e9, "d2h_bw": 6e9, "dispatch_s": 1e-4,
                "sort_s_per_row": 25e-9}
        calls = []

        def measure():
            calls.append(1)
            time.sleep(0.3)
            return dict(real)

        self._reset(monkeypatch, measure)
        results: list[dict] = []
        threads = [
            threading.Thread(target=lambda: results.append(_LinkProfile.get()))
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert len(results) == 4 and all(r == real for r in results), results
        assert len(calls) == 1

    def test_failed_measurement_raises_to_the_scan(self, monkeypatch):
        """No substituted plan: a device that cannot be measured fails the
        scan that asked, and nothing is cached in its place."""
        def measure():
            raise RuntimeError("device unreachable")

        self._reset(monkeypatch, measure)
        schema, n, cols = _make_inputs()
        with pytest.raises(RuntimeError, match="device unreachable"):
            _run(schema, n, cols)
        assert _LinkProfile._cached is None


class TestPlannerSelfCalibration:
    """VERDICT r04 #6: a deliberately mis-set host-cost prior must converge
    to the right route from in-place measurements (EWMA over real merges)."""

    @pytest.fixture(autouse=True)
    def _fresh_calib(self):
        from horaedb_tpu.storage.read import _HostCalib

        _HostCalib.reset()
        yield
        _HostCalib.reset()

    def test_misset_cheap_host_prior_converges_to_device(self, monkeypatch):
        from horaedb_tpu.storage.read import _HostCalib

        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))
        # prior claims host sorts are ~free -> auto wrongly routes host
        monkeypatch.setattr(_HostCalib, "_sort", 1e-12)
        schema, n, cols = _make_inputs(n=200_000, shuffled=True)
        with scanstats.scan_stats() as st0:
            _run(schema, n, cols)
        assert "path_host_merge" in _routes(st0)  # mis-routed at first
        routes = None
        for i in range(25):
            with scanstats.scan_stats() as st:
                _run(schema, n, cols)
            routes = _routes(st)
            if "path_host_merge" not in routes:
                break
        assert "path_host_merge" not in routes, (
            f"route never converged off the mis-set prior; "
            f"calibrated sort={_HostCalib.sort_s_per_row():.2e}"
        )
        # the estimate left the absurd prior far behind
        assert _HostCalib.sort_s_per_row() > 1e-9

    def test_calib_freezes_with_env_off(self, monkeypatch):
        from horaedb_tpu.storage.read import _HostCalib

        monkeypatch.setenv("HORAEDB_PLANNER_CALIB", "off")
        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))
        monkeypatch.setattr(_HostCalib, "_sort", 1e-12)
        schema, n, cols = _make_inputs(n=200_000, shuffled=True)
        for _ in range(3):
            with scanstats.scan_stats() as st:
                _run(schema, n, cols)
        assert "path_host_merge" in _routes(st)  # stays mis-routed, frozen
        assert _HostCalib._sort == 1e-12

    def test_presorted_merges_do_not_poison_estimate(self, monkeypatch):
        from horaedb_tpu.storage.read import _HostCalib

        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))
        before = _HostCalib.sort_s_per_row()
        schema, n, cols = _make_inputs(n=200_000, shuffled=False)
        with scanstats.scan_stats() as st:
            _run(schema, n, cols)
        assert "path_host_merge" in _routes(st)  # presorted always host
        # the O(n) shortcut must not be folded into the per-row SORT cost
        assert _HostCalib.sort_s_per_row() == before


class TestBackendWhoseF64IsNotExact:
    """An accelerator carries f64 as a pair of f32 (ops/aggregate.py
    device_f64_is_exact): f64 predicate lanes evaluate on the host and ride
    the mask lane, f64 sort keys keep the merge on the host."""

    @staticmethod
    def _inexact(monkeypatch):
        from horaedb_tpu.ops import aggregate

        monkeypatch.setattr(aggregate, "device_f64_is_exact", lambda: False)
        monkeypatch.setattr(_LinkProfile, "_cached", dict(FAST_LINK))

    def test_f64_predicate_is_evaluated_on_the_host_then_merged_on_device(
        self, monkeypatch
    ):
        from horaedb_tpu.ops import filter as F

        schema, n, cols = _make_inputs(n=20_000)
        cols["v"][::3] *= 1e300  # no f32 exponent holds these
        pred = F.Compare("v", "gt", 1e299)
        asked = []

        def host_mask():
            asked.append(1)
            return F.eval_predicate_np(pred, {"v": cols["v"]})

        def run():
            return _plan_and_merge(
                schema, n, lambda name: cols[name], pred, host_mask, False,
                lambda name: cols[name].dtype.itemsize,
            )

        monkeypatch.setenv("HORAEDB_SCAN_PATH", "host")
        want = run()
        self._inexact(monkeypatch)
        monkeypatch.setenv("HORAEDB_SCAN_PATH", "device")
        del asked[:]
        with scanstats.scan_stats() as st:
            got = run()
        assert asked, "the f64 predicate went to the device"
        assert _routes(st) & {"path_device_merge", "path_device_merge_packed"}
        np.testing.assert_array_equal(got, want)
        assert len(got) and np.all(cols["v"][got] > 1e299)

    def test_f64_sort_keys_stay_on_the_host(self, monkeypatch):
        import pytest

        from horaedb_tpu.common.error import HoraeError

        schema = StorageSchema.try_new(
            pa.schema([("pk", pa.float64()), ("v", pa.float64())]), 1,
            UpdateMode.OVERWRITE,
        )
        rng = np.random.default_rng(3)
        n = 50_000
        cols = {"pk": rng.normal(size=n) * 1e300,
                "__seq__": np.full(n, 3, dtype=np.uint64),
                "v": rng.normal(size=n)}
        self._inexact(monkeypatch)
        with scanstats.scan_stats() as st:
            idx = _run(schema, n, cols)
        assert _routes(st) == {"path_host_f64_keys", "path_host_merge"}, st.counts
        assert np.all(np.diff(cols["pk"][idx]) > 0) and len(idx) == n
        monkeypatch.setenv("HORAEDB_SCAN_PATH", "device")
        with pytest.raises(HoraeError, match="f64 keys"):
            _run(schema, n, cols)
