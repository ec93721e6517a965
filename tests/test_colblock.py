"""The zero-copy spine's buffer contract (common/colblock.py): block
alignment/ownership/epoch semantics, the lineage events each sanctioned
hand-off files, device round-trip bit-exactness, and the end-to-end
ingest->flush->scan->cache-hit path asserting ZERO copy events at every
refactored hand-off (the one surviving scan copy is the materialize
take — the output itself)."""

import asyncio

import numpy as np
import pyarrow as pa
import pytest

import horaedb_tpu.ops  # noqa: F401 — enables x64 before device tests
from horaedb_tpu.common import colblock, memtrace
from horaedb_tpu.common.error import HoraeError


def bits(f64_arr) -> np.ndarray:
    return np.asarray(f64_arr, dtype=np.float64).view(np.uint64)


# f64 values whose BITS a JSON/float round-trip would launder: a NaN
# with payload, negative zero, a subnormal
TRICKY = np.array([0x7FF8_0000_DEAD_BEEF, 0x8000_0000_0000_0000, 0x1],
                  dtype=np.uint64).view(np.float64)


class TestAlignedEmpty:
    def test_alignment_across_dtypes_and_sizes(self):
        for dt in (np.uint64, np.int64, np.float64, np.int32, np.bool_):
            for n in (1, 7, 63, 64, 65, 1000):
                a = colblock.aligned_empty(n, dt)
                assert a.ctypes.data % colblock.ALIGNMENT == 0
                assert a.dtype == np.dtype(dt) and len(a) == n
                assert a.flags.c_contiguous and a.flags.writeable


class TestColBlockContract:
    def make(self):
        return colblock.ColBlock.wrap({
            "ts": np.arange(8, dtype=np.int64),
            "value": np.linspace(0.0, 1.0, 8),
        })

    def test_freeze_is_idempotent_and_bumps_epoch_once(self):
        b = self.make()
        assert not b.frozen and b.epoch == 0
        b.freeze()
        assert b.frozen and b.epoch == 1
        b.freeze()
        assert b.epoch == 1

    def test_frozen_lane_is_read_only_and_writable_lane_raises(self):
        b = self.make()
        b.writable_lane("ts")[0] = 99  # fill phase: fine
        b.freeze()
        with pytest.raises(HoraeError):
            b.writable_lane("ts")
        with pytest.raises(ValueError):
            b.lane("ts")[0] = 1
        assert int(b.lane("ts")[0]) == 99

    def test_ragged_lanes_rejected(self):
        with pytest.raises(HoraeError):
            colblock.ColBlock.wrap({
                "a": np.zeros(3), "b": np.zeros(4),
            })

    def test_cow_on_frozen_yields_writable_next_epoch(self):
        b = self.make().freeze()
        with memtrace.mem_trace() as led:
            c = b.cow("materialize")
        assert c is not b and not c.frozen and c.epoch == b.epoch + 1
        c.writable_lane("ts")[0] = -1
        assert int(b.lane("ts")[0]) != -1  # the original is untouched
        v = memtrace.verdict(led)
        assert v["copies"] == 2 and v["allocs"] == 0  # one per lane
        # unfrozen cow is the single-owner identity, no events
        u = self.make()
        with memtrace.mem_trace() as led2:
            assert u.cow("materialize") is u
        assert memtrace.verdict(led2)["copies"] == 0

    def test_share_requires_freeze_and_files_reuse(self):
        b = self.make()
        with pytest.raises(HoraeError):
            b.share("result_fill")
        b.freeze()
        with memtrace.mem_trace() as led:
            assert b.share("result_fill") is b
        v = memtrace.verdict(led)
        assert v["reuses"] == 1 and v["copies"] == 0
        assert v["per_stage"]["result_fill"]["reuse_bytes"] == b.nbytes

    def test_copy_lane_is_tracked_writable_aligned(self):
        b = self.make().freeze()
        with memtrace.mem_trace() as led:
            a = b.copy_lane("value", "materialize")
        assert a.flags.writeable
        assert a.ctypes.data % colblock.ALIGNMENT == 0
        assert memtrace.verdict(led)["copies"] == 1

    def test_alloc_is_aligned_and_tracked(self):
        with memtrace.mem_trace() as led:
            b = colblock.ColBlock.alloc(
                {"ts": np.int64, "value": np.float64}, 100, "append")
        assert b.aligned() and b.n_rows == 100
        assert memtrace.verdict(led)["allocs"] == 2

    def test_to_arrow_batch_is_one_view_event_bit_exact(self):
        vals = TRICKY.copy()
        b = colblock.ColBlock.wrap({
            "ts": np.arange(3, dtype=np.int64), "value": vals,
        }).freeze()
        schema = pa.schema([("ts", pa.int64()), ("value", pa.float64())])
        with memtrace.mem_trace() as led:
            batch = b.to_arrow_batch(schema)
        v = memtrace.verdict(led)
        assert v["copies"] == 0 and v["views"] == 1
        assert v["per_stage"]["flush_encode"]["view_bytes"] == b.nbytes
        got = batch.column(1).to_numpy(zero_copy_only=False)
        assert np.array_equal(bits(got), bits(vals))

    def test_device_round_trip_bit_exact_one_staging_charge(self):
        vals = TRICKY.copy()
        b = colblock.ColBlock.wrap({
            "ts": np.array([-(2**62), 0, 2**62], dtype=np.int64),
            "value": vals,
        }).freeze()
        with memtrace.mem_trace() as led:
            dev = b.to_device()
        v = memtrace.verdict(led)
        # ONE device_staged charge for the whole block, no host alloc
        assert v["per_stage"]["h2d"]["copy"] == 1
        assert v["per_stage"]["h2d"]["copy_bytes"] == b.nbytes
        assert v["allocs"] == 0
        back = np.asarray(dev["value"])
        assert back.dtype == np.float64
        assert np.array_equal(bits(back), bits(vals))
        assert np.array_equal(np.asarray(dev["ts"]), b.lane("ts"))


class TestGrowableColBlock:
    SCHEMA = {"ts": np.int64, "value": np.float64}

    def test_growth_carries_prefix_and_tracks_allocs(self):
        g = colblock.GrowableColBlock(self.SCHEMA, capacity=4)
        g.append({"ts": np.arange(4, dtype=np.int64),
                  "value": np.ones(4)})
        with memtrace.mem_trace() as led:
            g.append({"ts": np.arange(4, 10, dtype=np.int64),
                      "value": np.full(6, 2.0)})
        assert memtrace.verdict(led)["allocs"] == 2  # one grow per lane
        assert g.n_rows == 10 and g.capacity >= 10
        block, _ = g.seal()
        assert np.array_equal(
            block.lane("ts"), np.arange(10, dtype=np.int64))

    def test_seal_detaches_frozen_views_and_empties_arena(self):
        g = colblock.GrowableColBlock(self.SCHEMA, capacity=8)
        g.append({"ts": np.arange(5, dtype=np.int64),
                  "value": np.zeros(5)})
        with memtrace.mem_trace() as led:
            block, backing = g.seal()
        v = memtrace.verdict(led)
        assert v["copies"] == 0 and v["allocs"] == 0
        assert v["per_stage"]["seal"]["view"] == 1
        assert block.frozen and block.n_rows == 5
        assert g.n_rows == 0 and g.capacity == 0
        # the sealed views alias the returned backing (zero-copy seal)
        assert block.lane("ts").base is not None
        assert len(backing["ts"]) == 8

    def test_adopt_spare_is_reuse(self):
        g = colblock.GrowableColBlock(self.SCHEMA, capacity=8)
        _, backing = g.seal()
        with memtrace.mem_trace() as led:
            g2 = colblock.GrowableColBlock.adopt_spare(backing)
        v = memtrace.verdict(led)
        assert v["reuses"] == 1 and v["allocs"] == 0
        assert g2.capacity == 8 and g2.n_rows == 0

    def test_commit_past_capacity_raises(self):
        g = colblock.GrowableColBlock(self.SCHEMA, capacity=4)
        g.writable_lane("ts")[:4] = 7
        g.commit(4)
        with pytest.raises(HoraeError):
            g.commit(1)


class TestAsLane:
    def test_no_conversion_is_view(self):
        a = np.arange(10, dtype=np.int64)
        with memtrace.mem_trace() as led:
            out = colblock.as_lane(a, np.int64, "host_prep")
        assert out is a
        v = memtrace.verdict(led)
        assert v["views"] == 1 and v["copies"] == 0

    def test_dtype_conversion_is_one_honest_copy(self):
        a = np.arange(10, dtype=np.int32)
        with memtrace.mem_trace() as led:
            out = colblock.as_lane(a, np.int64, "host_prep")
        assert out.dtype == np.int64
        v = memtrace.verdict(led)
        assert v["copies"] == 1 and v["views"] == 0


class TestArrowLanes:
    def chunked_table(self):
        # two record batches -> every column arrives 2-chunked
        b1 = pa.record_batch(
            {"ts": np.arange(6, dtype=np.int64),
             "value": np.linspace(0, 1, 6)})
        b2 = pa.record_batch(
            {"ts": np.arange(6, 12, dtype=np.int64),
             "value": np.linspace(1, 2, 6)})
        return pa.Table.from_batches([b1, b2])

    def test_chunks_are_zero_copy_views(self):
        t = self.chunked_table()
        lanes = colblock.ArrowLanes(t)
        with memtrace.mem_trace() as led:
            chks = lanes.chunks("ts")
        assert [len(c) for c in chks] == [6, 6]
        v = memtrace.verdict(led)
        assert v["views"] == 1 and v["copies"] == 0
        assert np.array_equal(
            np.concatenate(chks), np.arange(12, dtype=np.int64))

    def test_lane_single_chunk_view_multi_chunk_one_copy(self):
        single = self.chunked_table().combine_chunks()
        with memtrace.mem_trace() as led:
            a = colblock.ArrowLanes(single).lane("ts")
        v = memtrace.verdict(led)
        assert v["copies"] == 0
        assert np.array_equal(a, np.arange(12, dtype=np.int64))
        with memtrace.mem_trace() as led:
            lanes = colblock.ArrowLanes(self.chunked_table())
            a = lanes.lane("ts")
            lanes.lane("ts")  # cached: no second event
        v = memtrace.verdict(led)
        assert v["copies"] == 1  # the one sanctioned concat
        assert np.array_equal(a, np.arange(12, dtype=np.int64))

    def test_chunk_edges_and_the_presorted_probe_wrap_nothing_on_overlap(self):
        """The sortedness probe reads the chunk boundaries as arrow
        scalars first: chunks that overlap are refused before one lane
        is wrapped; chunks in order go on to the row-wise check."""
        from horaedb_tpu.storage.read import _lanes_presorted

        t = self.chunked_table()
        lanes = colblock.ArrowLanes(t)
        assert lanes.chunk_edges(("ts",)) == [((0,), (5,)), ((6,), (11,))]
        assert _lanes_presorted(lanes, ("ts",)) is True
        overlap = pa.Table.from_batches(list(reversed(t.to_batches())))
        lanes = colblock.ArrowLanes(overlap)
        with memtrace.mem_trace() as led:
            assert _lanes_presorted(lanes, ("ts",)) is False
            assert _lanes_presorted(lanes, ("ts",)) is False  # memoized
        assert lanes._chunks == {} and lanes._lanes == {}
        v = memtrace.verdict(led)
        assert v["views"] == 0 and v["copies"] == 0
        # the lane a device route asks for next: one arrow combine, one copy
        with memtrace.mem_trace() as led:
            a = lanes.lane("ts")
        assert memtrace.verdict(led)["copies"] == 1 and lanes._chunks == {}
        assert np.array_equal(a, np.r_[6:12, 0:6])

    def test_gather_sorted_matches_full_gather(self):
        lanes = colblock.ArrowLanes(self.chunked_table())
        idx = np.array([0, 3, 5, 6, 7, 11], dtype=np.int64)
        got = lanes.gather_sorted("value", idx)
        want = lanes.lane("value")[idx]
        assert np.array_equal(bits(got), bits(want))

    def test_eval_chunked_matches_full_eval(self):
        t = self.chunked_table()
        lanes = colblock.ArrowLanes(t)
        fn = lambda cols: cols["value"] > 0.75  # noqa: E731
        got = lanes.eval_chunked(fn, ["value"])
        full = t.column("value").combine_chunks().to_numpy() > 0.75
        assert np.array_equal(got, full)


class TestZeroCopySpineEndToEnd:
    def test_ingest_flush_scan_cache_hit_zero_copy_handoffs(self):
        from horaedb_tpu.objstore import MemStore
        from horaedb_tpu.ops.filter import Compare
        from horaedb_tpu.storage import (
            ObjectBasedStorage,
            ScanRequest,
            StorageConfig,
            TimeRange,
            WriteRequest,
            scanstats,
        )

        SEG = 24 * 3_600_000
        t_lo = (1_700_000_000_000 // SEG + 1) * SEG
        n = 20_000
        rng = np.random.default_rng(3)
        schema = pa.schema([
            ("tsid", pa.int64()), ("ts", pa.int64()),
            ("value", pa.float64()),
        ])

        def batch(off):
            r = np.random.default_rng(3 + off)
            tsid = np.sort(r.integers(0, 32, n, dtype=np.int64))
            ts = t_lo + (np.arange(n, dtype=np.int64) * 15_000) % SEG
            vals = r.normal(size=n)
            b = pa.RecordBatch.from_pydict(
                {"tsid": tsid, "ts": ts, "value": vals}, schema=schema)
            return b, TimeRange(int(ts.min()), int(ts.max()) + 1)

        async def run():
            eng = await ObjectBasedStorage.try_new(
                "colblock_e2e", MemStore(), schema, num_primary_keys=2,
                segment_duration_ms=SEG, config=StorageConfig(),
                enable_compaction_scheduler=False,
                start_background_merger=False,
            )
            try:
                with scanstats.scan_stats() as st:
                    for off in (0, 1):  # two SSTs -> the merge fold runs
                        b, rng_t = batch(off)
                        await eng.write(WriteRequest(b, rng_t))
                ingest = memtrace.verdict(st.mem)

                async def scan():
                    req = ScanRequest(
                        range=TimeRange(0, 2**62),
                        predicate=Compare("value", "gt", 0.0))
                    rows = 0
                    async for blk in eng.scan(req):
                        rows += blk.num_rows
                    return rows

                with scanstats.scan_stats() as st:
                    rows_cold = await scan()
                cold = memtrace.verdict(st.mem)
                with scanstats.scan_stats() as st:
                    rows_warm = await scan()
                warm = memtrace.verdict(st.mem)
                return ingest, cold, warm, rows_cold, rows_warm
            finally:
                await eng.close()

        ingest, cold, warm, rows_cold, rows_warm = asyncio.run(run())
        assert rows_cold > 0 and rows_cold == rows_warm
        # ingest: flush encode feeds the writers zero-copy — allocs are
        # the encoded output blobs, never a lane copy
        for stage, row in ingest["per_stage"].items():
            assert "copy" not in row, (stage, row)
        # the refactored hand-offs stay copy-free on BOTH scans: the
        # chunk-aware merge (host_prep), the fills, seal/append. Other
        # stages (decode, materialize) may copy honestly — the decode
        # impl is calibration-dependent, and the materialize take IS
        # the output — so the pin targets the spine's stages, not the
        # ledger total (mem-smoke pins the totals on its fixed shape).
        for v in (cold, warm):
            for stage in ("host_prep", "seal", "append", "parse",
                          "result_fill"):
                row = v["per_stage"].get(stage, {})
                assert "copy" not in row, (stage, row)
            # every hand-off files under a canonical stage: /metrics
            # pre-registers exactly these, so nothing a scan reports is
            # missing from the copy-tax surface
            assert set(v["per_stage"]) <= set(memtrace.STAGES), v
        # the materialize take still happens exactly once per scan
        assert cold["per_stage"]["materialize"]["copy"] >= 1
        assert warm["per_stage"]["materialize"]["copy"] >= 1
