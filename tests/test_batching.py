"""Query batcher (server/batching.py) + the stacked kernel
(ops/aggregate.stacked_downsample).

The contract under test, end to end:

- **Bit-exact parity**: coalesced results equal solo execution
  (HORAEDB_BATCH=off) for every stacked shape — property-swept across
  padded bucket sizes (row/series/batch axes all land in different
  power-of-two classes), mixed tenants holding their own admission
  slots, filtered + unfiltered members sharing one union scan, and
  mid-batch deadline expiry (the expiring member 504s, the group
  completes for everyone else).
- **The lone-query fast path**: no concurrent batchable company means
  an immediate solo launch — batched_with=1, no window stage recorded.
- **Deadlines and honesty**: a budget that cannot cover the window
  launches solo; HORAEDB_BATCH=off forces solo.
- **CostModel attribution**: amortized batched samples must not pollute
  the solo EWMA the admission gate prices (the regression the
  batched_with flag exists for).
- **Config**: [metric_engine.query.batching] round-trips through TOML
  with deny-unknown-fields and validate() bounds.
"""

import asyncio
import os

import numpy as np
import pytest

from horaedb_tpu.common.deadline import Deadline, deadline_scope
from horaedb_tpu.common.error import DeadlineExceeded
from horaedb_tpu.engine import MetricEngine, QueryRequest
from horaedb_tpu.server import batching
from horaedb_tpu.server.batching import (
    SOLO,
    BatchingConfig,
    QueryBatcher,
    pow2ceil,
)
from horaedb_tpu.storage import scanstats
from tests.conftest import async_test

ms = __import__(
    "horaedb_tpu.common.time_ext", fromlist=["ReadableDuration"]
).ReadableDuration.millis

BASE = 1_700_000_000_000


@pytest.fixture(autouse=True)
def _batch_env(monkeypatch):
    """Batching on, serving off (every query real-scans, so the batcher
    — not the result cache — is what the assertions exercise), and a
    fresh planner state per test."""
    monkeypatch.delenv("HORAEDB_BATCH", raising=False)
    monkeypatch.setenv("HORAEDB_SERVING", "off")
    g = batching.GLOBAL_BATCHER
    saved = g.config
    g.configure(BatchingConfig())
    g._groups.clear()
    g._active.clear()
    yield
    g.configure(saved)
    g._groups.clear()
    g._active.clear()


def make_payload(metric=b"batch_cpu", n_series=16, n_samples=30,
                 value=lambda s, i: float(s * 1000 + i)):
    from horaedb_tpu.pb import remote_write_pb2

    req = remote_write_pb2.WriteRequest()
    for s in range(n_series):
        series = req.timeseries.add()
        for k, v in ((b"__name__", metric),
                     (b"host", f"h{s:03d}".encode())):
            lab = series.labels.add()
            lab.name = k
            lab.value = v
        for i in range(n_samples):
            smp = series.samples.add()
            smp.timestamp = BASE + i * 1000
            smp.value = value(s, i)
    return req.SerializeToString()


async def open_engine(store, **kw):
    return await MetricEngine.open("db", store, enable_compaction=False,
                                   **kw)


def assert_same_result(got, want, ctx=""):
    assert (got is None) == (want is None), ctx
    if got is None:
        return
    g_tsids, g_grids = got
    w_tsids, w_grids = want
    assert g_tsids == w_tsids, ctx
    for k in ("sum", "count", "min", "max"):
        assert np.array_equal(g_grids[k], w_grids[k]), f"{ctx}:{k}"
    assert np.array_equal(
        np.nan_to_num(g_grids["mean"], nan=1e300),
        np.nan_to_num(w_grids["mean"], nan=1e300),
    ), f"{ctx}:mean"


class TestShapeClasses:
    def test_pow2ceil(self):
        assert [pow2ceil(n) for n in (1, 2, 3, 7, 8, 9)] == \
            [1, 2, 4, 8, 8, 16]

    def test_same_step_window_same_class(self):
        b = QueryBatcher()
        assert b.shape_key(5000, 12, 5) == b.shape_key(5000, 12, 8)
        assert b.shape_key(5000, 12, 8) != b.shape_key(5000, 12, 9)
        assert b.shape_key(5000, 12, 8) != b.shape_key(1000, 12, 8)
        assert b.shape_key(5000, 12, 8) != b.shape_key(5000, 13, 8)

    def test_cell_cap_bounds_group(self):
        b = QueryBatcher(BatchingConfig(max_stacked_cells=100))
        assert b._max_group_for(8, 4) == 3  # 100 // 32
        assert b._max_group_for(64, 4) == 0  # cannot fit two members


class TestStackedKernelProperty:
    """Property sweep: the stacked kernel equals per-query
    downsample_sorted bit-for-bit across padded bucket sizes (batch,
    row, and series axes in different power-of-two classes)."""

    def test_parity_across_padded_shapes(self):
        from horaedb_tpu.ops import aggregate as agg

        rng = np.random.default_rng(42)
        for B, rpad, S, T in [(2, 32, 1, 4), (3, 64, 8, 6),
                              (5, 128, 16, 3), (8, 64, 3, 10)]:
            bucket_ms = 1000
            ts_b = np.zeros((B, rpad), np.int64)
            sid_b = np.zeros((B, rpad), np.int32)
            val_b = np.zeros((B, rpad), np.float64)
            ok_b = np.zeros((B, rpad), bool)
            t0_b = np.zeros((B,), np.int64)
            keys_b = np.zeros((2, B, rpad), np.int64)
            solo = []
            for q in range(B):
                n = int(rng.integers(0, rpad))
                sid = np.sort(rng.integers(0, S, n)).astype(np.int32)
                ts = rng.integers(0, T * bucket_ms, n).astype(np.int64)
                order = np.lexsort((ts, sid))
                sid, ts = sid[order], ts[order]
                t0 = int(q * 7919)
                ts = ts + t0
                # quarter-integer values: binary-exact sums, so parity
                # really is bit-exact, not tolerance-exact
                vals = rng.integers(-1000, 1000, n).astype(np.float64) / 4
                out = agg.downsample_sorted(
                    ts, sid, vals, t0, bucket_ms,
                    num_series=S, num_buckets=T,
                )
                solo.append({k: np.asarray(v) for k, v in out.items()})
                ts_b[q, :n] = ts
                sid_b[q, :n] = sid
                val_b[q, :n] = vals
                ok_b[q, :n] = True
                t0_b[q] = t0
                keys_b[0, q, :n], keys_b[1, q, :n] = agg.f64_order_keys(vals)
            stacked = dict(agg.stacked_downsample(
                ts_b, sid_b, val_b, ok_b, t0_b, bucket_ms,
                num_series=S, num_buckets=T, order_keys=tuple(keys_b),
            ))
            for k in ("min", "max"):
                stacked[k] = agg.f64_from_order_keys(np.asarray(stacked[k]))
            for q in range(B):
                for k in ("sum", "count", "min", "max"):
                    assert np.array_equal(
                        np.asarray(stacked[k])[q], solo[q][k]
                    ), (B, rpad, S, T, q, k)
                assert np.array_equal(
                    np.nan_to_num(np.asarray(stacked["mean"])[q],
                                  nan=1e300),
                    np.nan_to_num(solo[q]["mean"], nan=1e300),
                ), (B, rpad, S, T, q, "mean")


    @pytest.mark.parametrize("with_nan", [False, True])
    def test_order_key_lane_is_the_float_minmax_bit_for_bit(self, with_nan):
        """min/max over the i64 order keys riding next to the f64 value
        lane equal numpy's float reduction: empty cells, masked rows, NaN
        cells and magnitudes no f32 exponent holds included."""
        from horaedb_tpu.ops import aggregate as agg

        rng = np.random.default_rng(7)
        B, R, S, T = 3, 64, 4, 5
        ts_b = rng.integers(0, T * 1000, (B, R)).astype(np.int64)
        sid_b = rng.integers(0, S - 1, (B, R)).astype(np.int32)  # last series empty
        val_b = rng.uniform(-50, 50, (B, R))
        val_b[:, 1::7] *= 1e300
        val_b[:, 2::7] *= 1e-300
        if with_nan:
            val_b[:, ::9] = np.nan
        ok_b = rng.random((B, R)) < 0.8
        t0_b = np.zeros((B,), np.int64)
        want_mn = np.full((B, S, T), np.inf)
        want_mx = np.full((B, S, T), -np.inf)
        for j, r in np.ndindex(B, R):
            if ok_b[j, r]:
                cell = (j, sid_b[j, r], ts_b[j, r] // 1000)
                with np.errstate(invalid="ignore"):
                    want_mn[cell] = np.minimum(want_mn[cell], val_b[j, r])
                    want_mx[cell] = np.maximum(want_mx[cell], val_b[j, r])
        kmin = np.zeros((B, R), np.int64)
        kmax = np.zeros((B, R), np.int64)
        for j in range(B):
            kmin[j], kmax[j] = agg.f64_order_keys(val_b[j])
        got = agg.stacked_downsample(
            ts_b, sid_b, val_b, ok_b, t0_b, 1000, num_series=S, num_buckets=T,
            order_keys=(kmin, kmax))
        for k, want in (("min", want_mn), ("max", want_mx)):
            assert np.asarray(got[k]).dtype == np.int64
            back = agg.f64_from_order_keys(np.asarray(got[k]))
            np.testing.assert_array_equal(
                np.nan_to_num(back, nan=1e300).view(np.int64),
                np.nan_to_num(want, nan=1e300).view(np.int64),
            )


class TestEngineParity:
    """Engine-level property test: a concurrent burst of compatible
    panels coalesces (batched_with > 1) and every answer equals the
    HORAEDB_BATCH=off oracle bit-for-bit."""

    @async_test
    async def test_burst_parity_across_bucket_sizes(self, mem_store):
        eng = await open_engine(mem_store)
        try:
            await eng.write_payload(make_payload(n_series=16))
            await eng.flush()
            # all three bucket sizes divide the 2h segment AND align
            # with BASE — the eligibility contract for the stacked lane
            for bucket_ms in (5000, 10000, 2000):
                reqs = [
                    QueryRequest(
                        metric=b"batch_cpu", start_ms=BASE,
                        end_ms=BASE + 30_000, bucket_ms=bucket_ms,
                        filters=[(b"host", f"h{s:03d}".encode())],
                    )
                    for s in range(7)
                ]
                os.environ["HORAEDB_BATCH"] = "off"
                solo = [await eng.query(r) for r in reqs]
                os.environ.pop("HORAEDB_BATCH", None)
                counts = [None] * len(reqs)

                async def one(i, reqs=reqs, counts=counts):
                    with scanstats.scan_stats() as st:
                        r = await eng.query(reqs[i])
                    counts[i] = dict(st.counts)
                    return r

                got = await asyncio.gather(
                    *(one(i) for i in range(len(reqs)))
                )
                for i, (g, w) in enumerate(zip(got, solo)):
                    assert_same_result(g, w, f"bucket={bucket_ms} q={i}")
                bw = [c.get("batched_with") for c in counts]
                assert any(x and x > 1 for x in bw), bw
        finally:
            await eng.close()

    @async_test
    async def test_shared_union_scan_with_unfiltered_member(self,
                                                            mem_store):
        """Filtered multi-host panels + an unfiltered (whole-metric)
        panel in one class: one union scan serves the cluster, every
        demuxed answer stays exact."""
        eng = await open_engine(mem_store)
        try:
            await eng.write_payload(make_payload(n_series=8))
            await eng.flush()
            reqs = [
                QueryRequest(
                    metric=b"batch_cpu", start_ms=BASE,
                    end_ms=BASE + 30_000, bucket_ms=5000,
                    filters=[(b"host", f"h{s:03d}".encode()),
                             ] if s >= 0 else [],
                )
                for s in range(5)
            ]
            # two multi-host members via matchers land in the same
            # series class as the full set
            reqs.append(QueryRequest(
                metric=b"batch_cpu", start_ms=BASE, end_ms=BASE + 30_000,
                bucket_ms=5000,
                matchers=[(b"host", "re", b"h00[0-4]")],
            ))
            os.environ["HORAEDB_BATCH"] = "off"
            solo = [await eng.query(r) for r in reqs]
            os.environ.pop("HORAEDB_BATCH", None)
            shared = []

            async def one(r):
                with scanstats.scan_stats() as st:
                    out = await eng.query(r)
                shared.append(st.counts.get("batch_shared_scans"))
                return out

            got = await asyncio.gather(*(one(r) for r in reqs))
            for i, (g, w) in enumerate(zip(got, solo)):
                assert_same_result(g, w, f"q={i}")
            assert any(s for s in shared if s), shared
        finally:
            await eng.close()

    @async_test
    async def test_mixed_tenants_keep_fairness_and_exactness(self,
                                                             mem_store):
        """Members of different tenants coalesce into one launch while
        each holds its own admission slot (inflight/metering unchanged
        by batching), and results stay exact."""
        from horaedb_tpu.server.admission import (
            AdmissionController,
            run_query,
        )

        eng = await open_engine(mem_store)
        try:
            await eng.write_payload(make_payload(n_series=8))
            await eng.flush()
            reqs = [
                QueryRequest(
                    metric=b"batch_cpu", start_ms=BASE,
                    end_ms=BASE + 30_000, bucket_ms=5000,
                    filters=[(b"host", f"h{s:03d}".encode())],
                )
                for s in range(6)
            ]
            os.environ["HORAEDB_BATCH"] = "off"
            solo = [await eng.query(r) for r in reqs]
            os.environ.pop("HORAEDB_BATCH", None)
            ctl = AdmissionController(max_concurrent=8)
            tenants = ["alpha", "beta", "gamma"]
            counts = [None] * len(reqs)

            async def one(i):
                with scanstats.scan_stats() as st:
                    out, slot = await run_query(
                        ctl, eng, reqs[i], tenant=tenants[i % 3],
                        cells=6 * 1,
                    )
                counts[i] = dict(st.counts)
                assert slot.tenant == tenants[i % 3]
                return out

            got = await asyncio.gather(*(one(i) for i in range(len(reqs))))
            for i, (g, w) in enumerate(zip(got, solo)):
                assert_same_result(g, w, f"tenant q={i}")
            assert any(
                (c.get("batched_with") or 0) > 1 for c in counts
            ), counts
            assert ctl.inflight == 0  # every slot released
        finally:
            await eng.close()

    @async_test
    async def test_unaligned_grid_runs_solo(self, mem_store):
        """A grid whose start is not bucket-aligned could put a segment
        boundary inside a bucket — outside the stacked lane's
        bit-exactness condition, so it must run solo even with
        company (and still equal the off-oracle)."""
        eng = await open_engine(mem_store)
        try:
            await eng.write_payload(make_payload(n_series=8))
            await eng.flush()
            reqs = [
                QueryRequest(
                    metric=b"batch_cpu", start_ms=BASE + 1,
                    end_ms=BASE + 30_001, bucket_ms=5000,
                    filters=[(b"host", f"h{s:03d}".encode())],
                )
                for s in range(6)
            ]
            os.environ["HORAEDB_BATCH"] = "off"
            solo = [await eng.query(r) for r in reqs]
            os.environ.pop("HORAEDB_BATCH", None)
            counts = [None] * len(reqs)

            async def one(i):
                with scanstats.scan_stats() as st:
                    r = await eng.query(reqs[i])
                counts[i] = dict(st.counts)
                return r

            got = await asyncio.gather(*(one(i) for i in range(len(reqs))))
            for i, (g, w) in enumerate(zip(got, solo)):
                assert_same_result(g, w, f"unaligned q={i}")
            assert all(c.get("batched_with") == 1 for c in counts), counts
        finally:
            await eng.close()

    @async_test
    async def test_cross_segment_cancellation_stays_exact(self,
                                                          mem_store):
        """Catastrophic float cancellation across a segment boundary
        (the case where a single-stream reduction and the per-segment
        partial fold differ in association): a bucket wider than the
        segment is ineligible for the stacked lane, so concurrent
        queries still equal the solo oracle bit-for-bit."""
        from horaedb_tpu.pb import remote_write_pb2

        HOUR = 3_600_000
        eng = await MetricEngine.open(
            "db", mem_store, segment_duration_ms=HOUR,
            enable_compaction=False,
        )
        try:
            req = remote_write_pb2.WriteRequest()
            for h in range(3):
                series = req.timeseries.add()
                for k, v in ((b"__name__", b"cancel_cpu"),
                             (b"host", f"h{h}".encode())):
                    lab = series.labels.add()
                    lab.name = k
                    lab.value = v
                for t, v in ((0, 1e16), (1000, 1.0),
                             (HOUR, -1e16), (HOUR + 1000, 1.0)):
                    smp = series.samples.add()
                    smp.timestamp = t
                    smp.value = v
            await eng.write_payload(req.SerializeToString())
            await eng.flush()
            reqs = [
                QueryRequest(
                    metric=b"cancel_cpu", start_ms=0, end_ms=2 * HOUR,
                    bucket_ms=2 * HOUR,  # one bucket spanning 2 segments
                    filters=[(b"host", f"h{h}".encode())],
                )
                for h in range(3)
            ]
            os.environ["HORAEDB_BATCH"] = "off"
            solo = [await eng.query(r) for r in reqs]
            os.environ.pop("HORAEDB_BATCH", None)
            counts = [None] * len(reqs)

            async def one(i):
                with scanstats.scan_stats() as st:
                    r = await eng.query(reqs[i])
                counts[i] = dict(st.counts)
                return r

            got = await asyncio.gather(*(one(i) for i in range(len(reqs))))
            for i, (g, w) in enumerate(zip(got, solo)):
                assert_same_result(g, w, f"cancel q={i}")
            # 2h bucket over 1h segments: never batched
            assert all(c.get("batched_with") == 1 for c in counts), counts
        finally:
            await eng.close()

    @async_test
    async def test_lone_query_is_solo_with_no_window_penalty(self,
                                                             mem_store):
        eng = await open_engine(mem_store)
        try:
            await eng.write_payload(make_payload(n_series=4))
            await eng.flush()
            req = QueryRequest(
                metric=b"batch_cpu", start_ms=BASE, end_ms=BASE + 30_000,
                bucket_ms=5000, filters=[(b"host", b"h001")],
            )
            with scanstats.scan_stats() as st:
                out = await eng.query(req)
            assert out is not None
            assert st.counts.get("batched_with") == 1
            # no hold: the window stage never ran
            assert "batch_window" not in st.seconds
        finally:
            await eng.close()

    @async_test
    async def test_short_deadline_launches_solo(self, mem_store):
        eng = await open_engine(mem_store)
        try:
            await eng.write_payload(make_payload(n_series=4))
            await eng.flush()
            batching.GLOBAL_BATCHER.configure(
                BatchingConfig(max_delay=ms(100))
            )
            req = QueryRequest(
                metric=b"batch_cpu", start_ms=BASE, end_ms=BASE + 30_000,
                bucket_ms=5000, filters=[(b"host", b"h001")],
            )
            # fake company so the lone-query fast path does not trigger
            tok = batching.GLOBAL_BATCHER.begin()
            try:
                with scanstats.scan_stats() as st, \
                        deadline_scope(Deadline(0.05)):
                    out = await eng.query(req)
            finally:
                batching.GLOBAL_BATCHER.end(tok)
            assert out is not None
            assert st.counts.get("batched_with") == 1
            assert "batch_window" not in st.seconds
        finally:
            await eng.close()

    @async_test
    async def test_env_off_forces_solo(self, mem_store):
        eng = await open_engine(mem_store)
        try:
            await eng.write_payload(make_payload(n_series=4))
            await eng.flush()
            os.environ["HORAEDB_BATCH"] = "off"
            req = QueryRequest(
                metric=b"batch_cpu", start_ms=BASE, end_ms=BASE + 30_000,
                bucket_ms=5000, filters=[(b"host", b"h001")],
            )

            async def one():
                with scanstats.scan_stats() as st:
                    await eng.query(req)
                return st.counts.get("batched_with")

            bw = await asyncio.gather(*(one() for _ in range(4)))
            assert all(x is None for x in bw), bw  # never reached a note
        finally:
            await eng.close()


class TestMidBatchDeadline:
    """A member whose end-to-end deadline dies while its group executes
    504s individually; the group still completes exactly for the rest."""

    @async_test
    async def test_expiring_member_504s_group_survives(self):
        b = QueryBatcher(BatchingConfig(max_delay=ms(30)))
        # concurrency signal so nobody takes the lone path
        toks = [b.begin(), b.begin()]
        gate = asyncio.Event()

        n, t = 30, 4
        sids = np.arange(3, dtype=np.uint64)

        async def slow_scan(ids):
            await gate.wait()
            ts = np.arange(n, dtype=np.int64) * 1000
            tsid = np.repeat(np.arange(3, dtype=np.uint64), 10)
            vals = np.arange(n, dtype=np.float64)
            return ts, tsid, vals

        async def member(budget_s, key):
            with deadline_scope(Deadline(budget_s)):
                return await b.coalesce(
                    bucket_ms=10_000, num_buckets=t, series_ids=sids,
                    t0=0, filtered=True, share_key=key,
                    scan=slow_scan,
                )

        async def run():
            t_short = asyncio.create_task(member(0.25, "a"))
            t_long = asyncio.create_task(member(30.0, "b"))
            await asyncio.sleep(0.6)  # window closed, scans gated
            gate.set()
            return t_short, t_long

        t_short, t_long = await run()
        with pytest.raises(DeadlineExceeded):
            await t_short
        res, notes = await t_long
        assert res is not None
        assert np.array_equal(res["count"].sum(axis=1), [10, 10, 10])
        # honest provenance: the launch WAS shared by both members' rows
        # (the expired caller just stopped listening for its slice)
        assert notes["batched_with"] == 2
        for t in toks:
            b.end(t)

    @async_test
    async def test_too_short_budget_never_joins_a_window(self):
        """Eligibility guard: a budget that cannot cover the window +
        a stacked execution goes solo immediately — it must never be
        parked in a group it would abandon anyway."""
        b = QueryBatcher(BatchingConfig(max_delay=ms(200)))
        # company exists, so only the deadline guard saves it
        toks = [b.begin(), b.begin()]
        sids = np.arange(2, dtype=np.uint64)

        async def scan(ids):  # pragma: no cover — must never run
            raise AssertionError("solo_deadline decision must not scan")

        with scanstats.scan_stats() as st, deadline_scope(Deadline(0.05)):
            res = await b.coalesce(
                bucket_ms=1000, num_buckets=2, series_ids=sids,
                t0=0, filtered=True, share_key="x", scan=scan,
            )
        assert res is SOLO
        assert st.counts.get("batched_with") == 1
        assert not b._groups
        for t in toks:
            b.end(t)

    @async_test
    async def test_all_members_cancelling_empties_the_group(self):
        """Client disconnects while coalescing: abandoned members leave
        the window; a fully-abandoned group never scans and leaves no
        pending state behind."""
        b = QueryBatcher(BatchingConfig(max_delay=ms(150)))
        toks = [b.begin(), b.begin()]
        sids = np.arange(2, dtype=np.uint64)

        async def scan(ids):  # pragma: no cover — must never run
            raise AssertionError("abandoned group must not scan")

        async def member():
            return await b.coalesce(
                bucket_ms=1000, num_buckets=2, series_ids=sids,
                t0=0, filtered=True, share_key="x", scan=scan,
            )

        t1 = asyncio.create_task(member())
        t2 = asyncio.create_task(member())
        await asyncio.sleep(0.02)  # both joined the window
        assert b._groups
        t1.cancel()
        t2.cancel()
        for t in (t1, t2):
            with pytest.raises(asyncio.CancelledError):
                await t
        assert not b._groups  # last abandon tore the group down
        await asyncio.sleep(0.2)  # a stray timer firing must be a no-op
        assert not b._groups
        for t in toks:
            b.end(t)


class TestOverflowDemotion:
    """A member whose materialized scan would blow the stacked buffer's
    max_rows budget demotes to the solo path (largest first); the rest
    of the group still launches stacked."""

    @async_test
    async def test_oversized_member_demotes_to_solo(self):
        b = QueryBatcher(BatchingConfig(max_delay=ms(30), max_rows=256))
        toks = [b.begin(), b.begin(), b.begin()]
        sids = np.arange(2, dtype=np.uint64)

        def rows(n):
            ts = np.arange(n, dtype=np.int64)
            tsid = np.zeros(n, dtype=np.uint64)
            vals = np.ones(n, dtype=np.float64)
            return ts, tsid, vals

        async def scan_small(ids):
            return rows(20)

        async def scan_huge(ids):
            return rows(300)  # pads to 512 > 256 budget

        async def member(scan, key):
            with scanstats.scan_stats() as st:
                res = await b.coalesce(
                    bucket_ms=1000, num_buckets=2, series_ids=sids,
                    t0=0, filtered=True, share_key=key, scan=scan,
                )
            return res, dict(st.counts)

        outs = await asyncio.gather(
            member(scan_small, "a"),
            member(scan_small, "b"),
            member(scan_huge, "c"),
        )
        stacked = [o for o in outs if o[0] is not SOLO]
        demoted = [o for o in outs if o[0] is SOLO]
        assert len(demoted) == 1 and len(stacked) == 2, outs
        # demoted member fell back with batched_with=1 noted
        assert demoted[0][1].get("batched_with") == 1
        for res, _ in stacked:
            grids, notes = res
            assert notes["batched_with"] == 2
            assert grids["count"].sum() == 20
        for t in toks:
            b.end(t)


class TestCostModelAttribution:
    """Satellite regression: amortized batched samples must not pollute
    the solo per-cell EWMA (or the compiled-shape set) the admission
    gate prices with."""

    def test_batched_observe_leaves_solo_ewma_alone(self):
        from horaedb_tpu.server.admission import CostModel

        cm = CostModel()
        seed = cm.per_cell_s
        cm.observe(10_000, 2.0, batched_with=8)
        assert cm.per_cell_s == seed
        assert cm._shapes == set()
        # the amortized EWMA learned the per-member share
        assert cm.per_cell_batched_s == pytest.approx(
            (2.0 / 8) / 10_000
        )
        # solo samples still train the gate's EWMA
        cm.observe(10_000, 2.0)
        assert cm.per_cell_s != seed
        assert cm._shapes

    def test_batched_ewma_converges_independently(self):
        from horaedb_tpu.server.admission import CostModel

        cm = CostModel(alpha=0.5)
        for _ in range(20):
            cm.observe(1000, 1.0, batched_with=4)
        assert cm.per_cell_batched_s == pytest.approx(0.25 / 1000,
                                                      rel=0.05)
        assert cm.per_cell_s == cm.PER_CELL_SEED

    @async_test
    async def test_slot_reads_batched_with_from_collector(self):
        from horaedb_tpu.server.admission import AdmissionController

        ctl = AdmissionController(max_concurrent=2)
        seed = ctl.cost_model.per_cell_s
        with scanstats.scan_stats():
            async with ctl.slot("t", cells=500):
                scanstats.note_max("batched_with", 4)
                await asyncio.sleep(0.01)
        assert ctl.cost_model.per_cell_s == seed
        assert ctl.cost_model.per_cell_batched_s is not None


class TestConfig:
    def test_toml_round_trip(self):
        from horaedb_tpu.server.config import Config

        c = Config.from_toml(
            "[metric_engine.query.batching]\n"
            "enabled = false\n"
            "max_delay = \"10ms\"\n"
            "max_group = 4\n"
            "max_stacked_cells = 65536\n"
            "max_rows = 4096\n"
        )
        b = c.metric_engine.query.batching
        assert (b.enabled, b.max_group, b.max_stacked_cells,
                b.max_rows) == (False, 4, 65536, 4096)
        assert b.max_delay.seconds == pytest.approx(0.01)
        c.validate()

    def test_unknown_key_rejected(self):
        from horaedb_tpu.common.error import HoraeError
        from horaedb_tpu.server.config import Config

        with pytest.raises(HoraeError):
            Config.from_toml("[metric_engine.query.batching]\nnope = 1")

    def test_validate_bounds(self):
        from horaedb_tpu.common.error import HoraeError
        from horaedb_tpu.server.config import Config

        c = Config.from_toml(
            "[metric_engine.query.batching]\nmax_group = 1\n"
        )
        with pytest.raises(HoraeError):
            c.validate()

    def test_example_toml_carries_the_block(self):
        from horaedb_tpu.server.config import Config

        c = Config.from_file("docs/example.toml")
        c.validate()
        assert c.metric_engine.query.batching.enabled is True


class TestExplain:
    def test_explain_payload_carries_batching_verdict(self):
        from horaedb_tpu.server.main import _explain_payload

        with scanstats.scan_stats() as st:
            scanstats.note_max("batched_with", 5)
            scanstats.note("batch_pad_waste_pct", 40)
            scanstats.note("batch_class_b5000_t6_s8", 1)
            scanstats.record("batch_window", 0.002)
        p = _explain_payload(st, "downsample")
        assert p["batching"]["batched_with"] == 5
        assert p["batching"]["pad_waste_pct"] == 40
        assert p["batching"]["shape_class"] == "b5000_t6_s8"
        assert p["batching"]["window_wait_s"] == pytest.approx(0.002)
        assert p["stages_s"]["batch_window"] == pytest.approx(0.002)

    def test_explain_without_batching_is_null_verdict(self):
        from horaedb_tpu.server.main import _explain_payload

        with scanstats.scan_stats() as st:
            pass
        p = _explain_payload(st, "raw")
        assert p["batching"]["batched_with"] is None
        assert p["batching"]["window_wait_s"] == 0.0
