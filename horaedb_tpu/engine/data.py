"""SampleManager: sample persistence + the device-side query pipeline.

Implements the reference's `SampleManager::persist` skeleton
(src/metric_engine/src/data/mod.rs:34-41, dead code in the snapshot): raw
sample rows land in the `data` table bucketed per time segment (a storage
write must not cross a segment, storage.rs:307-316), and queries run the
storage scan with (metric_id eq + TSID set-membership + time range)
predicates followed by on-device aggregation.
"""

from __future__ import annotations

import asyncio
import logging
import os

import numpy as np
import pyarrow as pa

from horaedb_tpu.common import colblock, memtrace, tracing
from horaedb_tpu.common.aio import TaskGroup
from horaedb_tpu.engine.flush_executor import (
    FLUSH_FAILURES_TOTAL,
    FLUSH_OVERLAP_RATIO,
    FlushExecutor,
    SealedMemtable,
)
from horaedb_tpu.engine.tables import DATA_SCHEMA
from horaedb_tpu.ops import aggregate as agg_ops
from horaedb_tpu.ops import filter as F
from horaedb_tpu.server.metrics import GLOBAL_METRICS
from horaedb_tpu.storage import scanstats
from horaedb_tpu.storage.read import ScanRequest, WriteRequest
from horaedb_tpu.storage.storage import ObjectBasedStorage
from horaedb_tpu.storage.types import TimeRange

logger = logging.getLogger(__name__)

FLUSH_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_ingest_flush_seconds",
    help="One buffered-ingest write-out (snapshot detach -> SSTs durable), "
         "by table root (region-qualified on regioned deployments).",
    labelnames=("table",),
)
FLUSH_ROWS = GLOBAL_METRICS.counter(
    "horaedb_ingest_flush_rows_total",
    help="Rows made durable by ingest flush write-outs.",
    labelnames=("table",),
)
FLUSH_FAILURES = GLOBAL_METRICS.counter(
    "horaedb_ingest_flush_failures_total",
    help="Failed write-outs (rows re-buffered for retry).",
    labelnames=("table",),
)
LATE_SAMPLES = GLOBAL_METRICS.counter(
    "horaedb_late_samples_total",
    help="Out-of-order/backfill samples: appended rows whose timestamp "
         "falls in a segment OLDER than the active one (the ingest low "
         "watermark). Routed to per-time-partition late buffers and "
         "flushed as ordinary per-segment SSTs; reads stay exact via "
         "merge-dedup. A sustained rate means lagging agents or a "
         "backfill import.",
    labelnames=("table",),
)


# Above this series cardinality the dense pushdown grid (num_series x
# num_buckets x 4 stats) and the device membership probe stop paying off;
# fall back to materializing + np.unique sizing by the rows actually in range.
MAX_PUSHDOWN_SERIES = 65_536

# Resolution guard: a downsample query must not demand an absurd number of
# buckets (start=0, bucket=1m would be a ~30M-bucket grid per series) —
# reject loudly, like Prometheus's max-resolution limit.
MAX_BUCKETS = 100_000

# In-flight per-segment pushdown scans PER SampleManager (shared across
# concurrent queries — a dashboard burst cannot multiply it).
SEGMENT_SCAN_CONCURRENCY = 4

# Shared read-only zeros arena for the constant field_id column: flush
# shards would otherwise allocate + zero-fill a fresh u64 lane per write
# (pyarrow wraps the view zero-copy; the batch never mutates it).
_ZEROS_U64 = np.zeros(0, dtype=np.uint64)


def _zeros_u64(n: int) -> np.ndarray:
    global _ZEROS_U64
    if len(_ZEROS_U64) < n:
        z = np.zeros(max(n, 2 * len(_ZEROS_U64), 4096), dtype=np.uint64)
        z.setflags(write=False)
        _ZEROS_U64 = z
    return _ZEROS_U64[:n]


class SampleManager:
    def __init__(
        self,
        storage: ObjectBasedStorage,
        segment_duration_ms: int,
        buffer_rows: int = 0,
        flush_workers: int = 2,
        flush_queue_max: int = 4,
        flush_stall_deadline_s: float = 30.0,
        serving=None,
    ):
        self._storage = storage
        self._segment_duration = segment_duration_ms
        # Serving tier handle (horaedb_tpu/serving.ServingTier) — the
        # query methods below are the ONE planner choke point where the
        # result cache and rollup substitution are consulted (jaxlint
        # J013). None = tier absent (storage-level tests).
        self._serving = serving
        # Observability identity: the storage root is region-qualified
        # ("metrics/region-0/data") so flush logs/metrics name the region.
        self._table_id = getattr(storage, "_root", None) or "data"
        # the table's flush stages in the one stage funnel: this manager
        # observes `drain`, the storage's writes the rest
        self._flush = scanstats.flush_family(self._table_id)
        # pre-register the flush families' children so /metrics exposes
        # them (zero state) before the first write-out
        for fam in (FLUSH_SECONDS, FLUSH_ROWS, FLUSH_FAILURES, LATE_SAMPLES):
            fam.labels(self._table_id)
        # Out-of-order/backfill low watermark: the max sample timestamp
        # this manager has ever buffered. A sample in a segment OLDER than
        # the watermark's is LATE: counted, and on the column-memtable
        # path ROUTED into per-time-partition buffers (self._buf, the
        # persist()-path per-segment dict that rides the same seal/replay
        # machinery) so the hot columnar drain keeps its O(n)
        # ts-monotone fast path and one backfill trickle cannot force a
        # full lexsort of the whole memtable.
        self._high_wm: int | None = None
        # Opt-in ingest buffering (the RFC's own data-table design batches
        # many samples per stored row, docs/rfcs/20240827-metric-engine.md
        # :218-232): rows accumulate per segment and flush as ONE storage
        # write when the total reaches buffer_rows. 0 = unbuffered — every
        # persist() is immediately durable, matching the reference's
        # write==SST contract (storage.rs:307-333). Buffered rows are NOT
        # durable until flush; queries flush first so reads stay consistent.
        self._buffer_rows = buffer_rows
        self._buf: dict[int, list[tuple[np.ndarray, ...]]] = {}
        # Dense-id column memtable: (metric_id, tsid) -> small dense int,
        # plus PREALLOCATED (dense-per-sample, ts, value) column arrays
        # appended in place (zero-copy drain: sealing hands over array
        # views; there is no flush-time concatenate and no per-row emit).
        # Flush counting-sorts by the pk rank of each dense id — O(n + k)
        # — and emits batches already in pk order so the storage write's
        # sortedness fast path skips its sort.
        self._dense: dict[tuple[int, int], int] = {}
        self._dense_keys: list[tuple[int, int]] = []
        self._cols: tuple[np.ndarray, np.ndarray, np.ndarray] | None = None
        self._fill = 0
        # recycled column backings (double-buffer arena: a successful
        # write-out returns its arrays here instead of the allocator)
        self._spare_cols: list[tuple[np.ndarray, ...]] = []
        self._buffered = 0
        # monotonic append counter — feeds the flush overlap-ratio metric
        self._appended_rows = 0
        # Native C++ accumulator (ingest/native.py NativeAccum): samples go
        # straight from the parser arena into C++ lanes, flushed pk-sorted.
        # None when the native library is unavailable (Python chunk buffer
        # serves instead).
        self._accum = None
        if buffer_rows > 0:
            try:
                from horaedb_tpu.ingest.native import NativeAccum

                self._accum = NativeAccum()
            except Exception:  # noqa: BLE001 — fall back to Python buffering
                self._accum = None
        # The overlapped ingest->flush pipeline (engine/flush_executor.py):
        # threshold flushes SEAL the active memtable (atomic swap on the
        # event loop — appends land in fresh buffers) and hand it to a
        # bounded background worker pool, so the append path never blocks
        # on drain/encode/upload. A full queue blocks appends on a
        # condition variable with a deadline (backpressure, never a drop);
        # flush() remains the strong barrier queries use.
        self._executor: "FlushExecutor | None" = None
        if buffer_rows > 0:
            self._executor = FlushExecutor(
                self._writeout_once,
                self._table_id,
                workers=flush_workers,
                queue_max=flush_queue_max,
                stall_deadline_s=flush_stall_deadline_s,
            )
        # bounded concurrent object-store PUTs across the flush pipeline
        # (lazy: binds the running loop)
        self._upload_sem: "asyncio.Semaphore | None" = None
        # shared bound for concurrent segment-pushdown scans (lazy: binds
        # the running loop)
        self._scan_sem: "asyncio.Semaphore | None" = None

    @property
    def buffering(self) -> bool:
        return self._buffer_rows > 0

    @property
    def native_accum_active(self) -> bool:
        return self._accum is not None

    def buffer_native_add(self, parser) -> int:
        """Append the parser's current parse into the C++ accumulator
        (engine.write_payload holds the parser borrowed). Returns total
        buffered rows.

        Late-sample accounting rides here too (one ts-lane copy + min/max
        per payload, ~1 ns/sample): the accumulator itself pk-sorts at
        drain and the flush splits by segment, so out-of-order rows are
        CORRECT on this path by construction — the watermark check only
        feeds `horaedb_late_samples_total` and keeps the watermark shared
        with the Python memtable paths."""
        before = self._accum.rows
        total = self._accum.add(parser)
        added = total - before
        # feed the overlap-ratio metric on the native hot path too
        self._appended_rows += added
        if added:
            ts = parser.sample_ts_view()
            if len(ts):
                late = self._late_mask(ts)
                if late is not None:
                    LATE_SAMPLES.labels(self._table_id).inc(
                        int(np.count_nonzero(late))
                    )
        return total

    def _late_mask(self, ts: np.ndarray) -> "np.ndarray | None":
        """Mask of samples whose segment is OLDER than the active segment
        of the PRE-batch high watermark, then advance the watermark — None
        when none are (the common in-order case pays one vectorized
        max/min + two compares). Lateness is judged against the watermark
        as it stood BEFORE this batch: an in-order batch that itself
        straddles a segment rollover must not count its pre-boundary
        samples as late (nothing arrived out of order)."""
        prev = self._high_wm
        mx = int(ts.max())
        if prev is None or mx > prev:
            self._high_wm = mx
        if prev is None:
            return None  # first traffic IS the stream, wherever it starts
        low = prev - prev % self._segment_duration
        if int(ts.min()) >= low:
            return None
        return ts < low

    def should_flush(self, rows: int) -> bool:
        return rows >= self._buffer_rows

    @property
    def buffered_rows(self) -> int:
        """Total rows awaiting durability (native accumulator + active
        Python memtable + sealed memtables queued/parked/in-flight on the
        flush executor)."""
        accum = self._accum.rows if self._accum is not None else 0
        pending = self._executor.pending_rows if self._executor else 0
        return accum + self._buffered + pending

    # Bound on concurrent object-store PUTs from this manager's flush
    # pipeline: several workers x several shards would otherwise fan out
    # encode+upload without limit on a small host.
    MAX_INFLIGHT_UPLOADS = 4

    @property
    def flush_in_flight(self) -> bool:
        return self._executor is not None and self._executor.busy

    @property
    def flush_executor(self) -> "FlushExecutor | None":
        return self._executor

    async def drain(self) -> None:
        """Await the flush queue empty, then flush the remainder
        (shutdown + the periodic flush loop). Loops: a concurrent writer
        may append while we await — exit only once no row is buffered
        anywhere, so nothing is abandoned at loop teardown."""
        if self._executor is None:
            return
        while True:
            await self.flush()
            if not self.buffered_rows:
                return

    @property
    def _has_pending_rows(self) -> bool:
        return bool(
            self._buffered or (self._accum is not None and self._accum.rows)
        )

    async def persist(
        self,
        metric_ids: np.ndarray,  # u64 per sample
        tsids: np.ndarray,       # u64 per sample
        ts: np.ndarray,          # i64 ms per sample
        values: np.ndarray,      # f64 per sample
    ) -> None:
        """One storage write per touched segment, rows sorted on device by
        the write path (or buffered, see __init__). Already per-segment —
        late samples land in their own partition by construction; the
        watermark check only counts them."""
        if len(ts) == 0:
            return
        late = self._late_mask(ts)
        if late is not None:
            LATE_SAMPLES.labels(self._table_id).inc(
                int(np.count_nonzero(late))
            )
        seg = ts - (ts % self._segment_duration)
        uniq = np.unique(seg)
        for seg_start in uniq:
            m = seg == seg_start if len(uniq) > 1 else slice(None)
            if self._buffer_rows > 0:
                chunk = (metric_ids[m], tsids[m], ts[m], values[m])
                self._buf.setdefault(int(seg_start), []).append(chunk)
                self._buffered += len(chunk[2])
                self._appended_rows += len(chunk[2])
            else:
                await self._write_segment(
                    metric_ids[m], tsids[m], ts[m], values[m]
                )
        if self._buffer_rows > 0 and self._buffered >= self._buffer_rows:
            await self.seal_and_submit()

    def _cols_for(self, n: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Active column arrays with room for `n` more rows — pulled from
        the recycled spare pool when a completed flush returned one
        (the double-buffer arena), grown geometrically otherwise."""
        cols = self._cols
        if cols is None:
            # eager capacity is CAPPED: an absurd buffer_rows (bench
            # sentinels, misconfiguration) must not preallocate
            # buffer_rows-sized arrays up front — growth is geometric
            cap = max(min(self._buffer_rows, 4 << 20), n, 1024)
            if self._spare_cols and len(self._spare_cols[-1][0]) >= cap:
                # double-buffer steady state: the previous generation's
                # backing re-issues without an allocation
                cols = self._spare_cols.pop()
                memtrace.track_bytes(
                    sum(int(c.nbytes) for c in cols), "append", "reuse"
                )
            else:
                cols = (
                    colblock.aligned_empty(cap, np.int64),   # dense series id
                    colblock.aligned_empty(cap, np.int64),   # ts
                    colblock.aligned_empty(cap, np.float64),  # value
                )
                for c in cols:
                    memtrace.track(c, "append", "alloc")
            self._cols = cols
        elif self._fill + n > len(cols[0]):
            cap = max(2 * len(cols[0]), self._fill + n)
            grown = tuple(colblock.aligned_empty(cap, c.dtype) for c in cols)
            for g, c in zip(grown, cols):
                memtrace.track(g, "append", "alloc")
                g[: self._fill] = c[: self._fill]
            self._cols = cols = grown
        return cols

    async def buffer_request(self, metric_arr, tsid_arr, req) -> None:
        """Hash-lane buffered ingest: one dense-id dict probe per series,
        then whole-request column appends IN PLACE into the preallocated
        active memtable (no per-request list nodes, no flush-time
        concatenate — the zero-copy drain).

        Out-of-order/backfill samples (segments older than the watermark's
        active segment) are ROUTED OUT into per-time-partition late
        buffers (`self._buf`, the persist()-path per-segment dict, which
        rides the same seal/replay machinery and flushes one SST per
        partition): the hot columnar memtable keeps its ts-monotone O(n)
        drain fast path, and a backfill trickle cannot force a full
        lexsort of everything buffered with it."""
        ts = req.sample_ts
        series_idx = req.sample_series
        vals = req.sample_value
        late = self._late_mask(ts) if len(ts) else None
        if late is not None:
            n_late = int(np.count_nonzero(late))
            LATE_SAMPLES.labels(self._table_id).inc(n_late)
            sel = np.flatnonzero(late)
            l_sidx = series_idx[sel]
            l_ts = ts[sel]
            chunk = (
                np.asarray(metric_arr, dtype=np.uint64)[l_sidx],
                np.asarray(tsid_arr, dtype=np.uint64)[l_sidx],
                l_ts,
                vals[sel],
            )
            seg = l_ts - (l_ts % self._segment_duration)
            uniq = np.unique(seg)
            for seg_start in uniq:
                m = seg == seg_start if len(uniq) > 1 else slice(None)
                self._buf.setdefault(int(seg_start), []).append(
                    tuple(a[m] for a in chunk)
                )
            self._buffered += n_late
            self._appended_rows += n_late
            keep = np.flatnonzero(~late)
            series_idx = series_idx[keep]
            ts = ts[keep]
            vals = vals[keep]
        n = len(ts)
        if n:
            dense = self._dense
            keys = self._dense_keys
            mids = metric_arr.tolist()
            tids = tsid_arr.tolist()
            per_series = np.empty(len(mids), dtype=np.int64)
            for s in range(len(mids)):
                k = (mids[s], tids[s])
                d = dense.get(k)
                if d is None:
                    d = len(keys)
                    dense[k] = d
                    keys.append(k)
                per_series[s] = d
            dcol, tcol, vcol = self._cols_for(n)
            f = self._fill
            dcol[f:f + n] = per_series[series_idx]
            tcol[f:f + n] = ts
            vcol[f:f + n] = vals
            self._fill = f + n
            self._buffered += n
            self._appended_rows += n
        if self._buffered >= self._buffer_rows:
            await self.seal_and_submit()

    def seal(self) -> "SealedMemtable | None":
        """Atomically detach the active memtable into an immutable
        SealedMemtable (the double-buffer swap): no awaits between the
        buffer detach and the accumulator take, so appends racing this
        seal land entirely in the fresh active buffers. Returns None when
        nothing is buffered — two concurrent flush() calls cannot
        double-seal the same rows.

        The memtable's dedup sequence is pinned HERE, so last-value dedup
        follows buffering order even if a later memtable's encode lands
        its SSTs (with higher file ids) first."""
        from horaedb_tpu.storage.sst import allocate_id

        has_accum = self._accum is not None and self._accum.rows
        if not (self._buffered or has_accum):
            return None
        with self._flush.stage("drain"):
            buf, self._buf = self._buf, {}
            keys, self._dense_keys = self._dense_keys, []
            self._dense = {}
            cols_view = None
            backing = None
            block = None
            if self._fill:
                backing = self._cols
                # the sealed rows travel as ONE frozen column block: read-only
                # zero-copy views of the arena's filled prefix (the drain reads
                # them in place — the old recycled-array copy is gone), while
                # the writable backing recycles into the spare pool after the
                # write-out lands
                block = colblock.ColBlock.wrap({
                    "__series__": backing[0][: self._fill],
                    "ts": backing[1][: self._fill],
                    "value": backing[2][: self._fill],
                }).freeze()
                memtrace.track_bytes(block.nbytes, "seal", "view")
                cols_view = tuple(
                    block.lane(k) for k in ("__series__", "ts", "value")
                )
                self._cols = None
                self._fill = 0
            rows = self._buffered
            self._buffered = 0
            lanes = None
            if has_accum:
                # synchronous C++ drain: pk-sorted lanes copied out, arena
                # cleared — part of the same atomic swap
                lanes = self._accum.take_sorted()
                rows += len(lanes[2])
            seq = allocate_id()
        return SealedMemtable(
            seq=seq, rows=rows, buf=buf, cols=cols_view, keys=keys,
            cols_backing=backing, lanes=lanes, block=block,
        )

    async def seal_and_submit(self) -> None:
        """Threshold flush trigger: swap in a fresh active memtable and
        hand the sealed one to the background executor. The append hot
        path never waits on drain/encode/upload — it blocks only when the
        bounded flush queue is full (backpressure with a stall deadline,
        horaedb_ingest_stall_seconds)."""
        ex = self._executor
        if ex is None:
            return
        # a new trigger is also the retry clock for parked failures
        ex.kick_parked()
        sealed = self.seal()
        if sealed is not None:
            try:
                await ex.submit(sealed)
            except BaseException:
                # stall deadline (or cancellation) while the queue was
                # full: the rows were already detached from the active
                # memtable — PARK them (never drop acked rows; the next
                # trigger or barrier retries) before surfacing the error
                ex.park(sealed)
                raise

    async def flush(self) -> None:
        """Strong flush barrier: every row buffered (acked) at entry is
        durable — or an error raised — by return.

        Seals the active memtable (urgent submit bypasses the queue
        bound), waits out the memtables queued/in-flight AT ENTRY (a
        snapshot — sustained ingest submitting more work cannot starve
        the barrier), then handles PARKED failures by error class
        (common/error.py):

        - a RETRYABLE failure keeps PR 5's semantics: background
          triggers re-queue it, and the barrier retries it inline
          exactly once — a second failure raises here (the memtable
          re-parks first, so no acked row is ever dropped).
        - a memtable parked on a PERSISTENT or FATAL error is skipped by
          background triggers entirely (kick_parked) — re-running a
          deterministic failure every trigger burns store budget without
          ever surfacing it. Only the barrier replays it (one inline
          attempt per barrier): still broken -> the error surfaces HERE
          on that first replay; cause fixed -> it drains. Rows stay
          parked throughout."""
        ex = self._executor
        if ex is None:
            return
        from horaedb_tpu.common import deadline as deadline_ctx

        ex.kick_parked()
        sealed = self.seal()
        if sealed is not None:
            await ex.submit(sealed, urgent=True)
        pending = ex.snapshot_pending()
        while True:
            await ex.wait_settled(pending)
            parked = ex.take_parked()
            if parked is None:
                return
            try:
                # the inline replay is durability work for ACKED rows and
                # must not run under the CALLING QUERY's deadline (the
                # barrier runs in the query task when a scan flushes
                # first): a budget-expired DeadlineExceeded here would
                # park the memtable as "persistent" and background
                # triggers would then skip it forever — acked rows stuck
                # memory-only on a healthy store
                with deadline_ctx.deadline_scope(None):
                    await self._writeout_once(parked)
            except BaseException as e:
                parked.last_error = e
                ex.park(parked)
                raise

    async def _writeout_once(self, sealed: "SealedMemtable") -> None:
        """One write-out attempt of a sealed memtable, timed and traced
        (logic in _writeout_sealed; this wrapper owns the flush
        observability so every caller — executor worker, flush-barrier
        inline retry — reports). On failure the un-landed remainder has
        already been converted into pinned-seq replay groups on `sealed`,
        so parking it loses nothing."""
        appended0 = self._appended_rows
        rows = sealed.rows
        with tracing.span(
            "ingest_flush", table=self._table_id, rows=rows, seq=sealed.seq,
        ):
            try:
                with FLUSH_SECONDS.labels(self._table_id).time():
                    await self._writeout_sealed(sealed)
            except BaseException:
                FLUSH_FAILURES.labels(self._table_id).inc()
                FLUSH_FAILURES_TOTAL.labels(self._table_id).inc()
                raise
        FLUSH_ROWS.labels(self._table_id).inc(rows)
        if rows:
            # rows appended to the ACTIVE memtable while this write-out ran,
            # per flushed row: the measured producer/consumer overlap
            FLUSH_OVERLAP_RATIO.labels(self._table_id).observe(
                (self._appended_rows - appended0) / rows
            )
        if sealed.cols_backing is not None and len(self._spare_cols) < 2:
            # recycle the column backing into the arena (success only: a
            # failed attempt's replay groups may still view into it)
            self._spare_cols.append(sealed.cols_backing)
            sealed.cols_backing = None

    async def _writeout_sealed(self, sealed: "SealedMemtable") -> None:
        """Write out one sealed memtable (one storage write per segment
        shard).

        Failure contract: on ANY write failure every un-landed row
        converts into pinned-seq replay groups on `sealed` (keeping the
        memtable's ORIGINAL sequence) before the error propagates, so
        already-acked samples survive for a retry and a delayed replay can
        never beat writes acked after them. Partial double-writes are
        safe: the storage merge dedups by pk + seq."""
        snap_seq = sealed.seq
        buf, sealed.buf = sealed.buf, {}
        cols, sealed.cols = sealed.cols, None
        keys, sealed.keys = sealed.keys, []
        lanes, sealed.lanes = sealed.lanes, None
        groups, sealed.groups = sealed.groups, []

        def _regroup_fresh() -> None:
            self._group_snapshot(sealed, buf, cols, keys, snap_seq)
            if lanes is not None:
                self._group_lanes(sealed, *lanes, seq=snap_seq)

        # 1) replay groups from failed attempts under their ORIGINAL seqs,
        # coalesced per (seq, segment) so a failed memtable of many small
        # requests replays as one SST per segment, not one per request
        # (already-landed shards of those attempts dedup by pk+seq)
        merged: "dict[tuple[int, int], list]" = {}
        for seq0, seg0, lanes0, presorted0 in groups:
            merged.setdefault((seq0, seg0), []).append((lanes0, presorted0))
        replay = list(merged.items())
        for i, ((seq0, _seg0), group) in enumerate(replay):
            if len(group) == 1:
                lanes0, presorted0 = group[0]
            else:
                lanes0 = tuple(
                    memtrace.tracked_concat(
                        [g[0][j] for g in group], "seal"
                    )
                    for j in range(4)
                )
                presorted0 = False  # concatenation breaks per-group order
            try:
                await self._write_segment(
                    *lanes0, presorted=presorted0, seq=seq0, fast=True
                )
            except BaseException:
                for (sq, sg), grp in replay[i:]:
                    for lanes1, presorted1 in grp:
                        sealed.groups.append((sq, sg, lanes1, presorted1))
                _regroup_fresh()
                raise
        # 2) this memtable's fresh rows
        try:
            for _seg_start, cols_list in sorted(buf.items()):
                seg_cols = [
                    memtrace.tracked_concat(
                        [c[i] for c in cols_list], "seal"
                    )
                    for i in range(4)
                ]
                await self._write_segment(*seg_cols, seq=snap_seq, fast=True)
            if cols is not None:
                await self._flush_cols(cols, keys, seq=snap_seq)
        except BaseException:
            _regroup_fresh()
            raise
        if lanes is not None:
            await self._flush_accum_lanes(sealed, *lanes, seq=snap_seq)

    # A flush larger than this splits into contiguous pk-range shards
    # written as independent SSTs concurrently: parquet encode (GIL-free)
    # and the per-object fsync are the flush bottleneck, and both overlap
    # across shards. More SSTs per segment is native LSM currency —
    # compaction folds them. MAX_FLUSH_SHARDS bounds thread/file fan-out —
    # by the ACTUAL cpu budget: on a 1-core box (CI, small containers)
    # shard concurrency cannot overlap anything and each extra shard just
    # pays its own fsync + manifest delta + encode setup.
    FLUSH_SHARD_ROWS = 128 * 1024
    MAX_FLUSH_SHARDS = min(8, 2 * (
        len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
        else (os.cpu_count() or 1)
    ))

    async def _flush_accum_lanes(
        self, sealed: "SealedMemtable", mid, tsid, ts, vals, seq=None
    ) -> None:
        """Write out pk-sorted lanes taken from the C++ accumulator (the
        take CLEARED it, so rows buffered during the awaited writes are
        never lost), split by segment (and by shard within large segments),
        the shards' parquet encodes running concurrently across the SST
        pool with the in-flight uploads bounded (_write_segment). On
        failure the lanes convert into pinned-seq replay groups on
        `sealed` so acked samples survive for a retry."""
        if not len(ts):
            return
        seg = ts - (ts % self._segment_duration)
        uniq = np.unique(seg)
        # Per-segment lanes (the lanes sort by (mid, tsid, ts), so segment
        # rows are scattered — a mask gather per segment; the overwhelmingly
        # common single-segment scrape keeps the zero-copy fast path).
        # Each per-segment lane set stays pk-sorted (mask gather preserves
        # order), so contiguous shard slices of it are pk-sorted too.
        per_seg: list[tuple[int, tuple]] = []
        if len(uniq) == 1:
            per_seg.append((int(uniq[0]), (mid, tsid, ts, vals)))
        else:
            for seg_start in uniq.tolist():
                m = seg == seg_start
                per_seg.append((int(seg_start), (mid[m], tsid[m], ts[m], vals[m])))
        work: list[tuple] = []
        for _seg_start, lanes in per_seg:
            smid, stsid, sts = lanes[0], lanes[1], lanes[2]
            n = len(sts)
            shards = min(max(1, -(-n // self.FLUSH_SHARD_ROWS)),
                         self.MAX_FLUSH_SHARDS)
            step = -(-n // shards)
            lo = 0
            while lo < n:
                hi = min(lo + step, n)
                # never split a run of identical (mid, tsid, ts) rows across
                # shards: all shards share one seq, and same-pk-same-seq
                # duplicates must stay inside one SST so the in-file row
                # order resolves them deterministically
                while hi < n and (
                    smid[hi] == smid[hi - 1]
                    and stsid[hi] == stsid[hi - 1]
                    and sts[hi] == sts[hi - 1]
                ):
                    hi += 1
                sl = slice(lo, hi)
                work.append(tuple(a[sl] for a in lanes))
                lo = hi
        try:
            if len(work) == 1:
                await self._write_segment(*work[0], presorted=True, seq=seq, fast=True)
            else:
                async with TaskGroup() as tg:
                    for lanes in work:
                        tg.create_task(
                            self._write_segment(*lanes, presorted=True, seq=seq, fast=True)
                        )
        except BaseException:
            self._group_lanes(sealed, mid, tsid, ts, vals, per_seg, seq=seq)
            raise

    def _group_lanes(
        self, sealed: "SealedMemtable", mid, tsid, ts, vals,
        per_seg=None, seq=None,
    ) -> None:
        """Convert failed accumulator lanes PER SEGMENT into pinned-seq
        replay groups on `sealed` (a batch must not cross a segment). The
        lanes keep their memtable's sequence so a later replay cannot beat
        writes acked after them. Shards that did land before the failure
        are harmless to re-write: storage dedups by pk + seq."""
        if not len(ts):
            return
        if per_seg is None:
            seg = ts - (ts % self._segment_duration)
            uniq = np.unique(seg)
            if len(uniq) == 1:
                per_seg = [(int(uniq[0]), (mid, tsid, ts, vals))]
            else:
                per_seg = [
                    (int(s), tuple(a[seg == s] for a in (mid, tsid, ts, vals)))
                    for s in uniq.tolist()
                ]
        for seg_start, lanes in per_seg:
            # accum lanes are pk-sorted; segment mask-gathers preserve that
            sealed.groups.append((seq, seg_start, lanes, True))

    def _group_snapshot(self, sealed, buf, cols, keys, seq: int) -> None:
        """Convert a failed memtable's fresh Python buffers into pinned-seq
        replay groups (per segment, original sequence preserved). Column
        views materialize into standalone per-segment lanes, so the parked
        groups never pin the active arena's backing arrays."""
        for seg_start, lst in buf.items():
            for lanes in lst:
                sealed.groups.append((seq, int(seg_start), lanes, False))
        if cols is not None:
            dense_ps, ts, vals = cols
            key_mid = np.fromiter((k[0] for k in keys), np.uint64, len(keys))
            key_tsid = np.fromiter((k[1] for k in keys), np.uint64, len(keys))
            mid = key_mid[dense_ps]
            tsid = key_tsid[dense_ps]
            seg = ts - (ts % self._segment_duration)
            for s in np.unique(seg).tolist():
                m = seg == s
                sealed.groups.append(
                    (seq, int(s), (mid[m], tsid[m], ts[m], vals[m]), False)
                )

    async def _flush_cols(self, cols, keys, seq=None) -> None:
        """Counting-sort the column memtable into pk order: rank the (few)
        unique series keys, gather rank per sample, one stable O(n + k)
        counting sort. The lanes arrive as views into the preallocated
        active arrays (zero-copy drain — no concatenate). Scrapes arrive
        in time order, so within a series the append order already sorts
        ts — verified in O(n); only genuinely out-of-order data pays a
        full lexsort."""
        # pk-rank sort is the drain's CPU cost (encode/upload time below)
        with self._flush.stage("drain"):
            dense_ps, ts, vals = cols
            k = len(keys)
            key_arr = np.empty((k, 2), dtype=np.uint64)
            for i, (m, t) in enumerate(keys):
                key_arr[i, 0] = m
                key_arr[i, 1] = t
            order = np.lexsort((key_arr[:, 1], key_arr[:, 0]))  # rank over k keys
            rank_of_dense = np.empty(k, dtype=np.int64)
            rank_of_dense[order] = np.arange(k)
            rank_ps = rank_of_dense[dense_ps].astype(np.int32)
            # stable radix argsort over small int ranks (numpy uses radix for
            # integer stable sorts — effectively linear, far cheaper than a
            # 3-key u64 lexsort)
            perm = np.argsort(rank_ps, kind="stable")
            counts = np.bincount(rank_ps, minlength=k)  # indexed by rank
            mid = key_arr[order, 0].repeat(counts)
            tsid = key_arr[order, 1].repeat(counts)
            ts = ts[perm]
            vals = vals[perm]
            # ts must be nondecreasing within each series group; a decrease is
            # only legal exactly at a group boundary
            dips = np.flatnonzero(np.diff(ts) < 0)
            boundaries = np.cumsum(counts)[:-1] - 1
            if np.setdiff1d(dips, boundaries).size:
                perm2 = np.lexsort((ts, tsid, mid))
                mid, tsid, ts, vals = mid[perm2], tsid[perm2], ts[perm2], vals[perm2]
            seg = ts - (ts % self._segment_duration)
            uniq = np.unique(seg)
        for seg_start in uniq:
            m = seg == seg_start if len(uniq) > 1 else slice(None)
            await self._write_segment(mid[m], tsid[m], ts[m], vals[m], seq=seq, fast=True)

    async def _write_segment(
        self, metric_ids, tsids, ts, values,
        presorted: bool = False, seq: "int | None" = None,
        fast: bool = False,
    ) -> None:
        """`fast`: flush-path (L0) writes take the fast parquet profile —
        compaction re-encodes them with the tuned one. Direct (unbuffered)
        persists keep tuned encodings: with no buffer there may be no
        compaction churn either, so those SSTs can live long.

        Flush-path writes also ride the bounded upload semaphore: several
        executor workers x several shards would otherwise fan encode+PUT
        out without limit on a small host."""
        # one frozen column block feeds the writer: the arrow batch wraps
        # the lanes zero-copy (primitive types), so the parquet encoder
        # reads the sealed bytes in place — no per-lane staging copy
        block = colblock.ColBlock.wrap({
            "metric_id": memtrace.tracked_contiguous(
                np.asarray(metric_ids, dtype=np.uint64), "append"
            ),
            "tsid": memtrace.tracked_contiguous(
                np.asarray(tsids, dtype=np.uint64), "append"
            ),
            "field_id": _zeros_u64(len(ts)),
            "ts": memtrace.tracked_contiguous(ts, "append"),
            "value": memtrace.tracked_contiguous(values, "append"),
        }).freeze()
        batch = block.to_arrow_batch(DATA_SCHEMA, stage="flush_encode")
        lo = int(ts.min())
        hi = int(ts.max()) + 1
        req = WriteRequest(batch, TimeRange(lo, hi), presorted=presorted,
                           seq=seq, fast_encode=fast)
        if fast:
            if self._upload_sem is None:
                self._upload_sem = asyncio.Semaphore(self.MAX_INFLIGHT_UPLOADS)
            async with self._upload_sem:
                await self._storage.write(req)
        else:
            await self._storage.write(req)

    # -- queries ---------------------------------------------------------------
    def _predicate(self, metric_id: int, tsids: list[int] | None, rng: TimeRange):
        parts = [
            F.Compare("metric_id", "eq", metric_id),
            F.Compare("ts", "ge", rng.start),
            F.Compare("ts", "lt", rng.end),
        ]
        if tsids is not None:
            parts.append(F.InSet("tsid", tuple(tsids)))
        return F.And(*parts)

    # -- the serving-tier choke point (jaxlint J013) ---------------------------
    # query_raw/query_downsample are the ONE place the result cache and
    # rollup substitution are consulted: every read surface (native JSON
    # queries, PromQL instant/range, exemplars) funnels through them, so
    # one lookup discipline covers the whole read plane. HORAEDB_SERVING=off
    # (the honesty switch) bypasses every shortcut — forced-cold answers
    # are the oracle serving answers are asserted bit-exact against.

    def _serving_key(
        self, kind: bytes, metric_id: int, tsids, rng: TimeRange,
        bucket_ms, limit, filtered: bool,
    ) -> "bytes | None":
        """Digest of (normalized plan fingerprint, sealed-SST id set,
        visibility epoch) — the cache key IS the invalidation contract
        (serving/cache.py). None = uncacheable: no SSTs cover the range
        (nothing worth caching), or the retention floor cuts into it
        (the floor moves every millisecond, so the masked row set is
        time-dependent and no stored answer can stay exact)."""
        import hashlib

        floor = self._storage.retention_floor()
        if floor is not None and floor > rng.start:
            return None
        ssts = self._storage.manifest.find_ssts(rng)
        if not ssts:
            return None
        h = hashlib.blake2b(digest_size=16)
        h.update(self._table_id.encode())
        h.update(kind)
        h.update(np.uint64(metric_id).tobytes())
        h.update(np.int64(rng.start).tobytes())
        h.update(np.int64(rng.end).tobytes())
        h.update(np.int64(-1 if bucket_ms is None else bucket_ms).tobytes())
        h.update(np.int64(-1 if limit is None else limit).tobytes())
        h.update(b"f" if filtered else b"u")
        if tsids is None:
            h.update(b"\x00")
        else:
            h.update(b"\x01")
            h.update(np.asarray(sorted(tsids), dtype=np.uint64).tobytes())
        h.update(
            np.asarray(sorted(s.id for s in ssts), dtype=np.uint64).tobytes()
        )
        tombs = sorted(
            t.id for t in self._storage.manifest.all_tombstones()
            if t.time_range.overlaps(rng)
        )
        h.update(np.asarray(tombs, dtype=np.uint64).tobytes())
        return h.digest()

    @staticmethod
    def _replay_notes(notes: dict) -> None:
        """Re-note a cached entry's fill-time provenance into the CURRENT
        query's collector, so EXPLAIN on a hit still names what the
        cached plan covered (rollup substitutions, SSTs selected)."""
        for k, v in notes.items():
            scanstats.note(k, int(v))

    def _serving_for_query(self):
        """The tier when it may serve this query, else None (counting the
        bypass when the honesty switch forced it off)."""
        from horaedb_tpu.serving import CACHE_REQUESTS

        serving = self._serving
        if serving is None:
            return None
        if not serving.active():
            scanstats.note("serving_cache_bypass")
            CACHE_REQUESTS.labels("bypass").inc()
            return None
        return serving

    async def _serving_cached(self, serving, key: bytes, fill):
        """Result-cache read path: hit replays and returns; miss runs
        `fill` single-flight (followers ride the leader's computation and
        replay its stored provenance)."""
        from horaedb_tpu.common import deadline as deadline_ctx
        from horaedb_tpu.serving import CACHE_REQUESTS

        hit = serving.cache.serving_get(key)
        if hit is not None:
            value, notes = hit
            scanstats.note("serving_cache_hit")
            CACHE_REQUESTS.labels("hit").inc()
            self._replay_notes(notes)
            return value
        scanstats.note("serving_cache_miss")
        CACHE_REQUESTS.labels("miss").inc()
        value, notes, leader = await serving.cache.serving_single_flight(
            key, self._table_id, fill
        )
        if not leader:
            # the leader's scan fed ITS collector; this query waited
            deadline_ctx.check("serving_cache")
            self._replay_notes(notes)
        return value

    async def query_raw(
        self,
        metric_id: int,
        tsids: list[int] | None,
        rng: TimeRange,
        limit: int | None = None,
    ) -> pa.Table | None:
        """Materialized (merged, deduped) sample rows.

        `limit` pushes down into the scan: the per-segment async generator
        stops being driven once enough rows accumulated, so later segments
        are never read (the reference's scan-stream laziness,
        storage.rs:335-370)."""
        if self._buffer_rows:
            # always flush (not just when _buffered > 0): an in-flight flush
            # has already sealed the buffers but its SSTs may not be durable
            # yet — flush() quiesces the executor, keeping reads consistent
            # with acked writes (union of active + sealed + flushed)
            await self.flush()
        serving = self._serving_for_query()
        if serving is None:
            return await self._query_raw_cold(metric_id, tsids, rng, limit)
        key = self._serving_key(
            b"raw", metric_id, tsids, rng, None, limit, tsids is not None
        )
        if key is None:
            return await self._query_raw_cold(metric_id, tsids, rng, limit)

        async def fill():
            table = await self._query_raw_cold(metric_id, tsids, rng, limit)
            nbytes = 64 + (table.nbytes if table is not None else 0)
            return table, nbytes, {}

        return await self._serving_cached(serving, key, fill)

    async def _query_raw_cold(
        self,
        metric_id: int,
        tsids: list[int] | None,
        rng: TimeRange,
        limit: int | None = None,
    ) -> pa.Table | None:
        from contextlib import aclosing

        batches = []
        total = 0
        # aclosing: an early break must run the generator's finally NOW so
        # the prefetched next-segment read is cancelled deterministically
        # (asyncgen GC finalization would let it issue the wasted I/O first)
        async with aclosing(self._storage.scan(
            ScanRequest(range=rng, predicate=self._predicate(metric_id, tsids, rng))
        )) as gen:
            async for b in gen:
                if limit is not None and total + b.num_rows >= limit:
                    batches.append(b.slice(0, limit - total))
                    total = limit
                    break
                batches.append(b)
                total += b.num_rows
        return pa.Table.from_batches(batches) if batches else None

    async def query_downsample(
        self,
        metric_id: int,
        tsids: list[int],
        rng: TimeRange,
        bucket_ms: int,
        filtered: bool = True,
    ) -> tuple[list[int], dict[str, np.ndarray]] | None:
        """Per-(series, bucket) sum/count/min/max/mean grids via aggregate
        PUSHDOWN: each segment reduces on device inside the scan (raw rows
        never return to host); per-segment partial grids combine trivially
        because the data-table pk includes the timestamp, so duplicates
        cannot span segments. Returns (tsid order, grids).

        Precision: on-device accumulation is float32 ONLY on real
        accelerators (TPU-native lane width); CPU/XLA-fallback meshes and
        the single-device path accumulate in f64 (x64 enabled), matching
        the reference's f64 aggregation exactly. On TPU the per-cell
        relative error is ~2^-24 * samples_per_cell — counter-style values
        above 2^24 (~16.7M) or cells with millions of samples lose low
        bits vs an f64 oracle. The materializing fallback (high
        cardinality) accumulates in f64 on host.

        `filtered=False` means `tsids` is just the metric's full series set
        (no tag filter): the TSID membership predicate is skipped, and very
        high cardinalities fall back to the materializing path whose output
        is sized by the series actually present in range."""
        from horaedb_tpu.common.error import ensure

        if self._buffer_rows:
            await self.flush()  # see query_raw: waits out in-flight flushes
        n_buckets = -(-(rng.end - rng.start) // bucket_ms)
        ensure(
            n_buckets <= MAX_BUCKETS,
            f"downsample resolution too high: {n_buckets} buckets "
            f"(max {MAX_BUCKETS}); narrow the range or coarsen bucket_ms",
        )
        serving = self._serving_for_query()
        if serving is None:
            return await self._query_downsample_cold(
                metric_id, tsids, rng, bucket_ms, int(n_buckets), filtered,
                serving=None,
            )
        key = self._serving_key(
            b"ds", metric_id, tsids, rng, bucket_ms, None, filtered
        )
        if key is None:
            return await self._query_downsample_cold(
                metric_id, tsids, rng, bucket_ms, int(n_buckets), filtered,
                serving=serving,
            )

        async def fill():
            prov: dict = {}
            res = await self._query_downsample_cold(
                metric_id, tsids, rng, bucket_ms, int(n_buckets), filtered,
                serving=serving, prov=prov,
            )
            nbytes = 64
            if res is not None:
                r_tsids, grids = res
                nbytes += len(r_tsids) * 8 + sum(
                    np.asarray(g).nbytes for g in grids.values()
                )
            return res, nbytes, prov

        return await self._serving_cached(serving, key, fill)

    async def _query_downsample_cold(
        self,
        metric_id: int,
        tsids: list[int],
        rng: TimeRange,
        bucket_ms: int,
        num_buckets: int,
        filtered: bool,
        serving=None,
        prov: "dict | None" = None,
    ) -> tuple[list[int], dict[str, np.ndarray]] | None:
        """One uncached downsample computation. With an active serving
        tier, segments whose rollup record passes the freshness contract
        (storage/rollup.py) fold bucket-count-scale pre-aggregated rows
        instead of scanning raw; everything else takes the device
        pushdown. `prov` collects the provenance a cached entry replays
        on later hits.

        Only cache MISSES reach here, so this is the query batcher's
        dispatch point (server/batching.py): a grid query with compatible
        concurrent company coalesces into ONE stacked kernel launch.
        Eligibility is decided HERE because only this layer knows the
        segment layout and the rollup plan:

        - grid/segment alignment (bucket_ms divides the segment duration
          AND rng.start is bucket-aligned) guarantees no bucket spans a
          segment boundary, so every cell accumulates rows of exactly one
          segment — the condition under which the batched single-stream
          reduction is bit-exact vs the solo per-segment partial fold
          (unaligned grids could differ in float association on
          cancelling data, so they run solo);
        - a non-empty rollup plan means the solo pushdown folds
          bucket-count-scale artifacts — far cheaper than the batched
          lane's raw scan — so rollup-covered queries run solo too.

        Everything else (lone queries, short deadlines, oversized
        shapes, HORAEDB_BATCH=off) continues down the solo pushdown
        unchanged."""
        from horaedb_tpu.server import batching

        if prov is None:
            prov = {}
        # retention-pruned SST selection (storage.select_ssts notes
        # ssts_retention_pruned provenance for EXPLAIN)
        ssts = self._storage.select_ssts(rng)
        if not ssts or not tsids:
            return None
        if len(tsids) > MAX_PUSHDOWN_SERIES:
            # the materialized fallback scans through ObjectBasedStorage.scan,
            # which notes its own ssts_selected — noting here too would
            # double-count the provenance
            return await self._query_downsample_materialized(
                metric_id, tsids if filtered else None, rng, bucket_ms
            )
        series_ids = np.asarray(sorted(tsids), dtype=np.uint64)
        segments = self._storage.group_by_segment(ssts)
        # Rollup substitution plan (storage/rollup.py): per segment, the
        # coarsest aligned rollup whose freshness contract passes — the
        # segment then costs a bucket-count-scale artifact read instead
        # of a raw scan. Planning is pure manifest state; a failure
        # degrades to all-raw, never an error. Computed BEFORE the
        # batching decision: rollup-covered queries must not trade the
        # artifact fold for the batched lane's raw scan.
        plan: dict = {}
        if serving is not None and serving.rollups_active:
            from horaedb_tpu.storage import rollup as rollup_mod

            try:
                plan = rollup_mod.plan_rollups(
                    self._storage, segments, rng, rng.start, bucket_ms
                )
            except Exception:  # noqa: BLE001 — raw is always available
                logger.warning("rollup planning failed; scanning raw",
                               exc_info=True)
                plan = {}
        batcher = batching.GLOBAL_BATCHER
        aligned = (
            self._segment_duration % bucket_ms == 0
            and rng.start % bucket_ms == 0
        )
        if not aligned or plan:
            batcher.note_ineligible()
            return await self._query_downsample_pushdown(
                metric_id, series_ids, ssts, segments, plan, rng,
                bucket_ms, num_buckets, filtered, prov,
            )
        tok = batcher.begin()
        try:
            res = await batcher.coalesce(
                bucket_ms=bucket_ms, num_buckets=num_buckets,
                series_ids=series_ids, t0=rng.start, filtered=filtered,
                # same-(table, metric, range) members share ONE union
                # scan — the N-panels-one-dashboard case pays one read
                share_key=(self._table_id, metric_id, rng.start, rng.end),
                scan=lambda ids: self._batch_scan_rows(metric_id, rng, ids),
            )
            if res is not batching.SOLO:
                grids, notes = res
                for k, n in (notes or {}).items():
                    if k == "batched_with":
                        scanstats.note_max(k, n)
                    else:
                        scanstats.note(k, n)
                    # cache replay must not claim a stacked launch on a
                    # later HIT — batch provenance stays out of `prov`
                    if not k.startswith(("batched_", "batch_")):
                        prov[k] = prov.get(k, 0) + n
                if grids is None:
                    return None
                return [int(x) for x in series_ids], grids
            return await self._query_downsample_pushdown(
                metric_id, series_ids, ssts, segments, plan, rng,
                bucket_ms, num_buckets, filtered, prov,
            )
        finally:
            batcher.end(tok)

    async def _batch_scan_rows(
        self,
        metric_id: int,
        rng: TimeRange,
        tsids: "list[int] | None",
    ):
        """One batch scan's row materialization (runs in the group's
        detached context): the same merged/deduped/visibility-masked rows
        a solo scan sees, as flat (ts i64, tsid u64, values f64) lanes —
        or None when nothing is in range. `tsids` may be the UNION of
        several members' series sets (batching.py de-multiplexes rows
        per member afterwards); None scans the whole metric."""
        table = await self._query_raw_cold(metric_id, tsids, rng)
        if table is None or table.num_rows == 0:
            return None
        return (
            table.column("ts").to_numpy().astype(np.int64, copy=False),
            table.column("tsid").to_numpy(),
            table.column("value").to_numpy().astype(np.float64, copy=False),
        )

    async def _query_downsample_pushdown(
        self,
        metric_id: int,
        series_ids: np.ndarray,
        ssts: list,
        segments: list,
        plan: dict,
        rng: TimeRange,
        bucket_ms: int,
        num_buckets: int,
        filtered: bool,
        prov: "dict | None" = None,
    ) -> tuple[list[int], dict[str, np.ndarray]] | None:
        """The solo per-segment device pushdown (the batcher's oracle).
        `segments`/`plan` come precomputed from the cold entry — the
        rollup plan now also feeds the batching eligibility decision."""
        if prov is None:
            prov = {}
        # EXPLAIN provenance: how many SSTs the time range selected (bloom
        # pruning and actual reads are noted per SST in storage/read.py)
        scanstats.note("ssts_selected", len(ssts))
        prov["ssts_selected"] = len(ssts)
        pred = self._predicate(
            metric_id, list(series_ids) if filtered else None, rng
        )
        # Per-segment pushdown passes run CONCURRENTLY: reads of one
        # segment overlap another's device kernel — the engine-side analog
        # of the reference's UnionExec driving per-segment plans. The
        # semaphore is SHARED across queries (one per manager) so a
        # dashboard burst cannot multiply the bound. Partials fold in
        # SEGMENT order, not completion order: float addition is not
        # associative, and the distributed scatter-gather path
        # (cluster/partial.py) promises the merged result is bit-exact vs
        # a single-node run — that only holds if the leaf fold itself is
        # deterministic. A small reorder buffer (`pending`) holds parts
        # that finish ahead of a slower earlier segment; in the common
        # case segments complete roughly in order and peak memory stays
        # the in-flight parts, not one grid per segment. TaskGroup
        # cancels + awaits siblings on first error — no detached scans
        # survive a failed query.
        if self._scan_sem is None:
            self._scan_sem = asyncio.Semaphore(SEGMENT_SCAN_CONCURRENCY)
        acc: dict[str, np.ndarray] | None = None
        pending: dict[int, dict[str, np.ndarray] | None] = {}
        next_fold = 0

        def _fold_one(part) -> None:
            nonlocal acc
            if part is None:
                return
            if acc is None:
                acc = part
            else:
                acc["sum"] = acc["sum"] + part["sum"]
                acc["count"] = acc["count"] + part["count"]
                acc["min"] = np.minimum(acc["min"], part["min"])
                acc["max"] = np.maximum(acc["max"], part["max"])

        def fold(idx: int, part) -> None:
            nonlocal next_fold
            pending[idx] = part
            while next_fold in pending:
                _fold_one(pending.pop(next_fold))
                next_fold += 1

        async def one_rollup(rec, seg, idx):
            """Fold one segment's rollup artifact instead of scanning it;
            any artifact-read failure degrades the segment to raw."""
            from horaedb_tpu.common import deadline as deadline_ctx
            from horaedb_tpu.common.error import DeadlineExceeded
            from horaedb_tpu.serving import (
                ROLLUP_ROWS,
                ROLLUP_SUBSTITUTIONS,
                resolution_label,
            )
            from horaedb_tpu.storage import rollup as rollup_mod

            lanes = None
            async with self._scan_sem:
                deadline_ctx.check("segment_scan")
                try:
                    lanes = await rollup_mod.read_rollup(self._storage, rec)
                except (DeadlineExceeded, asyncio.CancelledError):
                    raise
                except Exception:  # noqa: BLE001 — degrade to the raw scan
                    logger.warning(
                        "rollup artifact %d unreadable; raw-scanning "
                        "segment %d", rec.sst_id, rec.segment_start,
                        exc_info=True,
                    )
            if lanes is None:
                await one_segment(seg, idx)
                return
            part, rows = self._fold_rollup(
                lanes, metric_id, series_ids, rng, bucket_ms, num_buckets,
            )
            label = resolution_label(rec.resolution_ms)
            scanstats.note("rollup_segments")
            scanstats.note("rollup_rows_read", rows)
            scanstats.note(f"rollup_res_{label}")
            prov["rollup_segments"] = prov.get("rollup_segments", 0) + 1
            prov["rollup_rows_read"] = prov.get("rollup_rows_read", 0) + rows
            prov[f"rollup_res_{label}"] = prov.get(f"rollup_res_{label}", 0) + 1
            ROLLUP_SUBSTITUTIONS.labels(label).inc()
            ROLLUP_ROWS.inc(rows)
            fold(idx, part)

        async def one_segment(seg, idx):
            async with self._scan_sem:
                # cooperative deadline: a segment pass acquired AFTER the
                # budget died must not read + reduce (the TaskGroup
                # cancels siblings on the first raise)
                from horaedb_tpu.common import deadline as deadline_ctx

                deadline_ctx.check("segment_scan")
                # retry wrapper: a compaction may delete this snapshot's
                # files mid-query; the refresh re-reads the live SSTs
                part = await self._storage.scan_segment_retrying(
                    seg, rng,
                    lambda fresh: self._storage.parquet_reader.scan_segment_downsample(
                        fresh,
                        predicate=pred,
                        ts_column="ts",
                        value_column="value",
                        series_column="tsid",
                        series_ids=series_ids,
                        t0=rng.start,
                        bucket_ms=bucket_ms,
                        num_buckets=num_buckets,
                        # data-table pk is (metric_id, tsid, field_id, ts):
                        # metric_id is eq-pinned in `pred`, field_id is
                        # constant 0 — the packed (sid, ts) dedup is exact
                        packed_ok=True,
                    ),
                )
            # the fold is synchronous (no awaits): safe on the event loop.
            # A vanished segment (TTL) reports None so the reorder buffer
            # still advances past its index.
            fold(idx, part)
            if part is None:
                return
            scanstats.note("raw_segments")
            prov["raw_segments"] = prov.get("raw_segments", 0) + 1

        from horaedb_tpu.storage.types import Timestamp

        async with TaskGroup() as tg:
            for idx, seg in enumerate(segments):
                seg_start = Timestamp(
                    seg[0].meta.time_range.start
                ).truncate_by(self._segment_duration).value
                rec = plan.get(seg_start)
                if rec is not None:
                    tg.create_task(one_rollup(rec, seg, idx))
                else:
                    tg.create_task(one_segment(seg, idx))
        if acc is None or acc["count"].sum() == 0:
            return None
        with np.errstate(invalid="ignore", divide="ignore"):
            acc["mean"] = acc["sum"] / acc["count"]
        return [int(x) for x in series_ids], acc

    @staticmethod
    def _fold_rollup(
        lanes: dict, metric_id: int, series_ids: np.ndarray,
        rng: TimeRange, bucket_ms: int, num_buckets: int,
    ) -> tuple[dict | None, int]:
        """Scatter one rollup artifact's pre-aggregated rows into a query
        grid partial. Rows are unique per (series, bucket) by
        construction, and alignment was proven at plan time, so the
        scatter-adds combine exactly like raw-row partials. Returns
        (partial grids or None, rows folded)."""
        ts = np.asarray(lanes["ts"], dtype=np.int64)
        tsid = np.asarray(lanes["tsid"], dtype=np.uint64)
        mid = np.asarray(lanes["metric_id"], dtype=np.uint64)
        m = (
            (mid == np.uint64(metric_id))
            & (ts >= rng.start) & (ts < rng.end)
        )
        pos = np.searchsorted(series_ids, tsid)
        pos_c = np.clip(pos, 0, max(0, len(series_ids) - 1))
        m &= series_ids[pos_c] == tsid
        rows = int(np.count_nonzero(m))
        if not rows:
            return None, 0
        sel = np.flatnonzero(m)
        b = ((ts[sel] - rng.start) // bucket_ms).astype(np.int64)
        p = pos_c[sel]
        part = {
            "sum": np.zeros((len(series_ids), num_buckets)),
            "count": np.zeros((len(series_ids), num_buckets)),
            "min": np.full((len(series_ids), num_buckets), np.inf),
            "max": np.full((len(series_ids), num_buckets), -np.inf),
        }
        np.add.at(part["sum"], (p, b), np.asarray(lanes["sum"])[sel])
        np.add.at(part["count"], (p, b),
                  np.asarray(lanes["count"], dtype=np.float64)[sel])
        np.minimum.at(part["min"], (p, b), np.asarray(lanes["min"])[sel])
        np.maximum.at(part["max"], (p, b), np.asarray(lanes["max"])[sel])
        return part, rows

    async def _query_downsample_materialized(
        self,
        metric_id: int,
        tsids: list[int] | None,
        rng: TimeRange,
        bucket_ms: int,
    ) -> tuple[list[int], dict[str, np.ndarray]] | None:
        """High-cardinality fallback: materialize rows and size the output
        grid by np.unique of the series present in range (the sorted-scan
        fast path still applies: scan output is pk-ordered). Uses the COLD
        raw scan — the downsample result is what the choke point caches;
        nesting a second cache entry under the raw key would double-store
        the same bytes."""
        from horaedb_tpu.ops import aggregate as agg_ops

        table = await self._query_raw_cold(metric_id, tsids, rng)
        if table is None or table.num_rows == 0:
            return None
        t = table.column("ts").to_numpy()
        v = table.column("value").to_numpy()
        uniq, sid_dense = np.unique(table.column("tsid").to_numpy(), return_inverse=True)
        num_buckets = int(-(-(rng.end - rng.start) // bucket_ms))
        out = agg_ops.downsample_sorted(
            t, sid_dense.astype(np.int32), v, rng.start, bucket_ms,
            num_series=len(uniq), num_buckets=num_buckets,
        )
        return [int(x) for x in uniq], out
