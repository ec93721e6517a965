"""Background flush executor: the overlapped ingest->flush pipeline.

ROOFLINE §7 measured the ingest wall directly: pure ingest runs at
14.2 M samples/s but collapses to 4.5 M once flushes fire, because flush
work (accumulator drain, parquet encode, object-store upload) ran inline
on the append path. This module is the producer/consumer decoupling the
HoraeDB metric-engine RFC's LSM design gets from immutable memtables +
a background flusher:

- ``SealedMemtable``: an immutable snapshot of the SampleManager's
  active buffers (python per-segment chunks, the zero-copy column
  arrays, the C++ accumulator's pk-sorted lanes), sealed atomically on
  the event loop with its dedup sequence pinned. Appends after the seal
  land in a fresh active buffer — the double-buffer swap.
- ``FlushExecutor``: a bounded queue + bounded worker pool draining
  sealed memtables through the SampleManager's write-out. Appends never
  block on drain/encode/upload while the queue has room; when it is
  full they block on a condition variable with a deadline (recorded in
  ``horaedb_ingest_stall_seconds``) and fail loudly past it — bounded
  memory, never a silent drop.
- Crash-consistency: a failed write-out converts the sealed memtable's
  un-landed rows into pinned-seq replay groups and PARKS it (nothing is
  dropped); the next flush trigger or barrier re-queues it. Manifest
  visibility still commits only after the SST upload (storage layer),
  and shutdown drains the queue before the engine closes.

Workers are per-item tasks bounded by ``workers`` (no idle long-lived
loops to leak across event loops); all state is event-loop-confined.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Awaitable, Callable

import numpy as np

from horaedb_tpu.common import tracing
from horaedb_tpu.common.error import UnavailableError
from horaedb_tpu.server.metrics import GLOBAL_METRICS
from horaedb_tpu.storage import scanstats

logger = logging.getLogger(__name__)

FLUSH_QUEUE_DEPTH = GLOBAL_METRICS.gauge(
    "horaedb_flush_queue_depth",
    help="Sealed memtables awaiting a background flush worker (queued + "
         "parked-after-failure; excludes the one being written), by table.",
    labelnames=("table",),
)
INGEST_STALL_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_ingest_stall_seconds",
    help="Time appends spent blocked on a full flush queue (backpressure "
         "stalls on the condition variable), by table. A fat tail means "
         "flush bandwidth — not parse — is the ingest ceiling.",
    labelnames=("table",),
)
FLUSH_FAILURES_TOTAL = GLOBAL_METRICS.counter(
    "horaedb_flush_failures_total",
    help="Failed flush write-outs; the sealed memtable re-queues with its "
         "sequence pinned (zero rows lost) and a later trigger retries.",
    labelnames=("table",),
)
FLUSH_OVERLAP_RATIO = GLOBAL_METRICS.histogram(
    "horaedb_flush_overlap_ratio",
    help="Rows appended to the ACTIVE memtable while a flush write-out ran, "
         "over the rows in that write-out — 0 means ingest sat idle during "
         "the flush (no overlap), ~1 means full producer/consumer overlap.",
    labelnames=("table",),
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 1.0, 1.5, 2.5, 5.0),
)


@dataclass(eq=False)  # identity semantics: memtables live in queues/sets
class SealedMemtable:
    """One immutable flush unit. ``seq`` is the dedup sequence pinned at
    seal time, so a delayed/retried replay can never beat writes acked
    after it. After a failed attempt the un-landed state lives in
    ``groups`` (per-segment pinned-seq lane tuples) and the fresh fields
    are empty — the same object retries until it lands."""

    seq: int
    rows: int
    # persist()-path python buffers: segment start -> list of lane tuples
    buf: dict[int, list[tuple[np.ndarray, ...]]] = field(default_factory=dict)
    # buffer_request()-path zero-copy column views: (dense, ts, value)
    cols: tuple[np.ndarray, np.ndarray, np.ndarray] | None = None
    keys: list[tuple[int, int]] = field(default_factory=list)
    # full backing arrays behind `cols` — recycled into the spare pool
    # after a successful write-out (arena reuse across flushes)
    cols_backing: tuple[np.ndarray, ...] | None = None
    # C++ accumulator drain: (mid, tsid, ts, value) pk-sorted lanes
    lanes: tuple[np.ndarray, ...] | None = None
    # the sealed rows as ONE frozen column block (common/colblock.py):
    # `cols` above are its read-only lane views — consumers that need the
    # whole hand-off (drain, replay grouping) pass the block by reference
    # (block.share()) instead of re-materializing lanes
    block: "object | None" = None
    # pinned-seq replay groups from failed attempts:
    # (seq, segment_start, (mid, tsid, ts, value), presorted)
    groups: list[tuple[int, int, tuple, bool]] = field(default_factory=list)
    attempts: int = 0
    # the last write-out failure, kept WITH the memtable so retry policy
    # can classify it (common/error.py): retryable failures re-queue on
    # the next trigger; persistent/fatal ones surface at the barrier
    # instead of parking forever
    last_error: BaseException | None = None


class FlushExecutor:
    """Bounded background flush pool for ONE SampleManager.

    ``writeout`` is the manager's async write-out (one attempt; on
    failure it must convert the sealed memtable's remaining rows into
    pinned-seq ``groups`` before raising, so parking it loses nothing).
    """

    def __init__(
        self,
        writeout: Callable[[SealedMemtable], Awaitable[None]],
        table_id: str,
        workers: int = 2,
        queue_max: int = 4,
        stall_deadline_s: float = 30.0,
    ) -> None:
        self._writeout = writeout
        self._table = table_id
        self._workers = max(1, int(workers))
        self._queue_max = max(1, int(queue_max))
        self._deadline = float(stall_deadline_s)
        self._queue: deque[SealedMemtable] = deque()
        self._parked: list[SealedMemtable] = []
        self._inflight: set[SealedMemtable] = set()
        self._running = 0          # live worker tasks
        self._active_rows = 0      # rows inside in-flight write-outs
        self._cond: asyncio.Condition | None = None
        self._last_error: BaseException | None = None
        # pre-register every family child so /metrics shows the zero
        # state from boot (the PR2 convention)
        self._depth = FLUSH_QUEUE_DEPTH.labels(table_id)
        self._stall = INGEST_STALL_SECONDS.labels(table_id)
        FLUSH_FAILURES_TOTAL.labels(table_id)
        FLUSH_OVERLAP_RATIO.labels(table_id)
        for stage in ("drain", "encode", "upload"):
            scanstats.FLUSH_STAGE_SECONDS.labels(table_id, stage)
        self._depth.set(0)

    # -- state ---------------------------------------------------------------
    @property
    def backlog(self) -> int:
        """Sealed memtables not yet being worked (the queue-bound unit)."""
        return len(self._queue) + len(self._parked)

    @property
    def busy(self) -> bool:
        return bool(self._queue) or self._running > 0

    @property
    def pending_rows(self) -> int:
        """Rows sealed but not yet durable (queued + parked + in-flight)."""
        return (
            sum(s.rows for s in self._queue)
            + sum(s.rows for s in self._parked)
            + self._active_rows
        )

    @property
    def last_error(self) -> BaseException | None:
        return self._last_error

    def _condition(self) -> asyncio.Condition:
        if self._cond is None:  # lazy: binds the running loop
            self._cond = asyncio.Condition()
        return self._cond

    def _set_depth(self) -> None:
        self._depth.set(self.backlog)

    def _notify_soon(self) -> None:
        """Wake waiters from a sync call site (single-loop state change)."""
        if self._cond is None:
            return

        async def _n() -> None:
            async with self._cond:
                self._cond.notify_all()

        asyncio.get_running_loop().create_task(_n())

    # -- submission ----------------------------------------------------------
    async def submit(self, sealed: SealedMemtable, urgent: bool = False) -> None:
        """Queue a sealed memtable for background write-out.

        When the queue (including parked failures) is full, BLOCK on the
        condition variable until a worker frees a slot — the backpressure
        that bounds ingest memory at ~(queue_max + workers + 1) buffers —
        and raise past the stall deadline so the writer sees a retryable
        error instead of silently-dropped rows. ``urgent`` (the flush
        barrier) bypasses the bound: the caller drains immediately."""
        if not urgent and self.backlog >= self._queue_max:
            cond = self._condition()
            self._kick()  # workers must be running for a slot to ever free
            t0 = time.perf_counter()
            try:
                async with cond:
                    await asyncio.wait_for(
                        cond.wait_for(lambda: self.backlog < self._queue_max),
                        timeout=self._deadline,
                    )
            except asyncio.TimeoutError:
                stalled = time.perf_counter() - t0
                self._stall.observe(stalled)
                err = self._last_error
                # typed overload signal: the HTTP layer sheds this as
                # 503 + Retry-After (server/errors.py) instead of a 500 —
                # the sender's retry IS the backpressure release valve
                raise UnavailableError(
                    f"ingest stalled {stalled:.1f}s: flush queue full "
                    f"({self.backlog} sealed memtables, table={self._table})"
                    + (f"; last flush error: {err}" if err else ""),
                    retry_after_s=min(self._deadline, 5.0),
                )
            self._stall.observe(time.perf_counter() - t0)
        self._queue.append(sealed)
        self._set_depth()
        self._kick()

    def kick_parked(self) -> None:
        """Re-queue parked (failed) memtables at the FRONT — their pinned
        seqs are the oldest and a retry should land before fresh work.

        Classification gate (common/error.py): only RETRYABLE failures
        re-queue here. A memtable whose last failure was persistent or
        fatal stays parked — background workers re-attempting a
        deterministic failure on every trigger would burn store budget
        forever without ever surfacing it; the flush barrier owns
        raising those (SampleManager.flush)."""
        if not self._parked:
            return
        from horaedb_tpu.common.error import classify

        keep: list[SealedMemtable] = []
        while self._parked:
            s = self._parked.pop()
            if s.last_error is not None and classify(s.last_error) != "retryable":
                keep.append(s)
                continue
            self._queue.appendleft(s)
        keep.reverse()
        self._parked = keep
        self._set_depth()
        self._kick()

    def take_parked(self) -> SealedMemtable | None:
        """Pop one parked memtable for an inline (barrier) retry."""
        if not self._parked:
            return None
        s = self._parked.pop(0)
        self._set_depth()
        self._notify_soon()
        return s

    def park(self, sealed: SealedMemtable) -> None:
        """Park a memtable whose write-out failed (rows preserved)."""
        self._parked.append(sealed)
        self._set_depth()

    # -- workers -------------------------------------------------------------
    def _kick(self) -> None:
        while self._running < self._workers and self._queue:
            self._running += 1
            asyncio.get_running_loop().create_task(
                self._run(), name=f"flush-{self._table}"
            )

    async def _run(self) -> None:
        """One worker: drain queued memtables until the queue is empty,
        then exit (per-item tasks — nothing lingers at loop teardown)."""
        from horaedb_tpu.common import deadline as deadline_ctx

        # background durability work must NOT inherit a request deadline:
        # this task was possibly created from a query's flush barrier
        # (tasks copy the spawning context), and killing a half-done SST
        # upload because a dashboard panel's budget expired would turn a
        # slow query into parked memtables
        deadline_ctx.detach()
        cond = self._condition()
        try:
            while self._queue:
                item = self._queue.popleft()
                self._inflight.add(item)
                self._set_depth()
                self._active_rows += item.rows
                item.attempts += 1
                try:
                    with tracing.span(
                        "flush_task", table=self._table, rows=item.rows,
                        seq=item.seq, attempt=item.attempts,
                    ):
                        await self._writeout(item)
                    self._last_error = None
                except asyncio.CancelledError:
                    self.park(item)  # loop teardown: nothing is dropped
                    raise
                except BaseException as e:  # noqa: BLE001 — parked for retry
                    self._last_error = e
                    item.last_error = e
                    self.park(item)
                    logger.error(
                        "background flush failed (table=%s, rows=%d, "
                        "attempt %d); memtable re-queued",
                        self._table, item.rows, item.attempts, exc_info=e,
                    )
                finally:
                    self._active_rows -= item.rows
                    self._inflight.discard(item)
                async with cond:
                    cond.notify_all()
        finally:
            self._running -= 1
            try:
                async with cond:
                    cond.notify_all()
            except BaseException:  # noqa: BLE001 — teardown already raising
                pass

    # -- barriers ------------------------------------------------------------
    def snapshot_pending(self) -> "list[SealedMemtable]":
        """The memtables queued or in flight RIGHT NOW — the work a flush
        barrier must wait out. Deliberately excludes anything submitted
        after this call, so a barrier is never starved by sustained
        ingest that keeps the queue non-empty."""
        return list(self._queue) + list(self._inflight)

    async def wait_settled(self, items: "list[SealedMemtable]") -> None:
        """Wait until every memtable in `items` has SETTLED: written
        durably, or parked after a failure (the barrier then retries
        parked ones inline and surfaces the error — a background worker
        never spins on a broken store)."""
        self._kick()
        cond = self._condition()

        def pending(i: SealedMemtable) -> bool:
            return i in self._inflight or i in self._queue

        async with cond:
            await cond.wait_for(lambda: not any(pending(i) for i in items))
