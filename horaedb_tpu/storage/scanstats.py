"""Per-stage scan timing attribution.

The reference accepts DataFusion's ExecutionPlanMetricsSet but never reads it
(read.rs:84); here stage timing is first-class because the engine's perf
story spans three very different lanes — object-store IO + parquet decode
(host), host<->device transfer, and the XLA kernel itself — and
optimizing the wrong lane is the
classic failure mode (VERDICT r02: configs 1-2 were assumed kernel-bound,
measured 95% transfer-bound).

Usage:
    with scan_stats() as st:
        ... run scans ...
    st.as_dict()  # {"io_decode_s": ..., "host_prep_s": ..., ...}

The collector is a contextvar, so concurrent asyncio tasks spawned inside the
block attribute into the same collector without threading it through every
call. Every stage ALSO feeds the process-wide
`horaedb_scan_stage_seconds{stage=...}` histogram (server/metrics.py) and the
active trace span (common/tracing.py), so lane attribution is continuous on
/metrics — not just inside ad-hoc scan_stats() blocks. Overhead per stage:
two perf_counter calls + one histogram observe, against stage bodies that
decode whole segments or dispatch device kernels.
Stage sums can exceed wall clock (stages from concurrent SST reads overlap).

This is the ONE stage funnel of the process. A `Family` says which layer
a stage belongs to: the scan's lanes (`SCAN`, the module-level `stage()`),
a table's flush (`flush_family(table)`: sort, encode, upload, sidecar,
manifest, drain), a compaction task (`COMPACTION`: scan, encode, commit,
cleanup; `COMPACTION_SST` for the stages of the SSTs it writes) and the
ingest front (`ingest/pooled_parser.py`: parse, pool_wait). Every stage of
every family goes to four sinks: the family's histogram, the active
span's `stages` attribute, the per-query collector, and a
`jax.profiler.TraceAnnotation("<family>.<stage>")` on the profiler's
timeline, where the device's idle gaps are named after it
(bench_chip/trace/reduce.py). The annotation's name is constant: a stage
that is work must not match that file's WAITS pattern, a stage that is a
wait (`pool_wait`) must.
"""

from __future__ import annotations

import ctypes
import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from horaedb_tpu.common import tracing
from horaedb_tpu.server.metrics import GLOBAL_METRICS

# Canonical lane names for the /metrics histogram: the raw stage names are
# scan-internal (h2d/d2h/device_merge), but operators reason in the three
# lanes VERDICT r02 established — IO+decode, host<->device transfer, XLA
# kernel — plus the compile lane xprof feeds (a retrace storm looks like a
# kernel stall unless it has its own label). Stages outside the map keep
# their own label (host_merge, host_filter, materialize, encode, ...).
_STAGE_LANE = {
    "h2d": "transfer",
    "d2h": "transfer",
    "device_merge": "kernel",
    "device_agg": "kernel",
}

# The loop-side stage of a segment scan's merge (storage/read.py
# `_scan_segment`): the await of the worker thread that runs `host_prep` to
# `materialize`, queueing included. Its count is merges run off the loop;
# its sum less those inner stages is the wait for a thread. The inner
# stages are observed under their own names, so a sum of stages leaves this
# one out (attribution() does) or counts every merge twice. On the
# profiler's timeline it is a wait by name: no idle gap is named after it.
MERGE_WAIT = "merge_wait"

# The same for the aggregate pushdown (`scan_segment_downsample`): the
# loop-side await of `_fold_segment`, the worker call that runs `host_prep`,
# `pack_sort` and the fold's stages. Its count is raw segments folded off
# the loop (one a segment a query); its sum less those inner stages is the
# wait for a thread and for the GIL. The pushdown has no span of its own:
# its stages land on the caller's span, whose readers take them off the
# span's duration, so this one stays out of the span's `stages` too.
FOLD_WAIT = "fold_wait"
WAIT_STAGES = frozenset((MERGE_WAIT, FOLD_WAIT))

STAGE_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_scan_stage_seconds",
    help="Per-stage scan time by lane (io_decode, host_prep, transfer, "
         "kernel, compile, ...): the request-attribution view of scanstats.",
    labelnames=("stage",),
    # OpenMetrics exemplars: each bucket remembers the trace id of its
    # latest observation, so a stage-latency spike on a dashboard links
    # straight to a /debug/traces/{id} span tree
    exemplars=True,
)
# Pre-register the canonical lanes so /metrics always exposes the full
# attribution surface (zero-count histograms), even before the first scan
# routes through a given lane on this process. `decode` is the
# encoded-lane expansion stage (storage/encoding.py + ops/decode.py) —
# first-class because the compressed-domain scan's whole bet is moving
# wall time from io_decode/transfer into this (much smaller) lane.
# The lanes of one fold of the aggregate pushdown (ops/aggregate.py
# `fold_sorted`), each under its own label: `fold_prep` (host: the
# dispatcher's choice, order keys, padding to the row class), `fold_h2d`,
# `fold_kernel` (dispatch of `downsample_fold` and the wait for it),
# `fold_d2h` (the grids back, sliced and decoded); `fold_host` is the whole
# fold where the host lane (reduceat) serves it. `pack_sort` is what comes
# before them on the packed route (`_packed_downsample_pass`: the host
# predicate, the kept rows' key, a sort only where they are out of order,
# the gathers).
FOLD_STAGES = ("fold_prep", "fold_h2d", "fold_kernel", "fold_d2h", "fold_host")

for _lane in ("io_decode", "host_prep", "transfer", "kernel", "compile",
              "decode", MERGE_WAIT, FOLD_WAIT, "pack_sort", *FOLD_STAGES):
    STAGE_SECONDS.labels(_lane)
del _lane

FLUSH_STAGE_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_flush_stage_seconds",
    help="Per-stage cost of a flush or direct write, every write: drain "
         "(memtable -> pk-sorted lanes), sort, encode (parquet), upload "
         "(object-store PUT), sidecar (bloom + encoded lanes), manifest "
         "(commit). A compaction's SST writes are not here.",
    labelnames=("table", "stage"),
    # OpenMetrics exemplars: a slow flush stage names the trace that
    # paid it (telemetry package wires the source)
    exemplars=True,
)
COMPACTION_STAGE_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_compaction_stage_seconds",
    help="Per-stage cost of a compaction task: scan (read + merge the "
         "inputs), encode (all output shards written), commit (manifest "
         "update), cleanup (physical deletes, tombstone and rollup GC); "
         "sst_encode / sst_upload / sst_sidecar are one output shard's, "
         "inside encode and concurrent.",
    labelnames=("stage",),
)
for _stage in ("scan", "encode", "commit", "cleanup"):
    COMPACTION_STAGE_SECONDS.labels(_stage)
del _stage

# Roofline-attribution lane of each stage (attribution(), query EXPLAIN):
# anything not listed is host-side work.
_BOUND_LANE = {
    "io_decode": "io",
    "h2d": "transfer",
    "d2h": "transfer",
    "transfer": "transfer",
    "device_merge": "kernel",
    "device_agg": "kernel",
    "fold_h2d": "transfer",
    "fold_d2h": "transfer",
    "fold_kernel": "kernel",
    "kernel": "kernel",
    "compile": "compile",
    "decode": "decode",
}


@dataclass
class ScanStats:
    seconds: dict[str, float] = field(default_factory=dict)
    counts: dict[str, int] = field(default_factory=dict)
    # instrumented-kernel invocations (common/xprof.py feeds this): which
    # device kernels this query actually ran, and how often
    kernels: dict[str, int] = field(default_factory=dict)
    # buffer-lineage ledger (common/memtrace.py): scan_stats() opens one
    # alongside the timing collector, so every query route carries the
    # pinned `memory` EXPLAIN verdict without per-handler wiring. None
    # under HORAEDB_MEMTRACE=off.
    mem: object = None
    # a query's segments merge on worker threads, several at once, and
    # every one of them folds into this collector
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False, compare=False)

    def add(self, stage: str, secs: float) -> None:
        with self._lock:
            self.seconds[stage] = self.seconds.get(stage, 0.0) + secs
            self.counts[stage] = self.counts.get(stage, 0) + 1

    def count(self, stage: str, n: int = 1) -> None:
        with self._lock:
            self.counts[stage] = self.counts.get(stage, 0) + n

    def as_dict(self) -> dict:
        out = {f"{k}_s": round(v, 4) for k, v in self.seconds.items()}
        out.update({k: v for k, v in self.counts.items() if k not in self.seconds})
        return out

    def attribution(self) -> dict:
        """Fold the raw stage seconds into the roofline lanes and name the
        binding one: `bound` in io | transfer | kernel | compile | host
        (None when nothing was timed). This is the live half of the
        roofline story — xprof's kernel catalog supplies the predicted
        FLOPs/bytes envelope, this supplies the measured split."""
        lanes = {"io": 0.0, "host": 0.0, "transfer": 0.0, "kernel": 0.0,
                 "compile": 0.0, "decode": 0.0}
        for stage_name, secs in self.seconds.items():
            if stage_name not in WAIT_STAGES:  # their inner stages are all here
                lanes[_BOUND_LANE.get(stage_name, "host")] += secs
        bound = max(lanes, key=lanes.get) if any(lanes.values()) else None
        return {
            "lanes_s": {k: round(v, 6) for k, v in lanes.items()},
            "bound": bound,
        }


_ACTIVE: ContextVar[ScanStats | None] = ContextVar("horaedb_scan_stats", default=None)

# Compile-time deduction cell of the innermost open stage() block (None
# outside any stage). Compiles fire INSIDE stage bodies — xprof's wrapper
# detects them mid-`device_agg`/`device_merge` — so without this the
# compile wall time would land in BOTH the enclosing stage's lane and the
# compile lane, the kernel lane would always dominate, and `bound` could
# never actually say "compile". record("compile", ...) credits the cell;
# stage() subtracts it from its own elapsed time on close and propagates
# it to the enclosing stage's cell (nested stages must deduct too).
_COMPILE_DEDUCT: ContextVar["_DeductCell | None"] = ContextVar(
    "horaedb_scan_compile_deduct", default=None
)


class _DeductCell:
    """Deduction accumulator for one open stage. Credits arrive from
    WORKER THREADS too — asyncio.to_thread copies the context, so the
    concurrent per-SST decodes under one io_decode stage all share the
    enclosing stage's cell — hence the lock (a bare `+=` is a lost-update
    race) and the cap: cumulative credit never exceeds the stage's
    elapsed wall, so overlapping thread-seconds deduct at most the time
    that could physically have overlapped and the stage's own lane never
    silently absorbs a negative."""

    __slots__ = ("_t0", "_total", "_lock", "seconds")

    def __init__(self) -> None:
        self._t0 = time.perf_counter()
        self._total = 0.0
        self._lock = threading.Lock()
        # what the stage observed, once it has closed (stage() yields the
        # cell: a caller that logs its own stages reads it here)
        self.seconds = 0.0

    def add(self, secs: float) -> None:
        with self._lock:
            self._total = min(
                self._total + secs, time.perf_counter() - self._t0
            )

    def total(self) -> float:
        with self._lock:
            return self._total


@contextmanager
def scan_stats():
    """Collect stage timings — and buffer lineage — for every scan
    inside the block: the memtrace ledger opens with the collector, so
    the per-query memory verdict needs no per-route plumbing."""
    from horaedb_tpu.common import memtrace

    st = ScanStats()
    token = _ACTIVE.set(st)
    try:
        with memtrace.mem_trace() as ledger:
            st.mem = ledger
            yield st
    finally:
        _ACTIVE.reset(token)


_thread_named = threading.local()
_PR_SET_NAME = 15


def name_thread(name: "str | None" = None) -> None:
    """Give the calling thread a name at the OS level, once: its Python
    name (`asyncio_3`, `sst_0`) unless one is given. The profiler names a
    host line after its thread, and every Python thread is born with the
    process's name: twenty lines called `python3` cannot be told apart —
    the benchmark's trace reduction keeps one line a name, so it would
    see one of them. A worker is named before its first annotation; the
    main thread only where the server names it (`horaedb-loop`: renaming
    it renames the process as `top` shows it). Linux only; elsewhere the
    lines keep their names."""
    if name is None and getattr(_thread_named, "done", False):
        return
    _thread_named.done = True
    thread = threading.current_thread()
    if name is None and thread is threading.main_thread():
        return
    try:
        prctl = ctypes.CDLL(None, use_errno=True).prctl
    except (OSError, AttributeError):
        return
    prctl.argtypes = (ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                      ctypes.c_ulong, ctypes.c_ulong)
    prctl.restype = ctypes.c_int
    # the kernel keeps 15 bytes: the tail of the name is what differs
    prctl(_PR_SET_NAME, (name or thread.name).encode()[-15:], 0, 0, 0)


class Family:
    """One layer's stages: where `stage()` observes them (`child(stage)`
    is the histogram child) and the `<name>.` prefix of their profiler
    annotations. `prefix` renames every stage (a compaction's own SST
    writes are `sst_encode`, not the task's `encode`)."""

    __slots__ = ("name", "_child", "_prefix", "_known")

    def __init__(self, name: str, child, prefix: str = ""):
        self.name = name
        self._child = child
        self._prefix = prefix
        # stage as the caller names it -> (stage, annotation, histogram
        # child): the write path runs on the event loop, where every
        # microsecond is paid by each request queued behind it
        self._known: dict[str, tuple] = {}

    def _lookup(self, stage: str) -> tuple:
        known = self._known.get(stage)
        if known is None:
            full = self._prefix + stage
            known = self._known[stage] = (
                full, f"{self.name}.{full}", self._child(full))
        return known

    def mark(self, stage: str) -> TraceAnnotation:
        """The profiler annotation alone, for a synchronous body on the
        worker thread that runs it: the stage itself is timed by the
        `stage()` that awaits the worker (queueing included) and marks a
        stage in progress on the loop's thread; this marks the thread
        that does the work."""
        name_thread()
        return TraceAnnotation(self._lookup(stage)[1])

    def on_worker(self, stage: str, fn, *args):
        """`fn(*args)` under `mark(stage)`: what a stage hands to its
        worker thread (`asyncio.to_thread(fam.on_worker, "parse", ...)`)."""
        with self.mark(stage):
            return fn(*args)

    def stage(self, stage: str) -> "_Stage":
        """Time one stage into (a) the family's histogram — ALWAYS, so
        attribution shows on /metrics without any collector — (b) the
        active trace span's `stages` attr, (c) the active per-query
        collector when one is attached and (d) the profiler's timeline.
        Stages wrap chunky work (a segment's decode, one device merge, a
        parquet encode), so two perf_counter calls, one histogram observe
        and one TraceMe (an atomic load with no session open) are noise
        next to the work itself. `with ... as cell`: `cell.seconds` is
        what the stage observed, once it has closed."""
        return _Stage(*self._lookup(stage))

    def fold(self, st: "ScanStats | None", stage: str, dt: float) -> None:
        """An externally timed duration into the three sinks that take
        one (record())."""
        full, _, child = self._lookup(stage)
        _fold(st, full, child, dt)


def _fold(st: "ScanStats | None", stage: str, child, dt: float) -> None:
    if st is not None:
        st.add(stage, dt)
    child.observe(dt)
    if stage != FOLD_WAIT:
        tracing.add_stage(stage, dt)


class _Stage:
    """One open stage (a plain context manager: a generator's frame costs
    a third again of the whole, on the loop's thread)."""

    __slots__ = ("_stage", "_child", "_annotation", "_st", "_cell", "_token", "_t0")

    def __init__(self, stage: str, annotation: str, child):
        self._stage = stage
        self._child = child
        self._annotation = TraceAnnotation(annotation)

    def __enter__(self) -> _DeductCell:
        name_thread()
        self._st = _ACTIVE.get()
        self._cell = cell = _DeductCell()
        self._token = _COMPILE_DEDUCT.set(cell)
        self._t0 = time.perf_counter()
        self._annotation.__enter__()
        return cell

    def __exit__(self, *exc) -> bool:
        self._annotation.__exit__(*exc)
        cell = self._cell
        deducted = cell.total()
        dt = cell.seconds = max(0.0, time.perf_counter() - self._t0 - deducted)
        _COMPILE_DEDUCT.reset(self._token)
        outer = _COMPILE_DEDUCT.get()
        if outer is not None:
            outer.add(deducted)
        _fold(self._st, self._stage, self._child, dt)
        return False


SCAN = Family("scan", lambda s: STAGE_SECONDS.labels(_STAGE_LANE.get(s, s)))
COMPACTION = Family("compaction", COMPACTION_STAGE_SECONDS.labels)
COMPACTION_SST = Family("compaction", COMPACTION_STAGE_SECONDS.labels,
                        prefix="sst_")


def flush_family(table: str) -> Family:
    """The flush stages of one table root."""
    return Family("flush", lambda s: FLUSH_STAGE_SECONDS.labels(table, s))


def stage(name: str):
    """One stage of the scan's lanes (`SCAN.stage`)."""
    return SCAN.stage(name)


@contextmanager
def deducted_stage(name: str):
    """stage() for expansion work that runs INSIDE another stage's block
    (the encoded read path's `decode` lane runs inside the callers'
    `io_decode` stages): times the body, subtracts any nested deduction
    credits (a first-use kernel compile fires mid-decode and records the
    compile lane via xprof) so the compile seconds are not counted in
    BOTH the compile and this lane, then records the net with
    record(..., deduct=True) so the enclosing stage deducts the whole
    wall — every second lands in exactly one lane."""
    cell = _DeductCell()
    token = _COMPILE_DEDUCT.set(cell)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = max(0.0, time.perf_counter() - t0 - cell.total())
        _COMPILE_DEDUCT.reset(token)
        outer = _COMPILE_DEDUCT.get()
        if outer is not None:
            # nested credits (compile) must also deduct from the
            # enclosing stage; record() below adds `dt` itself
            outer.add(cell.total())
        record(name, dt, deduct=True)


def record(name: str, secs: float, *, deduct: "bool | None" = None) -> None:
    """Fold an externally-timed duration in as if a stage() block measured
    it: collector + process histogram + active trace span. xprof reports
    compile time through this (the compile happens inside jax's dispatch,
    where no `with stage(...):` block can wrap it); a compile recorded
    inside an open stage is deducted from that stage so the time is
    attributed ONCE — to the compile lane. `deduct=True` extends the
    same once-only attribution to any lane recorded inside an enclosing
    stage (the encoded read path records its `decode` expansion and
    sidecar-fetch time this way from inside the callers' `io_decode`
    blocks — without the deduction, io would double-count every decode
    second and `bound` could never say "decode")."""
    if deduct is None:
        deduct = name == "compile"
    if deduct:
        cell = _COMPILE_DEDUCT.get()
        if cell is not None:
            cell.add(secs)
    SCAN.fold(_ACTIVE.get(), name, secs)


def kernel_use(name: str) -> None:
    """Note one invocation of an instrumented kernel on the active
    collector (no-op without one — one contextvar get, the same
    steady-state budget as span())."""
    st = _ACTIVE.get()
    if st is not None:
        with st._lock:
            st.kernels[name] = st.kernels.get(name, 0) + 1


def active() -> bool:
    """True when a collector is attached. Device paths use this to decide
    whether to fence async transfers for attribution: with no collector,
    skipping the fence lets H2D overlap kernel dispatch in the device
    queue (the un-fenced form is the production fast path)."""
    return _ACTIVE.get() is not None


def note(name: str, n: int = 1) -> None:
    """Bump a counter (e.g. rows decoded, path taken) on the active collector."""
    st = _ACTIVE.get()
    if st is not None:
        st.count(name, n)


def current() -> "ScanStats | None":
    """The active collector object (or None). The query batcher keys its
    concurrency signal on collector IDENTITY: a regioned query's N
    fan-out sub-queries share one collector, so they count as ONE client
    and a lone regioned query keeps the no-window fast path."""
    return _ACTIVE.get()


def get_note(name: str) -> "int | None":
    """Read a counter off the active collector (None without one or when
    the note was never set). The admission slot uses this to learn how
    wide a stacked launch its query rode (batched_with) without threading
    the batcher through the slot protocol."""
    st = _ACTIVE.get()
    return None if st is None else st.counts.get(name)


def note_max(name: str, n: int) -> None:
    """Record the MAXIMUM of `n` across the collector's lifetime instead
    of a running sum — for width-style facts (e.g. regions fanned out)
    that repeat per sub-query and would over-report if accumulated."""
    st = _ACTIVE.get()
    if st is not None:
        with st._lock:
            st.counts[name] = max(st.counts.get(name, 0), n)
