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
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field

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
for _lane in ("io_decode", "host_prep", "transfer", "kernel", "compile",
              "decode"):
    STAGE_SECONDS.labels(_lane)
del _lane

# Roofline-attribution lane of each stage (attribution(), query EXPLAIN):
# anything not listed is host-side work.
_BOUND_LANE = {
    "io_decode": "io",
    "h2d": "transfer",
    "d2h": "transfer",
    "transfer": "transfer",
    "device_merge": "kernel",
    "device_agg": "kernel",
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

    def add(self, stage: str, secs: float) -> None:
        self.seconds[stage] = self.seconds.get(stage, 0.0) + secs
        self.counts[stage] = self.counts.get(stage, 0) + 1

    def count(self, stage: str, n: int = 1) -> None:
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

    __slots__ = ("_t0", "_total", "_lock")

    def __init__(self) -> None:
        self._t0 = time.perf_counter()
        self._total = 0.0
        self._lock = threading.Lock()

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


@contextmanager
def stage(name: str):
    """Time one stage into (a) the active per-query collector when one is
    attached, (b) the process-wide `horaedb_scan_stage_seconds{stage=...}`
    histogram — ALWAYS, so lane attribution shows on /metrics without any
    collector — and (c) the active trace span's `stages` attr. Stages wrap
    chunky work (a segment's decode, one device merge), so the two
    perf_counter calls + one histogram observe are noise next to the work
    itself."""
    st = _ACTIVE.get()
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
            outer.add(cell.total())
        if st is not None:
            st.add(name, dt)
        STAGE_SECONDS.labels(_STAGE_LANE.get(name, name)).observe(dt)
        tracing.add_stage(name, dt)


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
    st = _ACTIVE.get()
    if st is not None:
        st.add(name, secs)
    STAGE_SECONDS.labels(_STAGE_LANE.get(name, name)).observe(secs)
    tracing.add_stage(name, secs)


def kernel_use(name: str) -> None:
    """Note one invocation of an instrumented kernel on the active
    collector (no-op without one — one contextvar get, the same
    steady-state budget as span())."""
    st = _ACTIVE.get()
    if st is not None:
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
        st.counts[name] = max(st.counts.get(name, 0), n)
