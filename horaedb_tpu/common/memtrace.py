"""Buffer-lineage ledger + copy-tax accounting for the data plane.

ROADMAP item 2 (the Arrow-native zero-copy data plane) demands
allocation-count regression tests on the scan path — but nothing in the
engine could SEE an allocation or a copy: ROOFLINE §4's copy-tax figure
was hand-derived. This module is the instrument. Every data-plane
hand-off (pooled-parser append, memtable seal/drain, flush encode,
parquet materialize, encoded-lane decode, host_prep lane conversion,
`jax.device_put` staging, cache fills, the cluster wire codec)
reports through ONE cheap funnel:

    track(buf, "materialize", "copy")        # size read off the buffer
    track_bytes(n, "h2d", "copy")            # size known directly
    arr = tracked_contiguous(arr, "wire_codec")   # the J024 funnel
    out = tracked_combine(table, "materialize")   # copy vs view decided
                                                  # by the chunk layout

Aggregation is two-level, mirroring storage/scanstats.py:

- **process-wide**: `horaedb_mem_bytes_total{stage,kind}` /
  `horaedb_mem_events_total{stage,kind}` counter families (+ the
  `horaedb_mem_device_staging_bytes_total` staging odometer) — the
  copy-tax table `GET /debug/memory` renders comes straight from these.
- **per-query**: a `MemLedger` contextvar opened by
  `scanstats.scan_stats()`, folded into the pinned `memory` EXPLAIN
  verdict (bytes allocated, copies vs views per stage, device staging
  bytes, peak-delta under deep mode).

Modes (`HORAEDB_MEMTRACE`, overridable via `[metric_engine.memory]`):

- `""` (default) — cheap lineage: one dict update on the per-query
  ledger + one cached counter inc per event. No tracemalloc.
- `"deep"` — per-query tracemalloc sampling: peak-delta bytes and the
  top allocation sites ride the verdict. Opt-in; attribution quality
  over speed.
- `"off"`  — `track()` returns its argument immediately; the funnel
  helpers still perform the underlying operation (the data path is
  IDENTICAL in every mode — only the accounting varies). mem-smoke
  measures this mode against the default to pin the <2% overhead bound.

Kinds are a closed vocabulary:

- `alloc` — a fresh buffer with no parent (arena growth, np.empty)
- `copy`  — bytes physically duplicated from a parent buffer
- `view`  — a new handle over existing bytes (zero-copy)
- `reuse` — a pooled buffer re-issued without allocation
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from contextvars import ContextVar

from horaedb_tpu.server.metrics import GLOBAL_METRICS

KINDS = ("alloc", "copy", "view", "reuse")

# Canonical lineage stages (the hand-off inventory in the module
# docstring). track() accepts any stage string — these are pre-registered
# so /metrics exposes the full copy-tax surface from boot (zero-count
# children), the same eager zero-state contract every other family keeps.
STAGES = (
    "parse", "append", "seal", "flush_encode", "materialize", "host_prep",
    "decode", "h2d", "result_fill", "rollup_fill", "wire_codec",
)

MEM_BYTES = GLOBAL_METRICS.counter(
    "horaedb_mem_bytes_total",
    help="Data-plane bytes by lineage stage and kind (alloc|copy|view|"
         "reuse): the process-lifetime copy-tax ledger.",
    labelnames=("stage", "kind"),
)
MEM_EVENTS = GLOBAL_METRICS.counter(
    "horaedb_mem_events_total",
    help="Data-plane buffer hand-off events by lineage stage and kind.",
    labelnames=("stage", "kind"),
)
DEVICE_STAGING = GLOBAL_METRICS.counter(
    "horaedb_mem_device_staging_bytes_total",
    help="Bytes staged host->device through the tracked jax.device_put "
         "hand-offs (a subset of the copy rows above, split out because "
         "transfer is its own roofline lane).",
)

# Label-resolution is a dict probe + lock in the registry; the hot path
# caches children per (stage, kind) so steady-state cost is one dict hit
# + one locked float add per family.
_BYTES_CHILD: dict = {}
_EVENTS_CHILD: dict = {}
for _s in STAGES:
    for _k in KINDS:
        _BYTES_CHILD[(_s, _k)] = MEM_BYTES.labels(_s, _k)
        _EVENTS_CHILD[(_s, _k)] = MEM_EVENTS.labels(_s, _k)
del _s, _k

_VALID_MODES = ("", "deep", "off")
MODES = _VALID_MODES  # public face (server/config.py validation)


def env_default() -> str:
    mode = os.environ.get("HORAEDB_MEMTRACE", "")
    return mode if mode in _VALID_MODES else ""


_MODE = env_default()


def configure(mode: str) -> None:
    """Set the tracing mode ("" | "deep" | "off"). build_app applies
    `[metric_engine.memory] memtrace`; tests pin modes explicitly."""
    global _MODE
    if mode not in _VALID_MODES:
        from horaedb_tpu.common.error import HoraeError

        raise HoraeError(
            f"memory.memtrace must be one of {_VALID_MODES}, got {mode!r}"
        )
    _MODE = mode


def mode() -> str:
    return _MODE


class MemLedger:
    """Per-query lineage accumulator, locked as ScanStats is: the
    per-SST decode workers and the per-segment merge workers of one query
    share the ledger through the copied context, several at once."""

    __slots__ = ("events", "device_bytes", "peak_delta", "top_sites", "_lock")

    def __init__(self) -> None:
        # (stage, kind) -> [events, bytes]
        self.events: dict[tuple[str, str], list] = {}
        self.device_bytes = 0
        self.peak_delta: int | None = None
        self.top_sites: list[dict] = []
        self._lock = threading.Lock()

    def add(self, stage: str, kind: str, nbytes: int) -> None:
        with self._lock:
            cell = self.events.get((stage, kind))
            if cell is None:
                self.events[(stage, kind)] = [1, nbytes]
            else:
                cell[0] += 1
                cell[1] += nbytes

    def merge(self, other: "MemLedger") -> None:
        """Fold a fragment's ledger in (the cluster coordinator grafts
        computing-node verdicts through verdict_merge, not this)."""
        for key, (n, b) in other.events.items():
            cell = self.events.get(key)
            if cell is None:
                self.events[key] = [n, b]
            else:
                cell[0] += n
                cell[1] += b
        self.device_bytes += other.device_bytes


_ACTIVE: ContextVar[MemLedger | None] = ContextVar(
    "horaedb_mem_ledger", default=None
)


@contextmanager
def mem_trace():
    """Open a per-query ledger (scan_stats() does this for every query
    route). Yields None in `off` mode — callers treat the ledger as
    opaque and read it back through verdict()."""
    if _MODE == "off":
        yield None
        return
    ledger = MemLedger()
    deep = _MODE == "deep"
    baseline = 0
    started_here = False
    if deep:
        import tracemalloc

        if not tracemalloc.is_tracing():
            tracemalloc.start()
            started_here = True
        baseline = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
    token = _ACTIVE.set(ledger)
    try:
        yield ledger
    finally:
        _ACTIVE.reset(token)
        if deep:
            import tracemalloc

            if tracemalloc.is_tracing():
                _current, peak = tracemalloc.get_traced_memory()
                ledger.peak_delta = max(0, peak - baseline)
                stats = tracemalloc.take_snapshot().statistics("lineno")
                ledger.top_sites = [
                    {
                        "site": f"{st.traceback[0].filename}:"
                                f"{st.traceback[0].lineno}",
                        "kib": round(st.size / 1024, 1),
                        "count": st.count,
                    }
                    for st in stats[:8]
                ]
                if started_here:
                    tracemalloc.stop()


def active() -> "MemLedger | None":
    return _ACTIVE.get()


def _nbytes(buf) -> int:
    """Best-effort size of a buffer-ish object: numpy arrays, jax arrays,
    pyarrow Tables/Arrays/Buffers all expose .nbytes; bytes-like fall
    back to len; everything else counts 0 (the EVENT still counts)."""
    nb = getattr(buf, "nbytes", None)
    if nb is not None:
        try:
            return int(nb)
        except (TypeError, ValueError):
            return 0
    if isinstance(buf, (bytes, bytearray, memoryview)):
        return len(buf)
    return 0


def track(buf, stage: str, kind: str = "copy"):
    """Record one buffer hand-off; returns `buf` so call sites can wrap
    expressions in-line. Off mode: one string compare, nothing else."""
    if _MODE == "off":
        return buf
    track_bytes(_nbytes(buf), stage, kind)
    return buf


def track_bytes(nbytes: int, stage: str, kind: str = "copy") -> None:
    """track() when the size is already known (spares the attr probe)."""
    if _MODE == "off":
        return
    key = (stage, kind)
    bc = _BYTES_CHILD.get(key)
    if bc is None:  # non-canonical stage: resolve once, then cached
        # (events first: another thread that finds the bytes child reads
        # the events child next)
        _EVENTS_CHILD[key] = MEM_EVENTS.labels(*key)
        bc = _BYTES_CHILD[key] = MEM_BYTES.labels(*key)
    bc.inc(nbytes)
    _EVENTS_CHILD[key].inc()
    ledger = _ACTIVE.get()
    if ledger is not None:
        ledger.add(stage, kind, nbytes)


def device_staged(nbytes: int, stage: str = "h2d") -> None:
    """Record a host->device staging transfer (jax.device_put and the
    Block upload paths): a copy row under `stage` PLUS the dedicated
    staging odometer and the verdict's device_staging_bytes."""
    if _MODE == "off":
        return
    track_bytes(nbytes, stage, "copy")
    DEVICE_STAGING.inc(nbytes)
    ledger = _ACTIVE.get()
    if ledger is not None:
        with ledger._lock:
            ledger.device_bytes += nbytes


# ---------------------------------------------------------------------------
# Funnel helpers — the J024-sanctioned spellings of the raw copy
# primitives on data-plane modules. Each performs EXACTLY the underlying
# operation and decides copy-vs-view honestly from the result.


def tracked_contiguous(arr, stage: str):
    """np.ascontiguousarray through the funnel: `view` when the input was
    already contiguous (numpy returns it unchanged), `copy` otherwise."""
    import numpy as np

    out = np.ascontiguousarray(arr)
    if _MODE != "off":
        track_bytes(
            int(out.nbytes), stage, "view" if out is arr else "copy"
        )
    return out


def tracked_copy(arr, stage: str):
    """Explicit `.copy()` through the funnel — always a copy."""
    out = arr.copy()
    if _MODE != "off":
        track_bytes(_nbytes(out), stage, "copy")
    return out


def tracked_concat(arrays, stage: str, axis: int = 0):
    """np.concatenate through the funnel — always materializes."""
    import numpy as np

    out = np.concatenate(arrays, axis=axis)
    if _MODE != "off":
        track_bytes(int(out.nbytes), stage, "copy")
    return out


def tracked_combine(obj, stage: str):
    """`.combine_chunks()` through the funnel: a single-chunk (or empty)
    Table/ChunkedArray combines without moving bytes (`view`); multiple
    chunks physically concatenate (`copy`)."""
    columns = getattr(obj, "columns", None)
    if columns is not None:  # pa.Table
        multi = any(col.num_chunks > 1 for col in columns)
    else:  # pa.ChunkedArray
        multi = obj.num_chunks > 1
    out = obj.combine_chunks()
    if _MODE != "off":
        track_bytes(_nbytes(out), stage, "copy" if multi else "view")
    return out


def tracked_concat_tables(tables, stage: str, **kw):
    """pa.concat_tables through the funnel — chunk aggregation, zero-copy
    (`view`): the result references the input buffers."""
    import pyarrow as pa

    out = pa.concat_tables(tables, **kw)
    if _MODE != "off":
        track_bytes(_nbytes(out), stage, "view")
    return out


# ---------------------------------------------------------------------------
# Verdict — the pinned EXPLAIN `memory` payload.

VERDICT_KEYS = (
    "enabled", "deep", "bytes_allocated", "bytes_copied", "allocs",
    "copies", "views", "reuses", "device_staging_bytes",
    "peak_delta_bytes", "per_stage", "top_sites",
)


def verdict(ledger: "MemLedger | None") -> dict:
    """Fold a ledger into the pinned `memory` EXPLAIN schema. None (off
    mode) renders the same keys with zero values and enabled=False, so
    dashboards never branch on key presence."""
    out = {
        "enabled": ledger is not None,
        "deep": False,
        "bytes_allocated": 0,
        "bytes_copied": 0,
        "allocs": 0,
        "copies": 0,
        "views": 0,
        "reuses": 0,
        "device_staging_bytes": 0,
        "peak_delta_bytes": None,
        "per_stage": {},
        "top_sites": [],
    }
    if ledger is None:
        return out
    per_stage: dict[str, dict] = {}
    for (stage, kind), (n, b) in sorted(ledger.events.items()):
        row = per_stage.setdefault(stage, {})
        row[kind] = n
        row[f"{kind}_bytes"] = b
        out[f"{kind}s" if kind != "copy" else "copies"] += n
        if kind in ("alloc", "copy"):
            out["bytes_allocated"] += b
        if kind == "copy":
            out["bytes_copied"] += b
    out["per_stage"] = per_stage
    out["device_staging_bytes"] = ledger.device_bytes
    out["peak_delta_bytes"] = ledger.peak_delta
    out["deep"] = ledger.peak_delta is not None
    out["top_sites"] = ledger.top_sites
    return out


def verdict_merge(base: dict, fragment: dict) -> dict:
    """Fold a computing node's shipped `memory` verdict into the
    coordinator's (the fleet-EXPLAIN graft): scalars add, per-stage rows
    add, peak-delta takes the max (peaks on different nodes do not sum),
    top sites concatenate and re-rank."""
    if not fragment or not fragment.get("enabled"):
        return base
    out = dict(base)
    out["enabled"] = True
    for k in ("bytes_allocated", "bytes_copied", "allocs", "copies",
              "views", "reuses", "device_staging_bytes"):
        out[k] = out.get(k, 0) + fragment.get(k, 0)
    per = {s: dict(row) for s, row in out.get("per_stage", {}).items()}
    for stage, row in fragment.get("per_stage", {}).items():
        mine = per.setdefault(stage, {})
        for k, v in row.items():
            mine[k] = mine.get(k, 0) + v
    out["per_stage"] = per
    peaks = [p for p in (out.get("peak_delta_bytes"),
                         fragment.get("peak_delta_bytes")) if p is not None]
    out["peak_delta_bytes"] = max(peaks) if peaks else None
    out["deep"] = out["peak_delta_bytes"] is not None
    sites = list(out.get("top_sites", ())) + list(
        fragment.get("top_sites", ()))
    out["top_sites"] = sorted(
        sites, key=lambda s: -s.get("kib", 0))[:8]
    return out


def copy_tax_table() -> list[dict]:
    """The process-lifetime per-stage copy-tax table (/debug/memory):
    one row per (stage, kind) seen since boot, ranked by bytes."""
    rows = []
    for (stage, kind), child in list(_BYTES_CHILD.items()):
        b = child.value
        n = _EVENTS_CHILD[(stage, kind)].value
        if n:
            rows.append({"stage": stage, "kind": kind,
                         "events": int(n), "bytes": int(b)})
    rows.sort(key=lambda r: -r["bytes"])
    return rows
