"""Scan pipeline: parquet SSTs -> device filter/merge/dedup -> record batches.

This module replaces the reference's DataFusion physical plan
(`build_df_plan`: ParquetExec -> FilterExec -> SortPreservingMergeExec ->
MergeExec, src/columnar_storage/src/read.rs:429-494) with a TPU execution
shape:

  1. host: row-group-pruned parquet reads per SST (the analog of the custom
     ParquetFileReaderFactory + pruning predicate, read.rs:66-93,459-463),
     fanned out concurrently;
  2. device: ONE fused XLA kernel per segment — predicate mask, k-way merge
     (sort over the concatenated block with rejected rows sunk to the tail),
     and last-value dedup mask (reference MergeExec semantics,
     read.rs:99-385);
  3. host: gather surviving rows, strip builtin columns unless keep_builtin,
     emit fixed-size record batches old->new.

Ordering contract preserved: output sorted by (pk..., __seq__), duplicates
collapsed per UpdateMode; filter runs BEFORE dedup exactly like the
reference's plan, so a newest-version row rejected by the predicate exposes
the older surviving version.

Append mode and binary value columns follow the hybrid path: the device
computes the sort permutation and group boundaries over the numeric key lanes
and the host applies pyarrow takes + BytesMergeOperator (SURVEY §7 risk (b)).
"""

from __future__ import annotations

import asyncio
import io
import logging
import os
import threading
import time
from collections import OrderedDict
from contextvars import ContextVar
from dataclasses import dataclass
from functools import lru_cache, wraps
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from horaedb_tpu.common import colblock
from horaedb_tpu.common import deadline as deadline_ctx
from horaedb_tpu.common import memtrace
from horaedb_tpu.common import tracing
from horaedb_tpu.common.bytebudget import GLOBAL_POOLS
from horaedb_tpu.common.error import HoraeError, ensure
from horaedb_tpu.common.xprof import xjit
from horaedb_tpu.objstore import ObjectStore
from horaedb_tpu.server.metrics import GLOBAL_METRICS
from horaedb_tpu.ops import aggregate as agg_ops
from horaedb_tpu.ops import dedup as dedup_ops
from horaedb_tpu.ops import filter as filter_ops
from horaedb_tpu.ops import sort as sort_ops
from horaedb_tpu.ops.blocks import (
    DEFAULT_PAD_MULTIPLE,
    PACK_SENTINEL,
    Block,
    arrow_column_to_numpy,
)
from horaedb_tpu.ops.filter import Predicate
from horaedb_tpu.storage import scanstats
from horaedb_tpu.storage.config import UpdateMode
from horaedb_tpu.storage.operator import BytesMergeOperator
from horaedb_tpu.storage.sst import SstFile, SstPathGenerator
from horaedb_tpu.storage.types import (
    RESERVED_COLUMN_NAME,
    SEQ_COLUMN_NAME,
    StorageSchema,
    TimeRange,
)

logger = logging.getLogger(__name__)

DEFAULT_SCAN_BATCH_SIZE = 8192

SCAN_PATH = GLOBAL_METRICS.counter(
    "horaedb_scan_path_total",
    help="Segment scans by the merge route(s) the planner took, named as "
         "EXPLAIN's scan_paths names them: host_merge (host SIMD), "
         "device_merge_packed / device_merge (single-device kernels), "
         "device_merge_sharded (cross-chip). One count a segment scan and "
         "route, however many chunks merged; a pushdown aggregate takes no "
         "merge route.",
    labelnames=("path",),
)
# pre-register so the route split is visible on /metrics from boot
for _p in ("host_merge", "device_merge_packed", "device_merge",
           "device_merge_sharded"):
    SCAN_PATH.labels(_p)
del _p

FOOTER_PRUNES = GLOBAL_METRICS.counter(
    "horaedb_scan_footer_prunes_total",
    help="Reads of an SST that pruned its row groups by a predicate, by "
         "what served the footer's statistics: lanes (the min/max arrays "
         "kept with the cached footer, already there) or walk (this read "
         "walked the footer's metadata objects, to build the lanes or for "
         "a leaf the lanes cannot decide: a column whose statistics are "
         "not numbers, a literal its dtype cannot hold).",
    labelnames=("served",),
)
for _s in ("lanes", "walk"):
    FOOTER_PRUNES.labels(_s)
del _s

# the routes of the segment scan in progress (scan_segment counts each once)
_ROUTES: ContextVar["set[str] | None"] = ContextVar("horaedb_scan_routes",
                                                    default=None)


def _route(name: str) -> None:
    """The planner took merge route `name`: EXPLAIN's `scan_paths` and
    `horaedb_scan_path_total` name it alike."""
    scanstats.note("path_" + name)
    routes = _ROUTES.get()
    if routes is not None:
        routes.add(name)


def _is_binary_like(t: pa.DataType) -> bool:
    """The single definition of 'cannot ride a device lane'."""
    return pa.types.is_binary(t) or pa.types.is_large_binary(t) or pa.types.is_string(t)


@dataclass
class ScanRequest:
    """Reference: storage.rs ScanRequest — range prunes SSTs (row-exact time
    filtering is the caller's predicate, matching reference semantics)."""

    range: TimeRange
    predicate: Predicate | None = None
    projections: list[int] | None = None
    # Skip SST files with id <= min_sst_id (file granularity — an SST's id
    # IS its write sequence). The index sidecar replay scans only what
    # landed after its watermark; compacted outputs get fresh (larger) ids,
    # so their old rows may reappear — callers must replay idempotently.
    min_sst_id: int | None = None


@dataclass
class CompactRequest:
    """Manual-compaction request (storage.rs:372-374; the reference's is an
    empty struct). `time_range` scopes the pick to SSTs overlapping it —
    None keeps the reference's compact-everything behavior."""

    time_range: "TimeRange | None" = None


@dataclass
class WriteRequest:
    batch: pa.RecordBatch
    time_range: TimeRange
    # Whether to check the batch is within the same segment (storage.rs:307-316).
    enable_check: bool = True
    # Caller guarantees the batch is already pk-sorted (e.g. the metric
    # engine's accumulator flush): the write path skips the sort AND the
    # O(n) sortedness verification.
    presorted: bool = False
    # Explicit sequence for the __seq__ column / FileMeta (defaults to the
    # SST's file id). Concurrent flush snapshots allocate their sequence at
    # snapshot-detach time so last-value dedup follows buffering order even
    # when a later snapshot's encode finishes first.
    seq: int | None = None
    # Ingest-flush writes opt into the fast parquet encode profile (L0
    # trade: ~2x faster encode, ~1.7x bytes until compaction re-encodes);
    # honored only when WriteConfig.flush_fast_encode is on.
    fast_encode: bool = False


# ---------------------------------------------------------------------------
# host<->device link profile + scan-path cost model
# ---------------------------------------------------------------------------


class _LinkProfile:
    """Measured host<->device transfer characteristics (module singleton).

    The materializing-scan planner needs real numbers, not assumptions: H2D
    bandwidth, D2H bandwidth and dispatch latency decide where the device
    merge starts to beat host SIMD. One 8 MB measurement per process, made
    on the first scan's own thread; a device that cannot be measured raises
    to that scan instead of being planned around."""

    _cached: dict | None = None
    _lock = threading.Lock()

    @classmethod
    def get(cls) -> dict:
        if cls._cached is None:
            with cls._lock:
                if cls._cached is None:
                    cls._cached = cls._measure()
        return cls._cached

    @staticmethod
    def _measure() -> dict:
        dev = jax.devices()[0]
        if dev.platform == "cpu":
            # same memory space ("transfer" is a memcpy), but the XLA
            # multi-key stable sort is single-core and ~1.6 us/row —
            # an order slower than numpy's packed argsort (measured on
            # the quick-baseline shape), so it must carry its real cost
            return {"h2d_bw": 8e9, "d2h_bw": 8e9, "dispatch_s": 1e-4,
                    "sort_s_per_row": 1.2e-6}
        warm = jax.jit(lambda x: x.sum())
        small = jax.device_put(np.arange(128, dtype=np.float32))
        # jaxlint: disable=J001 one-time link calibration, off the query path
        warm(small).block_until_ready()  # compile outside the clock
        t0 = time.perf_counter()
        # jaxlint: disable=J001 one-time link calibration, off the query path
        warm(small).block_until_ready()
        dispatch = max(time.perf_counter() - t0, 1e-5)
        probe = np.empty(8 << 20, np.uint8)
        t0 = time.perf_counter()
        d = jax.device_put(probe)
        # jaxlint: disable=J001 one-time link calibration, off the query path
        d.block_until_ready()
        h2d = len(probe) / max(time.perf_counter() - t0 - dispatch, 1e-6)
        t0 = time.perf_counter()
        np.asarray(d)
        d2h = len(probe) / max(time.perf_counter() - t0 - dispatch, 1e-6)
        # accelerator multi-key sort throughput prior (~4 ns/row per key
        # lane, 6 lanes on the scan shape)
        return {"h2d_bw": h2d, "d2h_bw": d2h, "dispatch_s": dispatch,
                "sort_s_per_row": 25e-9}


# host merge cost priors (measured microbench on the CI shape): stable u64
# argsort + pack + dedup ≈ 150-250 ns per SURVIVING row; vectorized
# predicate eval ≈ 2 ns/row per term. These only steer the host/device
# choice — being 2x off moves the crossover, not correctness.
_HOST_SORT_S_PER_ROW = 200e-9
_HOST_EVAL_S_PER_ROW = 2e-9


class _HostCalib:
    """Self-calibrating host-cost estimates (VERDICT r04 #6).

    The static numbers above are PRIORS; on any other machine they are
    faith. Every real (non-presorted) host merge and host predicate eval is
    timed in place and folded into a per-process EWMA, so a mis-set prior
    converges to this host's true speed after a few sizable scans and the
    host/device routing crossover lands where it belongs.

    Learning is one-sided by construction: observations only arrive on the
    routes actually taken, so a prior that wrongly makes the host look
    EXPENSIVE routes everything to the device and never self-corrects (the
    device side is covered by the measured _LinkProfile instead). The
    dangerous direction — a prior that makes the host look cheap — corrects
    itself, because the mis-routed host work is exactly what gets measured.

    `HORAEDB_PLANNER_CALIB=off` freezes the priors (A/B and routing tests
    that pin expectations to the static constants)."""

    ALPHA = 0.25          # EWMA weight per observation
    MIN_ROWS = 50_000     # below this, timer noise dominates the signal
    _sort = _HOST_SORT_S_PER_ROW
    _eval = _HOST_EVAL_S_PER_ROW
    # merges run on worker threads, several at once: an observation is a
    # read-modify-write of the estimate
    _lock = threading.Lock()

    @staticmethod
    def enabled() -> bool:
        return os.environ.get("HORAEDB_PLANNER_CALIB", "on") != "off"

    @classmethod
    def sort_s_per_row(cls) -> float:
        return cls._sort

    @classmethod
    def eval_s_per_row(cls) -> float:
        return cls._eval

    @classmethod
    def observe_sort(cls, rows: int, secs: float) -> None:
        if rows >= cls.MIN_ROWS and secs > 0 and cls.enabled():
            with cls._lock:
                cls._sort += cls.ALPHA * (secs / rows - cls._sort)

    @classmethod
    def observe_eval(cls, rows_terms: int, secs: float) -> None:
        if rows_terms >= cls.MIN_ROWS and secs > 0 and cls.enabled():
            with cls._lock:
                cls._eval += cls.ALPHA * (secs / rows_terms - cls._eval)

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._sort = _HOST_SORT_S_PER_ROW
            cls._eval = _HOST_EVAL_S_PER_ROW


# Block size past which an ambient mesh upgrades the packed merge to the
# cross-chip sample-sort (parallel/merge.py). Below it the all-to-all's
# fixed cost (extra device sort + exchange + per-device dispatch) outweighs
# the parallelism. Read per call like HORAEDB_SCAN_PATH, so A/B harnesses
# and the virtual-mesh dryrun can flip it after import.
def _sharded_min_rows() -> int:
    return int(os.environ.get("HORAEDB_SHARDED_MIN_ROWS", 4_000_000))


def _pack_sort_keys(
    col, sort_keys: tuple[str, ...], n: int
) -> tuple[np.ndarray, int] | None:
    """Pack the (pk..., __seq__) sort keys into ONE u64 per row: pk columns
    offset to their min, __seq__ replaced by its dense rank (sequences are
    ns-clock file ids — ranking costs one np.unique and saves ~50 bits).
    Returns (packed, seq_width) or None when a key is non-integer or the
    widths exceed 63 bits (bit 63 stays free as the reject/padding
    sentinel). Shared by the host argsort merge and the packed device
    kernel, so both orderings are definitionally identical."""
    if n == 0:
        return None
    encs: list[tuple[np.ndarray, int]] = []
    for name in sort_keys:
        a = col(name)
        if not np.issubdtype(a.dtype, np.integer):
            return None
        if name == SEQ_COLUMN_NAME:
            uniq = np.unique(a)
            enc = np.searchsorted(uniq, a).astype(np.uint64)
            width = max(1, int(len(uniq) - 1).bit_length())
        else:
            lo, hi = int(a.min()), int(a.max())
            span = hi - lo  # python ints: no overflow on u64/i64 extremes
            if span >= (1 << 63):
                return None
            if a.dtype == np.uint64:
                enc = a - np.uint64(lo)
            else:
                enc = (a.astype(np.int64) - lo).astype(np.uint64)
            width = max(1, span.bit_length())
        encs.append((enc, width))
    if sum(w for _, w in encs) > 63:
        return None
    packed = np.zeros(n, np.uint64)
    for enc, width in encs:
        packed = (packed << np.uint64(width)) | enc
    return packed, encs[-1][1]


_PACK_SENTINEL = PACK_SENTINEL  # shared masked-row contract (ops/blocks.py)


def _merge_rows(n: int) -> int:
    """Padded length of a device merge block: the power-of-two class of n
    (at least one default pad unit), so merges of nearby sizes share one
    compiled sort instead of paying the TPU's sort compile per size."""
    return sort_ops.pow2_rows(n, DEFAULT_PAD_MULTIPLE)

# once-per-process flag for the forced-sharded-without-mesh downgrade
# warning (the scanstats note still records every occurrence)
_warned_sharded_no_mesh = False


def _kernel_cache(maxsize: int):
    """`lru_cache` for a kernel builder, built under a lock: merges run
    on worker threads, and two that meet a new key at once must get ONE
    kernel. `lru_cache` alone would run the builder for each, and every
    kernel object is a jit of its own: a second compile of the same
    program, tens of seconds of it on a TPU."""
    def wrap(build):
        cached = lru_cache(maxsize=maxsize)(build)
        lock = threading.Lock()

        @wraps(build)
        def get(*key, **kw):
            with lock:
                return cached(*key, **kw)

        return get

    return wrap


@_kernel_cache(maxsize=2)
def _packed_merge_kernel(do_dedup: bool):
    """Single-lane merge kernel: the whole (pk..., seq-rank) ordering rides
    one u64 (rejected rows pre-sunk to the all-ones sentinel on host), so
    the device sorts TWO operands (key + iota) instead of mask + every key
    lane + iota — and only 8 bytes/row ever cross the link inbound, 4
    bytes/survivor outbound. Dedup needs no pk gathers: the group id is
    packed >> seq_width. `seq_width` is an operand, not part of the
    program: merges of different fan-in share one compiled sort."""

    @xjit(kernel="packed_merge")
    def kernel(packed, num_valid, seq_width):
        n = packed.shape[0]
        iota = jnp.arange(n, dtype=jnp.int32)
        sp, perm = jax.lax.sort((packed, iota), num_keys=1, is_stable=True)
        # valid rows (63-bit keys) sort strictly before sentinel rows
        inb = jnp.arange(n) < num_valid
        if do_dedup:
            grp = sp >> seq_width
            nxt = jnp.concatenate([grp[1:], grp[-1:]])
            keep = inb & ((jnp.arange(n) == num_valid - 1) | (nxt != grp))
        else:
            keep = inb
        kcnt = jnp.sum(keep)
        pos = jnp.where(keep, jnp.cumsum(keep) - 1,
                        kcnt + jnp.cumsum(~keep) - 1)
        out_idx = jnp.zeros(n, dtype=jnp.int32).at[pos].set(perm)
        return out_idx, kcnt

    return kernel


def _build_packed_index_kernel(seq_width: int, do_dedup: bool):
    """The packed merge for keys whose seq rank takes `seq_width` bits:
    fn(packed, num_valid) -> (out_idx, kept_count)."""
    kernel = _packed_merge_kernel(do_dedup)
    width = np.uint64(seq_width)
    return lambda packed, num_valid: kernel(packed, num_valid, width)


def _host_merge_indices(
    col_of,
    n_rows: int,
    sort_keys: tuple[str, ...],
    num_pk: int,
    mask: np.ndarray | None,
    do_dedup: bool,
    lanes=None,
) -> np.ndarray:
    """Vectorized host merge: filter -> stable sort by (pk..., __seq__) ->
    last-value dedup. Returns row indices (into the unfiltered input) in
    output order.

    `col_of(name)` returns the full numpy lane for a sort-key column. Rows
    are compacted through `mask` FIRST, so the O(n log n) sort runs on
    surviving rows only — the reason this path demolishes the device round
    trip on selective scans over slow links.

    With `lanes` (a colblock.ArrowLanes over the chunked scan table) the
    merge consumes lanes chunk-wise: the sortedness probe checks per-chunk
    order + chunk boundaries, and the mask compaction gathers survivors
    straight out of the per-chunk views — no full-column combine_chunks
    copy ever happens on this route (the r19 baseline's 4 host_prep
    copies).

    Sort strategy: pack all sort keys into one u64 (pk columns offset to
    their min, __seq__ replaced by its dense rank — sequences are ns-clock
    file ids, ranking costs one np.unique and saves ~50 bits) and run ONE
    stable argsort; fall back to np.lexsort when the packed widths exceed
    63 bits or a key is floating-point. Dedup = keep-last per pk group,
    matching the reference MergeExec's LastValueOperator (operator.rs:36-44).
    """
    if mask is not None:
        base = np.nonzero(mask)[0]
        n = len(base)
    else:
        base = None
        n = n_rows
    if n == 0:
        return np.empty(0, np.int64)

    def col(name: str) -> np.ndarray:
        if lanes is not None:
            if base is not None:
                return lanes.gather_sorted(name, base)
            return lanes.lane(name)
        a = np.asarray(col_of(name))
        return a[base] if base is not None else a

    if lanes is not None:
        presorted = _lanes_presorted(lanes, sort_keys)
    else:
        presorted = _rows_presorted(
            {k: np.asarray(col_of(k)) for k in sort_keys}, sort_keys
        )
    # presorted shortcut: a compacted segment (or one flush's disjoint
    # shards, pre-ordered by _order_tables_by_first_key) is already in
    # (pk..., seq) order — survivors keep input order and dedup is one
    # adjacent compare: O(n) total, no sort
    if presorted:
        if do_dedup:
            keep = np.zeros(n, dtype=bool)
            keep[-1] = True
            if lanes is not None and base is None:
                for name in sort_keys[:num_pk]:
                    keep[:-1] |= _adjacent_neq_chunked(lanes, name)
            else:
                for name in sort_keys[:num_pk]:
                    a = col(name)
                    keep[:-1] |= a[:-1] != a[1:]
            final = base[keep] if base is not None else np.nonzero(keep)[0]
        else:
            final = base if base is not None else np.arange(n)
        return final

    packres = _pack_sort_keys(col, sort_keys, n)
    if packres is not None:
        packed, seq_width = packres
        order = np.argsort(packed, kind="stable")
        if do_dedup:
            group = packed[order] >> np.uint64(seq_width)
            keep = np.empty(n, dtype=bool)
            keep[:-1] = group[:-1] != group[1:]
            keep[-1] = True
        else:
            keep = None
    else:
        order = np.lexsort(tuple(col(k) for k in reversed(sort_keys)))
        if do_dedup:
            keep = np.zeros(n, dtype=bool)
            keep[-1] = True
            for name in sort_keys[:num_pk]:
                a = col(name)[order]
                keep[:-1] |= a[:-1] != a[1:]
        else:
            keep = None

    final = base[order] if base is not None else order
    return final[keep] if keep is not None else final


@_kernel_cache(maxsize=256)
def _build_index_kernel(
    key_names: tuple[str, ...],
    sort_keys: tuple[str, ...],
    pk_names: tuple[str, ...],
    template: Predicate | None,
    use_mask: bool,
    do_dedup: bool,
    presorted: bool,
):
    """Index-only scan kernel: mask -> sort -> dedup -> COMPACTED surviving
    row indices. The device sees only the sort-key (+ predicate) lanes and
    returns kept_count + int32 indices — 4 bytes per surviving row across
    the link instead of every column in both directions. The host then
    materializes any column type (incl. binary) with one arrow take.

    `use_mask=True` takes a precomputed host mask as a lane (predicates
    referencing binary columns, or masks the planner already paid for);
    otherwise the predicate template evaluates on device.
    """

    def core(cols: dict, mask, num_valid):
        n = cols[sort_keys[0]].shape[0]
        valid = jnp.arange(n) < num_valid
        mask = mask & valid
        kept = jnp.sum(mask)
        if presorted:
            pos = jnp.where(mask, jnp.cumsum(mask) - 1,
                            kept + jnp.cumsum(~mask) - 1)
            perm = jnp.zeros(n, dtype=jnp.int32).at[pos].set(
                jnp.arange(n, dtype=jnp.int32)
            )
        else:
            # rejected/padding rows sink: ~mask is the most significant key
            perm = sort_ops.lexsort_perm([~mask, *(cols[k] for k in sort_keys)])
        if do_dedup:
            sorted_pk = {k: jnp.take(cols[k], perm, axis=0) for k in pk_names}
            keep = dedup_ops.dedup_last_value(sorted_pk, list(pk_names), kept)
        else:
            keep = jnp.arange(n) < kept
        kcnt = jnp.sum(keep)
        pos2 = jnp.where(keep, jnp.cumsum(keep) - 1,
                         kcnt + jnp.cumsum(~keep) - 1)
        out_idx = jnp.zeros(n, dtype=jnp.int32).at[pos2].set(perm.astype(jnp.int32))
        return out_idx, kcnt

    if use_mask:

        @xjit(kernel="index_merge_mask")
        def kernel(cols: dict, ext_mask, num_valid):
            return core(cols, ext_mask != 0, num_valid)

    else:

        @xjit(kernel="index_merge_filter")
        def kernel(cols: dict, literals: tuple, num_valid):
            n = cols[sort_keys[0]].shape[0]
            mask = filter_ops.eval_predicate(template, cols, literals)
            del n
            return core(cols, mask, num_valid)

    del key_names  # cache key only
    return kernel


def _is_f64(schema: StorageSchema, name: str) -> bool:
    i = schema.arrow_schema.get_field_index(name)
    return i >= 0 and pa.types.is_float64(schema.arrow_schema.field(i).type)


def _plan_and_merge(
    schema: StorageSchema,
    n: int,
    col_of,
    predicate: Predicate | None,
    host_mask_fn,
    binary_pred: bool,
    itemsize_of,
    defer_device: bool = False,
    lanes=None,
) -> "np.ndarray | object":
    """Decide host-SIMD vs index-only-device for one materializing merge and
    run it; returns surviving row indices in output order.

    `defer_device=True` splits the device route into issue/collect: the
    kernel is DISPATCHED (async, device queue) and a zero-arg `collect()`
    closure comes back instead of indices — the chunked scan uses this to
    double-buffer: chunk i's kernel runs while chunk i+1 decodes and packs
    on host (VERDICT r03 #2). Host routes always return indices directly.

    Cost model (all terms measured, see _LinkProfile): the device pays
    key-lane H2D + 4 B/survivor D2H + dispatch latency; the host pays a
    vectorized predicate eval over all rows plus sort/dedup/take over
    SURVIVING rows only. The host mask is evaluated lazily — when the device
    wins even at worst-case selectivity, the predicate ships as a template
    and evaluates on device (no host pass at all).

    `HORAEDB_SCAN_PATH` in {auto, host, device, sharded} overrides (A/B
    harnesses, tests). Binary-column predicates always evaluate on host (the
    device has no byte lanes) but may still merge on device via the mask lane;
    so do f64 predicates on a backend whose f64 is not exact.

    When an ambient mesh is installed (parallel/mesh.py) the packed route
    upgrades to the cross-chip sample-sort merge (parallel/merge.py) for
    blocks past `HORAEDB_SHARDED_MIN_ROWS` — the sharded analog of the
    reference's single-node SortPreservingMergeExec (read.rs:479-492);
    `sharded` mode forces it regardless of size (tests, dryrun).
    """
    pk_names = tuple(schema.primary_key_names)
    sort_keys = pk_names + (SEQ_COLUMN_NAME,)
    do_dedup = schema.update_mode == UpdateMode.OVERWRITE
    if n == 0:
        return np.empty(0, np.int64)

    pred_cols = filter_ops.pred_columns(predicate)
    mode = os.environ.get("HORAEDB_SCAN_PATH", "auto")
    if mode not in ("auto", "host", "device", "sharded"):
        # a typo'd override must fail LOUDLY: an unknown mode falling
        # through to auto would silently measure the wrong path — the
        # exact A/B-honesty failure the explicit modes exist to prevent
        raise HoraeError(
            f"HORAEDB_SCAN_PATH={mode!r} is not one of "
            "auto/host/device/sharded"
        )
    # an accelerator's f64 is lossy (ops/aggregate.py device_f64_is_exact):
    # f64 predicate lanes evaluate on the host and ride the mask lane, as
    # byte lanes do, and f64 sort keys keep the whole merge on the host
    host_pred, host_keys = binary_pred, False
    if not agg_ops.device_f64_is_exact():
        host_pred = host_pred or any(_is_f64(schema, c) for c in pred_cols)
        host_keys = any(_is_f64(schema, k) for k in sort_keys)
        ensure(not host_keys or mode in ("auto", "host"),
               f"HORAEDB_SCAN_PATH={mode} cannot sort f64 keys exactly on "
               "this backend")
    link = _LinkProfile.get()
    dispatch = link["dispatch_s"]

    def host_merge(mask: np.ndarray | None) -> np.ndarray:
        _route("host_merge")
        sel_rows = int(np.count_nonzero(mask)) if mask is not None else n
        t0 = time.perf_counter()
        with scanstats.stage("host_merge"):
            res = _host_merge_indices(
                col_of, n, sort_keys, len(pk_names), mask, do_dedup,
                lanes=lanes,
            )
        # feed the planner's rolling host-sort estimate — but only when the
        # merge actually sorted (the presorted O(n) shortcut is routed
        # unconditionally and would poison the per-row figure)
        if _presorted and not _presorted[0]:
            _HostCalib.observe_sort(sel_rows, time.perf_counter() - t0)
        return res

    key_bytes = sum(itemsize_of(name) for name in sort_keys)

    def device_merge_packed(mask):
        """Single-u64-lane device merge -> np.ndarray indices, a zero-arg
        collect closure (defer_device), or None when keys don't pack. Worth
        the ~30 ns/row host pack only when it saves more link time than it
        costs — i.e. slow links, exactly where the device path's H2D hurts.
        Routes to the cross-chip sample-sort merge when a mesh is ambient."""
        from horaedb_tpu.parallel.mesh import active_mesh

        mesh = active_mesh()
        if mode == "sharded" and mesh is None:
            # forced sharded with no ambient mesh: the likeliest harness
            # mistake (mesh install failed/skipped) — same honesty bar as
            # the unpackable fallback below, the downgrade must be visible.
            # The scanstats note records every occurrence; the log line is
            # once-per-process (this fires on EVERY chunk of every scan —
            # repeating it would bury the rest of the log)
            scanstats.note("path_sharded_fallback_no_mesh")
            global _warned_sharded_no_mesh
            if not _warned_sharded_no_mesh:
                _warned_sharded_no_mesh = True
                logger.warning(
                    "HORAEDB_SCAN_PATH=sharded but no mesh is active; "
                    "falling back to the single-device kernel (n=%d)", n,
                )
        # size-based upgrade only in auto mode: an explicit mode=device
        # must PIN the single-device kernel even on a mesh-active process,
        # or A/B harnesses silently measure the sharded path (the same
        # honesty bar the unpackable-fallback warning below holds)
        want_sharded = mesh is not None and (
            mode == "sharded"
            or (mode == "auto" and n >= _sharded_min_rows())
        )
        if not want_sharded and (key_bytes - 8) / link["h2d_bw"] < 30e-9:
            return None
        with scanstats.stage("host_prep"):
            packres = _pack_sort_keys(col_of, sort_keys, n)
            if packres is None:
                if mode == "sharded" or want_sharded:
                    # forced/auto-upgraded sharded mode downgrading is worth
                    # a trace: an A/B harness must not silently measure the
                    # single-device path (float or >63-bit keys don't pack)
                    scanstats.note("path_sharded_fallback_unpackable")
                    logger.warning(
                        "sharded merge requested but sort keys do not pack "
                        "into u64; falling back to the single-device lane "
                        "kernel (n=%d)", n,
                    )
                return None
            packed, seq_width = packres
            if mask is not None:
                packed = np.where(mask, packed, _PACK_SENTINEL)
                nv = int(np.count_nonzero(mask))
            else:
                nv = n
        if want_sharded:
            from horaedb_tpu.parallel.merge import sharded_packed_merge

            _route("device_merge_sharded")
            with scanstats.stage("device_merge"):
                res = sharded_packed_merge(
                    packed, seq_width, do_dedup, mesh, defer=defer_device
                )
            return res
        _route("device_merge_packed")
        with scanstats.stage("h2d"):
            block = Block.from_numpy({"__packed__": packed},
                                     pad_multiple=_merge_rows(n),
                                     pad_keys=("__packed__",))
            if scanstats.active():  # fence only for attribution
                # jaxlint: disable=J001 h2d attribution fence; profiling runs only
                jax.block_until_ready(list(block.columns.values()))
        with scanstats.stage("device_merge"):
            kernel = _build_packed_index_kernel(seq_width, do_dedup)
            out_idx, kcnt = kernel(block.columns["__packed__"], nv)
        if defer_device:
            return lambda: _collect_device(out_idx, kcnt)
        return _collect_device(out_idx, kcnt)

    def _collect_device(out_idx, kcnt) -> np.ndarray:
        """Sync point of a dispatched device merge (split out so deferred
        callers can overlap the kernel with the next chunk's host work)."""
        with scanstats.stage("device_merge"):
            k = int(kcnt)
        if k == 0:
            return np.empty(0, np.int64)
        with scanstats.stage("d2h"):
            return np.asarray(out_idx[:k]).astype(np.int64)

    def device_merge(mask):
        # -> np.ndarray indices, or a collect closure under defer_device
        if mask is not None or predicate is None:
            packed_res = device_merge_packed(mask)
            if packed_res is not None:
                return packed_res
        _route("device_merge")
        need = list(sort_keys)
        if mask is None:
            need += [c for c in sorted(pred_cols) if c not in need]
        arrays = {name: col_of(name) for name in need}
        with scanstats.stage("host_prep"):
            presorted = _rows_presorted(arrays, sort_keys)
            if mask is not None:
                arrays = dict(arrays)
                arrays["__mask__"] = mask.astype(np.uint8)
        with scanstats.stage("h2d"):
            block = Block.from_numpy(arrays, pad_multiple=_merge_rows(n),
                                     pad_keys=sort_keys)
            if scanstats.active():  # fence only for attribution
                # jaxlint: disable=J001 h2d attribution fence; profiling runs only
                jax.block_until_ready(list(block.columns.values()))
        with scanstats.stage("device_merge"):
            if mask is not None:
                kernel = _build_index_kernel(
                    tuple(block.names), sort_keys, pk_names, None, True,
                    do_dedup, presorted,
                )
                cols = {k: v for k, v in block.columns.items() if k != "__mask__"}
                out_idx, kcnt = kernel(cols, block.columns["__mask__"], block.num_valid)
            else:
                template, raw = filter_ops.split_literals(predicate)
                literals = filter_ops.literal_arrays(
                    template, raw, {k: v.dtype for k, v in block.columns.items()}
                )
                kernel = _build_index_kernel(
                    tuple(block.names), sort_keys, pk_names, template, False,
                    do_dedup, presorted,
                )
                out_idx, kcnt = kernel(block.columns, literals, block.num_valid)
        if defer_device:
            return lambda: _collect_device(out_idx, kcnt)
        return _collect_device(out_idx, kcnt)

    tmpl_bytes = key_bytes + sum(
        itemsize_of(c) for c in pred_cols if c not in sort_keys
    )

    def dev_cost(lane_bytes: int, sel: int) -> float:
        return (
            n * lane_bytes / link["h2d_bw"]
            + n * link["sort_s_per_row"]
            + sel * 4 / link["d2h_bw"]
            + 8 * dispatch
        )

    def host_cost(sel: int) -> float:
        # the arrow take that materializes survivors is paid identically by
        # both paths (the caller runs it on the returned indices), so it
        # appears in neither cost
        return sel * _HostCalib.sort_s_per_row()

    _presorted: list[bool] = []

    def keys_presorted() -> bool:
        """Lazily-computed-once: already in (pk..., seq) order? A compacted
        segment is; the host path then skips its sort entirely (O(n)
        adjacent compares, zero transfer), which no device route can beat."""
        if not _presorted:
            with scanstats.stage("host_prep"):
                if lanes is not None:
                    _presorted.append(_lanes_presorted(lanes, sort_keys))
                else:
                    _presorted.append(_rows_presorted(
                        {k: np.asarray(col_of(k)) for k in sort_keys},
                        sort_keys,
                    ))
        return _presorted[0]

    n_terms = (
        max(1, len(list(filter_ops.iter_nodes(predicate))))
        if predicate is not None else 1
    )

    def timed_eval() -> np.ndarray:
        t0 = time.perf_counter()
        mask = host_mask_fn()
        _HostCalib.observe_eval(n * n_terms, time.perf_counter() - t0)
        return mask

    def eval_mask() -> np.ndarray | None:
        if predicate is None:
            return None
        with scanstats.stage("host_filter"):
            return timed_eval()

    if mode == "device":
        if host_pred:
            return device_merge(eval_mask())
        return device_merge(None)
    if mode == "sharded":
        # force the cross-chip route: host-eval any predicate into a mask so
        # the packed path (the only sharded one) is always eligible
        return device_merge(eval_mask())
    if mode == "host" or host_keys:
        if host_keys:
            scanstats.note("path_host_f64_keys")
        return host_merge(eval_mask())
    # ambient-mesh auto upgrade (docs/operations.md): past the sharded
    # threshold the cross-chip merge supersedes the single-device cost
    # compare — dev_cost models ONE device and would undersell an N-chip
    # merge. Presorted blocks keep their O(n) host shortcut (no sort left
    # to shard).
    if n >= _sharded_min_rows() and not keys_presorted():
        from horaedb_tpu.parallel.mesh import active_mesh

        if active_mesh() is not None:
            return device_merge(eval_mask())
    if predicate is None:
        if not keys_presorted() and dev_cost(key_bytes, n) < host_cost(n):
            return device_merge(None)
        return host_merge(None)

    # auto with a predicate: if the device wins even at worst-case
    # selectivity, skip the host eval entirely
    eval_cost = n * _HostCalib.eval_s_per_row() * n_terms
    if not host_pred and dev_cost(tmpl_bytes, n) < eval_cost \
            and not keys_presorted():
        return device_merge(None)
    with scanstats.stage("host_filter"):
        mask = timed_eval()
        sel = int(np.count_nonzero(mask))
    if sel == 0:
        return np.empty(0, np.int64)
    if keys_presorted() or host_cost(sel) <= dev_cost(key_bytes + 1, sel):
        return host_merge(mask)
    return device_merge(mask)


# ---------------------------------------------------------------------------
# fused per-segment scan kernel
# ---------------------------------------------------------------------------


_HOST_MASK = "__hostmask__"


def _host_lane(sorted_cols: dict, name: str, bit_lanes: frozenset) -> np.ndarray:
    """One lane of a fused pass back on the host; an f64 lane that crossed
    as its i64 bits (`_fused_pass`) reads as f64 again."""
    a = np.asarray(sorted_cols[name])
    return a.view(np.float64) if name in bit_lanes else a


@_kernel_cache(maxsize=256)
def _build_scan_kernel(
    col_names: tuple[str, ...],
    sort_keys: tuple[str, ...],
    pk_names: tuple[str, ...],
    template: Predicate | None,
    do_dedup: bool,
    presorted: bool = False,
):
    """jit-compiled: mask -> sort(rejected to tail) -> dedup mask.

    Cache key is (schema columns, sort keys, predicate *template*, mode); the
    predicate's literal values are traced operands (ops/filter.py Slot), so a
    new constant reuses the compiled executable.

    `presorted`: the caller verified (host-side, O(n)) that rows are already
    (pk..., __seq__)-sorted — the common case: a compacted segment is one
    sorted SST, and one flush's shards are disjoint sorted ranges. The
    O(n log n) multi-key lexsort collapses to an O(n) STABLE partition
    (rejected rows sink, relative order preserved on both sides), built from
    two cumsums + one scatter of arange.
    """

    @xjit(kernel="scan_kernel")
    def kernel(cols: dict, literals: tuple, num_valid):
        n = cols[sort_keys[0]].shape[0]
        valid = jnp.arange(n) < num_valid
        mask = filter_ops.eval_predicate(template, cols, literals) & valid
        if _HOST_MASK in cols:  # the predicate was evaluated on the host
            mask = mask & (cols[_HOST_MASK] != 0)
        kept = jnp.sum(mask)
        if presorted:
            # stable partition: valid rows keep their (sorted) order as a
            # prefix, rejected/padding rows sink in order
            pos = jnp.where(mask, jnp.cumsum(mask) - 1,
                            kept + jnp.cumsum(~mask) - 1)
            perm = jnp.zeros(n, dtype=pos.dtype).at[pos].set(jnp.arange(n))
        else:
            # Rejected/padding rows sink: ~mask is the most significant
            # key. Single-key passes (ops/sort.py): the TPU compiler does
            # not finish a variadic sort over this many 64-bit lanes.
            perm = sort_ops.lexsort_perm([~mask, *(cols[k] for k in sort_keys)])
        sorted_cols = {k: jnp.take(v, perm, axis=0) for k, v in cols.items()}
        if do_dedup:
            keep = dedup_ops.dedup_last_value(sorted_cols, list(pk_names), kept)
        else:
            keep = jnp.arange(n) < kept
        starts = dedup_ops.run_starts(
            [sorted_cols[k] for k in pk_names], jnp.arange(n) < kept
        )
        return sorted_cols, perm, keep, starts, kept

    del col_names  # part of the cache key only
    return kernel


def _order_tables_by_first_key(tables: list, sort_keys) -> list:
    """Order per-SST tables by their first row's sort key (each SST is
    internally sorted, so the first row is its minimum). Non-overlapping
    SSTs — compaction's pk-partitioned outputs, one flush's shards — then
    concatenate into a fully sorted run and the scan kernel's presorted
    fast path replaces its lexsort with an O(n) partition. Overlapping
    SSTs are unaffected (the O(n) sortedness check still decides)."""
    if len(tables) <= 1:
        return tables

    def first_key(t):
        return tuple(t.column(k)[0].as_py() for k in sort_keys)

    return sorted(tables, key=first_key)


def _lanes_presorted(lanes, sort_keys: tuple) -> bool:
    """Chunk-aware `_rows_presorted` over a colblock.ArrowLanes: each
    chunk checks independently (zero-copy per-chunk views) and the chunk
    BOUNDARIES compare as scalar key tuples — no full-column
    materialization. Memoized per sort-key tuple across planner probes."""
    key = tuple(sort_keys)
    cached = lanes.presorted_cache.get(key)
    if cached is not None:
        return cached
    # The chunk boundaries first, as arrow scalars: inputs that overlap
    # (thirty flushes over the same series) fail here after a few scalar
    # reads, before any chunk is wrapped as numpy. On a worker thread
    # every arrow or numpy call that lets go of the GIL waits for it
    # again behind the event loop; a lane wrapped chunk by chunk is three
    # such calls a chunk.
    edges = lanes.chunk_edges(key)
    if any(nxt[0] < prev[1] for prev, nxt in zip(edges, edges[1:])):
        lanes.presorted_cache[key] = False
        return False
    chks = {k: lanes.chunks(k) for k in sort_keys}
    nch = len(chks[sort_keys[0]]) if chks[sort_keys[0]] else 0
    ok = True
    prev_last = None
    for i in range(nch):
        sub = {k: chks[k][i] for k in sort_keys}
        if len(sub[sort_keys[0]]) == 0:
            continue
        if not _rows_presorted(sub, key):
            ok = False
            break
        first = tuple(int(sub[k][0]) for k in sort_keys)
        if prev_last is not None and first < prev_last:
            ok = False
            break
        prev_last = tuple(int(sub[k][-1]) for k in sort_keys)
    lanes.presorted_cache[key] = ok
    return ok


def _adjacent_neq_chunked(lanes, name: str) -> np.ndarray:
    """`a[:-1] != a[1:]` for one lane, computed per chunk (+ boundary
    compares) — the presorted-dedup compare without a combine copy."""
    views = lanes.chunks(name)
    bounds = lanes.bounds
    n = int(bounds[-1])
    neq = np.zeros(max(n - 1, 0), dtype=bool)
    for i, v in enumerate(views):
        lo = int(bounds[i])
        if len(v) > 1:
            neq[lo:lo + len(v) - 1] = v[:-1] != v[1:]
        nxt = views[i + 1] if i + 1 < len(views) else None
        if nxt is not None and len(v) and len(nxt):
            neq[lo + len(v) - 1] = v[-1] != nxt[0]
    return neq


def _rows_presorted(arrays: dict, sort_keys: tuple) -> bool:
    """O(n) host check: nondecreasing lexicographic (pk..., __seq__) order.
    Vectorized compares; ~10 ms per 2M rows vs ~1.5 s for the device
    lexsort it lets the kernel skip."""
    n = len(arrays[sort_keys[0]])
    if n <= 1:
        return True
    tie = np.ones(n - 1, dtype=bool)
    for k in sort_keys:
        a = np.asarray(arrays[k])
        hd, tl = a[:-1], a[1:]
        lt = hd < tl
        if not np.all(~tie | lt | (hd == tl)):
            return False
        tie = tie & (hd == tl)
        if not tie.any():
            return True
    return True


# ---------------------------------------------------------------------------
# parquet IO with row-group pruning
# ---------------------------------------------------------------------------


class _FoldSpec(NamedTuple):
    """What an aggregate pushdown folds a segment's rows into: dense
    [len(series_ids), num_buckets] grids, row i for `series_ids[i]` (a
    SORTED array of series keys), bucket k for [t0 + k * bucket_ms, ...)."""

    series_ids: np.ndarray
    t0: int
    bucket_ms: int
    num_buckets: int
    with_minmax: bool

    def empty_grids(self) -> dict:
        shape = (len(self.series_ids), self.num_buckets)
        grids = {"sum": np.zeros(shape), "count": np.zeros(shape)}
        if self.with_minmax:
            grids["min"] = np.full(shape, np.inf)
            grids["max"] = np.full(shape, -np.inf)
        return grids

    def dense_sid(self, col: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(dense position, hit mask). Misses keep their MONOTONE
        searchsorted position (not -1): the sorted-segment compaction
        needs monotone keys, and misses are excluded via the reduction's
        weight column instead of a key sentinel."""
        pos = np.searchsorted(self.series_ids, col)
        pos_c = np.clip(pos, 0, max(0, len(self.series_ids) - 1))
        hit = self.series_ids[pos_c] == col
        return pos_c.astype(np.int32), hit


class ParquetReader:
    """Per-SST parquet access + the per-segment device pipeline
    (reference: read.rs ParquetReader/build_df_plan)."""

    def __init__(
        self,
        store: ObjectStore,
        sst_path_gen: SstPathGenerator,
        schema: StorageSchema,
        scan_block_rows: int = 32 * 1024 * 1024,
        scan_cache_bytes: int = 0,
        enc_cache_bytes: int = 32 * 1024 * 1024,
    ):
        self._store = store
        self._path_gen = sst_path_gen
        self._schema = schema
        self._scan_block_rows = scan_block_rows
        # SSTs are immutable: cache open parquet handles (footer + schema
        # already parsed) keyed by path — the analog of the reference's
        # footer-size hint on its ParquetFileReaderFactory (read.rs:78-93).
        # Entries are (handle, per-handle lock): reads run in worker threads
        # and a pyarrow handle must not serve two reads at once. Protocol:
        # readers hold the handle lock for the whole read (the inserting
        # reader publishes the lock ALREADY ACQUIRED); closers (LRU eviction,
        # evict_cached) pop under the cache lock then acquire the handle lock
        # before close, so a handle is never closed mid-read. A busy handle
        # falls back to a transient open.
        self._pf_cache: "OrderedDict[str, tuple[pq.ParquetFile, threading.Lock]]" = OrderedDict()
        self._pf_cache_cap = 128
        self._pf_cache_lock = threading.Lock()
        # file_id -> decoded bloom sidecar (None = probed, no sidecar).
        # SSTs are immutable so entries never go stale; deletes evict.
        self._bloom_cache: dict[int, "dict | None"] = {}
        self._bloom_lock = threading.Lock()
        # Block cache at ROW-GROUP granularity, keyed (sst_id, row_group,
        # columns): pruning still decides which groups a query touches (the
        # selective-query win stays intact), and repeat reads of the hot
        # groups skip object-store IO + parquet decode. Immutable SSTs keep
        # entries fresh; deletes evict; LRU by decoded bytes.
        self._blk_cache: "OrderedDict[tuple[int, int, tuple], pa.Table]" = OrderedDict()
        self._blk_cache_bytes = 0
        self._blk_cache_cap = scan_cache_bytes
        self._blk_lock = threading.Lock()
        # sst_id -> its footer (parquet FileMetaData, arrow schema, and the
        # row groups' min/max lanes once a read has pruned by them): lets a
        # read whose pruned row groups are ALL cached skip the store
        # entirely (footers are tiny; evicted with the sst)
        self._meta_cache: dict[int, _Footer] = {}
        # sst_id -> (decoded `.enc` sidecar, resident bytes). Value None =
        # probed, absent/unreadable. Encoded sidecars are immutable like
        # their SSTs; LRU by RESIDENT BYTES like the block cache above
        # (a 1M-row sidecar is ~MBs decoded — an entry-count bound would
        # leave the footprint unbounded across big SSTs), deletes evict,
        # cap 0 disables. Cold fetches single-flight per sst id so N
        # concurrent scans over a fresh tree pay one GET+decode, not N.
        self._enc_cache: "OrderedDict[int, tuple[object, int]]" = OrderedDict()
        self._enc_cache_bytes = 0
        self._enc_cache_cap = enc_cache_bytes
        self._enc_lock = threading.Lock()
        # sst_id -> (owning loop, future) for the in-flight sidecar fetch;
        # futures are loop-bound, so a caller on a DIFFERENT loop (engines
        # are occasionally driven from more than one) duplicates the fetch
        # rather than awaiting across loops
        self._enc_inflight: "dict[int, tuple[object, object]]" = {}
        # Zero-arg callable returning the table's current Visibility (or
        # None) — retention + tombstone masking applied to EVERY read_sst
        # result via the shared helper (storage/visibility.py, jaxlint
        # J010). Installed by ObjectBasedStorage; None = no masking.
        self.visibility_provider = None
        # Tombstones for evicted sst ids: an in-flight read racing a delete
        # must not repopulate the caches after eviction (the entry would
        # leak forever). Bounded FIFO — old ids' reads are long finished.
        self._evicted_ids: "OrderedDict[int, None]" = OrderedDict()
        # unified pool registry (common/bytebudget.py): the reader's two
        # byte-budgeted caches report occupancy via weakref providers
        # (readers are per-table and come and go with engines — a pushed
        # gauge would drift; the provider sums only live readers)
        GLOBAL_POOLS.register_provider(
            "scan", self,
            lambda r: (r._blk_cache_bytes, len(r._blk_cache)),
        )
        GLOBAL_POOLS.register_provider(
            "sidecar", self,
            lambda r: (r._enc_cache_bytes, len(r._enc_cache)),
        )
        if scan_cache_bytes:
            GLOBAL_POOLS.set_capacity("scan", scan_cache_bytes)
        if enc_cache_bytes:
            GLOBAL_POOLS.set_capacity("sidecar", enc_cache_bytes)

    def _tombstoned(self, sst_id: int) -> bool:
        return sst_id in self._evicted_ids

    def _footer_known(self, sst_id: int) -> bool:
        with self._blk_lock:
            return sst_id in self._meta_cache

    def _assemble_cached(self, sst_id: int, get, predicate):
        """Serve a read purely from cache when the footer is known and every
        pruned row group is resident: (table, kept row groups). No table =
        fall through to IO, with the row groups the footer's statistics
        kept where the selection ran (`_read_pruned` does not select
        again). The first selection by a footer walks every row group of
        it in Python: for a worker thread, never the loop's."""
        with self._blk_lock:
            footer = self._meta_cache.get(sst_id)
        if footer is None:
            return None, None
        keep = _select_row_groups(footer, predicate)
        if not keep:
            return footer.schema.empty_table(), keep
        parts = []
        for rg in keep:
            t = get(rg)
            if t is None:
                return None, keep
            parts.append(t)
        return memtrace.tracked_concat_tables(parts, "host_prep"), keep

    def _rg_cache_hooks(self, sst_id: int, cols_key: tuple):
        """(get, put) closures for _read_pruned, or None when disabled.

        `get` probes the reader's byte-bounded LRU of decoded row groups
        (`_blk_cache`, keyed `(sst, row group, columns)`), `put` is its
        size-gated insert. Blocks are cached pre-visibility (read_sst
        masks after assembly), so a later tombstone can never be
        skipped."""
        if self._blk_cache_cap <= 0:
            return None

        def get(rg: int):
            k = (sst_id, rg, cols_key)
            with self._blk_lock:
                t = self._blk_cache.get(k)
                if t is not None:
                    self._blk_cache.move_to_end(k)
            return t

        def put(rg: int, table: pa.Table) -> None:
            size = table.nbytes
            if size > self._blk_cache_cap // 4:
                return  # one entry must not dominate the cache
            k = (sst_id, rg, cols_key)
            with self._blk_lock:
                if self._tombstoned(sst_id) or k in self._blk_cache:
                    return
                self._blk_cache[k] = table
                self._blk_cache_bytes += size
                while self._blk_cache_bytes > self._blk_cache_cap and self._blk_cache:
                    _k, old = self._blk_cache.popitem(last=False)
                    self._blk_cache_bytes -= old.nbytes
                    GLOBAL_POOLS.note_eviction("scan")

        return get, put

    async def _bloom_skip(self, sst: SstFile, predicate) -> bool:
        """True when the SST's bloom sidecar proves no row can satisfy the
        predicate's conjunctive equality constraints (storage/bloom.py).
        Sound under the engine's filter-BEFORE-dedup plan order."""
        from horaedb_tpu.storage import bloom as bloom_mod

        constraints = bloom_mod.eq_constraints(predicate)
        if not constraints:
            return False
        with self._bloom_lock:
            probed = sst.id in self._bloom_cache
            blooms = self._bloom_cache.get(sst.id)
        if not probed:
            from horaedb_tpu.objstore import NotFound

            try:
                data = await self._store.get(self._path_gen.generate_bloom(sst.id))
                blooms = bloom_mod.decode_blooms(data)
            except NotFound:
                blooms = None
            except Exception:  # noqa: BLE001 — corrupt sidecar: never prune
                logger.warning("unreadable bloom sidecar for sst %d", sst.id)
                blooms = None
            with self._bloom_lock:
                self._bloom_cache[sst.id] = blooms
        if blooms is None:
            return False
        return bloom_mod.can_skip(blooms, constraints)

    async def read_sst(
        self,
        sst: SstFile,
        columns: list[str] | None,
        predicate: Predicate | None,
        use_block_cache: bool = True,
    ) -> pa.Table:
        """Read one SST's projected columns, skipping row groups whose
        min/max statistics can't satisfy the predicate (and whole SSTs whose
        bloom sidecar rules the predicate out). Format-v2 SSTs serve
        qualifying reads from the encoded-lane sidecar instead (predicates
        evaluate on the encoded form, pages prune on zone maps, lanes
        decode through the sanctioned funnel) — per SST, so mixed v1/v2
        trees scan exactly with each file on its own path."""
        got = await self._open_sst(sst, columns, predicate, use_block_cache)
        return await asyncio.to_thread(got) if callable(got) else got

    async def _open_sst(
        self,
        sst: SstFile,
        columns: list[str] | None,
        predicate: Predicate | None,
        use_block_cache: bool = True,
    ):
        """Everything of `read_sst` that may await, up to the parquet
        decode: the table itself where none is needed (pruned by its bloom
        sidecar or served from encoded lanes), else the read as a
        zero-argument synchronous call for a worker thread, which returns
        the table as `read_sst` does (counted, masked, a vanished file
        raised as NotFound). The call begins with the block cache's probe:
        the selection behind it (`_select_row_groups`) walks a footer it
        has not seen in Python over every row group, so it runs once an
        SST a read and never here, on the loop's thread."""
        # cooperative deadline per SST read: an expired query stops
        # paying IO + decode here, SST by SST (common/deadline.py)
        deadline_ctx.check("sst_read")
        path = self._path_gen.generate(sst.id)
        if predicate is not None and await self._bloom_skip(sst, predicate):
            # EXPLAIN provenance: this SST never cost any IO
            scanstats.note("ssts_bloom_pruned")
            fields = [
                f for f in self._schema.arrow_schema
                if columns is None or f.name in columns
            ]
            return pa.schema(fields).empty_table()
        if sst.meta.format_version >= 2:
            from horaedb_tpu.ops import decode as decode_ops

            if decode_ops.scan_mode() != "raw":
                enc = await self._enc_sidecar(sst)
                if enc is not None:
                    # off-loop like the parquet decode below: a full-SST
                    # numpy expansion (and, on first use, the decode
                    # calibration micro-A/B incl. kernel compiles) must
                    # not freeze the event loop's admission/deadline/
                    # cancellation machinery
                    try:
                        table = await asyncio.to_thread(
                            scanstats.SCAN.on_worker, "io_decode",
                            self._read_encoded, enc, columns, predicate,
                        )
                    except Exception:  # noqa: BLE001 — the parquet
                        # object is authoritative: ANY malformed-sidecar
                        # decode error (truncated payload a header-level
                        # check missed, lying page metadata) degrades
                        # this read, never 500s the query
                        logger.warning(
                            "encoded read failed for sst %d; falling "
                            "back to parquet", sst.id, exc_info=True,
                        )
                        table = None
                    if table is not None:
                        scanstats.note("ssts_read")
                        scanstats.note("ssts_encoded")
                        # per-tenant usage provenance (telemetry/metering):
                        # bytes this query MATERIALIZED from storage (the
                        # decoded size — the work done for this tenant;
                        # wire-size compression provenance is the separate
                        # encoded_bytes/decoded_bytes pair)
                        scanstats.note("bytes_scanned", int(table.nbytes))
                        return self._mask_visibility(sst, table)
        scanstats.note("ssts_read")
        cols_key = tuple(sorted(columns)) if columns is not None else ("*",)
        rg_cache = self._rg_cache_hooks(sst.id, cols_key) if use_block_cache else None
        local = self._store.local_path(path)
        # the row groups the footer's statistics keep, once the selection
        # has run: it runs once an SST a read, and on a worker
        keep = data = None
        if local is None:
            # a store with no local files hands the object over as bytes,
            # worth a GET only where the block cache cannot serve: there
            # the probe takes a hop of its own, before the GET
            if rg_cache is not None and self._footer_known(sst.id):
                cached, keep = await asyncio.to_thread(
                    scanstats.SCAN.on_worker, "io_decode",
                    self._assemble_cached, sst.id, rg_cache[0], predicate)
                if cached is not None:
                    scanstats.note("bytes_scanned", int(cached.nbytes))
                    return self._mask_visibility(sst, cached)
            data = await self._store.get(path)

        def meta_sink(footer: _Footer) -> None:
            with self._blk_lock:
                if not self._tombstoned(sst.id):
                    self._meta_cache.setdefault(sst.id, footer)

        def _close_evicted(evicted) -> None:
            if evicted is not None:
                old, old_lock = evicted
                with old_lock:  # wait out any in-flight read
                    old.close()

        def pruned(pf: pq.ParquetFile, kept) -> pa.Table:
            return _read_pruned(pf, columns, predicate, rg_cache,
                                meta_sink if rg_cache else None, kept)

        def _read() -> pa.Table:
            if data is not None:
                return pruned(pq.ParquetFile(io.BytesIO(data)), keep)
            kept = None
            if rg_cache is not None:
                cached, kept = self._assemble_cached(
                    sst.id, rg_cache[0], predicate)
                if cached is not None:
                    return cached
            with self._pf_cache_lock:
                entry = self._pf_cache.get(path)
                if entry is not None:
                    self._pf_cache.move_to_end(path)
            if entry is not None:
                pf, handle_lock = entry
                if handle_lock.acquire(blocking=False):
                    try:
                        return pruned(pf, kept)
                    finally:
                        handle_lock.release()
                # handle busy with a concurrent read: open transient
            pf = pq.ParquetFile(local)
            my_lock = threading.Lock()
            my_lock.acquire()  # published pre-acquired: we read it first
            inserted = False
            evicted = None
            if entry is None:
                with self._pf_cache_lock:
                    if path not in self._pf_cache:
                        self._pf_cache[path] = (pf, my_lock)
                        inserted = True
                        if len(self._pf_cache) > self._pf_cache_cap:
                            _, evicted = self._pf_cache.popitem(last=False)
            try:
                return pruned(pf, kept)
            finally:
                my_lock.release()
                if not inserted:
                    pf.close()  # transient handle (cache busy or lost race)
                _close_evicted(evicted)

        def decode() -> pa.Table:
            from horaedb_tpu.objstore import NotFound

            # a caller's `io_decode` stage marks a stage in progress on
            # the thread that awaits; this marks the thread that decodes
            try:
                table = scanstats.SCAN.on_worker("io_decode", _read)
            except FileNotFoundError as e:
                # compaction deleted the file after the caller's manifest
                # snapshot; normalized so scan layers can refresh + retry
                raise NotFound(f"sst object vanished: {path}") from e
            # block-cache-served reads charge the same materialized bytes
            # as cold reads: usage metering must not depend on which cache
            # layer answered an identical query
            scanstats.note("bytes_scanned", int(table.nbytes))
            return self._mask_visibility(sst, table)

        return decode

    async def _enc_sidecar(self, sst: SstFile):
        """Cached decoded `.enc` sidecar of a format-v2 SST, or None
        (absent/corrupt — the parquet path covers it; a manifest-registered
        v2 SST always has one, so a miss is a degraded store, not a bug)."""
        loop = asyncio.get_running_loop()
        fut = None
        while True:
            with self._enc_lock:
                hit = self._enc_cache.get(sst.id)
                if hit is not None:
                    self._enc_cache.move_to_end(sst.id)
                    return hit[0]
                flight = self._enc_inflight.get(sst.id)
                if flight is None:
                    fut = loop.create_future()
                    self._enc_inflight[sst.id] = (loop, fut)
                    break
            f_loop, f_fut = flight
            if f_loop is not loop:
                break  # cross-loop caller: duplicate the fetch for this read
            # single-flight: the leader resolves the future with its verdict
            # (None on a transient failure — this read falls back to parquet)
            return await f_fut
        enc, cacheable = None, False
        try:
            enc, cacheable = await self._fetch_enc_sidecar(sst)
        finally:
            if fut is not None:
                if cacheable:
                    self._enc_cache_put(sst.id, enc)
                with self._enc_lock:
                    entry = self._enc_inflight.get(sst.id)
                    if entry is not None and entry[1] is fut:
                        del self._enc_inflight[sst.id]
                if not fut.done():
                    fut.set_result(enc)
        if fut is None and cacheable:
            self._enc_cache_put(sst.id, enc)
        return enc

    def _enc_cache_put(self, sst_id: int, enc) -> None:
        if self._enc_cache_cap <= 0:
            return
        nbytes = 64 if enc is None else enc.footprint_bytes() + 64
        with self._blk_lock:
            tomb = self._tombstoned(sst_id)
        with self._enc_lock:
            if not tomb and sst_id not in self._enc_cache:
                self._enc_cache[sst_id] = (enc, nbytes)
                self._enc_cache_bytes += nbytes
                while self._enc_cache_bytes > self._enc_cache_cap and self._enc_cache:
                    _, (_, nb) = self._enc_cache.popitem(last=False)
                    self._enc_cache_bytes -= nb
                    GLOBAL_POOLS.note_eviction("sidecar")

    async def _fetch_enc_sidecar(self, sst: SstFile):
        """One store fetch + decode of an SST's `.enc` object. Returns
        (enc-or-None, cacheable): transient store failures are NOT
        cacheable (the SST is immutable; a cached None would downgrade it
        to parquet for the entry's lifetime), NotFound and corrupt bytes
        are deterministic verdicts and are."""
        from horaedb_tpu.objstore import NotFound
        from horaedb_tpu.storage import encoding as enc_mod

        t0 = time.perf_counter()
        try:
            # deducted record, not a nested stage(): the callers wrap
            # read_sst in their own io_decode block, and a nested stage
            # would double-attribute this fetch to the io lane
            data = await self._store.get(self._path_gen.generate_enc(sst.id))
        except NotFound:
            enc = None  # definitively absent: cacheable
        except Exception:  # noqa: BLE001 — a TRANSIENT store failure
            # (breaker open, retries exhausted, deadline spent) must not
            # poison the cache. Fall back for THIS read only.
            logger.warning(
                "enc sidecar fetch failed for sst %d (transient; "
                "falling back to parquet for this read)", sst.id,
            )
            scanstats.record(
                "io_decode", time.perf_counter() - t0, deduct=True
            )
            return None, False
        else:
            try:
                enc = enc_mod.decode_blob(data)
                if enc.num_rows != sst.meta.num_rows:
                    raise HoraeError(
                        f"enc sidecar rows {enc.num_rows} != "
                        f"sst {sst.meta.num_rows}"
                    )
            except Exception:  # noqa: BLE001 — corrupt sidecar bytes are
                # deterministic (the object is immutable): cache the miss;
                # the parquet object remains authoritative
                logger.warning("unreadable enc sidecar for sst %d", sst.id)
                enc = None
        scanstats.record("io_decode", time.perf_counter() - t0, deduct=True)
        return enc, True

    def _read_encoded(self, enc, columns, predicate) -> "pa.Table | None":
        """Serve one SST read from its encoded sidecar: per-page zone
        pruning, predicate evaluation on the ENCODED form (rle run
        skipping, dict-id rewrite — storage/encoding.py), then decode of
        the surviving pages only, through the dispatcher-chosen funnel
        (ops/decode.py device kernels or the host numpy funnel). None =
        the sidecar does not cover the requested lanes; caller falls back
        to parquet. Row-exact: the predicate filter here runs BEFORE the
        merge exactly like the reference plan's FilterExec, so dropping
        rejected rows early is semantically identical to the parquet
        path's later row-wise mask."""
        from horaedb_tpu.ops import decode as decode_ops
        from horaedb_tpu.storage import encoding as enc_mod

        schema = self._schema.arrow_schema
        names = [
            f.name for f in schema if columns is None or f.name in columns
        ]
        if any(n not in enc.lanes for n in names):
            return None
        fields = [schema.field(schema.names.index(n)) for n in names]
        keep_pages, pruned = enc_mod.prune_pages(enc, predicate)
        if pruned:
            scanstats.note("pages_pruned", pruned)
        # per-lane encoding provenance (EXPLAIN `encoding.lanes`)
        for n in names:
            scanstats.note(f"enclane_{n}={enc.lanes[n].codec}", 0)
        if not keep_pages:
            return pa.schema(fields).empty_table()

        def lane_decode(n: str) -> np.ndarray:
            """Full-lane decode through the CALIBRATED dispatcher — the
            single decode entry for predicate eval and materialization,
            so the env pin and the decode_impl provenance cover both."""
            lane = enc.lanes[n]
            rows = sum(lane.pages[p].rows for p in keep_pages)
            impl = decode_ops.choose(lane.codec, rows)
            scanstats.note(f"decode_impl_{impl}", 0)
            return enc_mod.decode_lane(lane, keep_pages, impl=impl)

        # deducted stage, not a nested stage(): read_sst runs inside the
        # callers' io_decode stage blocks, and attribution must count the
        # expansion ONCE — in the decode lane, with any first-use kernel
        # compile inside the block deducted into ITS lane, not both
        with scanstats.deducted_stage("decode"):
            decoded: dict[str, np.ndarray] = {}
            mask = None
            if predicate is not None:
                stats = enc_mod.EncodedEvalStats()
                mask = enc_mod.encoded_mask(
                    enc, predicate, keep_pages, stats, decoded,
                    decode=lane_decode,
                )
                if stats.runs_skipped:
                    scanstats.note("runs_skipped", stats.runs_skipped)
                if mask is not None and bool(mask.all()):
                    mask = None  # nothing rejected: skip the take
            sel = np.nonzero(mask)[0] if mask is not None else None
            if sel is not None and len(sel) == 0:
                return pa.schema(fields).empty_table()
            arrays = []
            enc_bytes = dec_bytes = 0
            for n in names:
                lane = enc.lanes[n]
                if lane.codec == "null":
                    count = len(sel) if sel is not None else sum(
                        lane.pages[p].rows for p in keep_pages
                    )
                    arrays.append(pa.nulls(count, fields[names.index(n)].type))
                    continue
                arr = decoded.get(n)
                if arr is None:
                    arr = lane_decode(n)
                enc_bytes += sum(lane.pages[p].length for p in keep_pages)
                dec_bytes += arr.nbytes
                if sel is not None:
                    arr = arr[sel]
                arrays.append(_np_to_arrow(arr, fields[names.index(n)].type))
            scanstats.note("encoded_bytes", enc_bytes)
            scanstats.note("decoded_bytes", dec_bytes)
            # lineage: every decoded lane is a fresh host buffer
            memtrace.track_bytes(dec_bytes, "decode", "alloc")
        return pa.Table.from_arrays(arrays, schema=pa.schema(fields))

    def _mask_visibility(self, sst: SstFile, table: pa.Table) -> pa.Table:
        """Retention + tombstone masking via the SHARED helper
        (storage/visibility.py) — the single funnel every scan route,
        the downsample pushdown, and compaction read through. Applied
        AFTER the block cache (cache entries stay raw/immutable; a
        tombstone created later still masks cached hits) and BEFORE the
        merge (exact for last-writer-wins, see the helper's contract)."""
        if self.visibility_provider is None or table.num_rows == 0:
            return table
        vis = self.visibility_provider()
        if vis is None:
            return table
        from horaedb_tpu.storage.visibility import apply_visibility

        return apply_visibility(table, vis, sst_range=sst.meta.time_range)

    def evict_cached(self, file_id: int) -> None:
        """Drop the cached handle of a deleted SST (compaction calls this
        before physical deletes so file descriptors don't linger)."""
        with self._pf_cache_lock:
            entry = self._pf_cache.pop(self._path_gen.generate(file_id), None)
        with self._bloom_lock:
            self._bloom_cache.pop(file_id, None)
        with self._enc_lock:
            ent = self._enc_cache.pop(file_id, None)
            if ent is not None:
                self._enc_cache_bytes -= ent[1]
        with self._blk_lock:
            self._meta_cache.pop(file_id, None)
            for key in [k for k in self._blk_cache if k[0] == file_id]:
                self._blk_cache_bytes -= self._blk_cache.pop(key).nbytes
            self._evicted_ids[file_id] = None
            while len(self._evicted_ids) > 65536:
                self._evicted_ids.popitem(last=False)
        if entry is not None:
            pf, handle_lock = entry
            with handle_lock:  # wait out any in-flight read
                pf.close()

    async def scan_segment(
        self,
        ssts: list[SstFile],
        predicate: Predicate | None,
        projections: list[int] | None,
        keep_builtin: bool,
        batch_size: int = DEFAULT_SCAN_BATCH_SIZE,
        use_block_cache: bool = True,
    ) -> list[pa.RecordBatch]:
        """Traced entry point of the per-segment pipeline: the span anchors
        the per-stage lane timings (scanstats bridges every stage() into the
        active span's `stages` attr) for /debug/traces."""
        routes: set[str] = set()
        token = _ROUTES.set(routes)
        try:
            with tracing.span(
                "scan_segment", ssts=len(ssts),
                rows=sum(s.meta.num_rows for s in ssts),
            ):
                return await self._scan_segment(
                    ssts, predicate, projections, keep_builtin, batch_size,
                    use_block_cache,
                )
        finally:
            _ROUTES.reset(token)
            for name in routes:
                SCAN_PATH.labels(name).inc()

    async def _scan_segment(
        self,
        ssts: list[SstFile],
        predicate: Predicate | None,
        projections: list[int] | None,
        keep_builtin: bool,
        batch_size: int = DEFAULT_SCAN_BATCH_SIZE,
        use_block_cache: bool = True,
    ) -> list[pa.RecordBatch]:
        """The fused device pipeline for one time segment.

        The event loop's part is to start things and to take what they
        give: `io_decode` awaits the reads of the segment's SSTs
        (`_decode_segment`: hops of a batch of rows on threads of the
        default pool, the block cache's probe and the footer walk with the
        parquet decode), then `merge_wait` awaits `_merge_segment`, ONE
        call on a thread of the same pool (`asyncio_<n>`) that runs every
        stage from `host_prep` to `materialize`, the wait on the device
        among them. Always, whatever the size and the route: a
        compaction's merge and a query's are the same call, and an
        aggregate pushdown (`scan_segment_downsample`) has the same shape
        with `fold_wait` and `_fold_segment` in their place.

        Segments whose SSTs exceed `scan_block_rows` in total take the
        hierarchical path: per-chunk device passes (filter+merge+dedup) whose
        sorted outputs merge in a device tree — the blockwise/carry-state
        streaming shape of SURVEY §5.7 (LastValue dedup is idempotent across
        levels, so intermediate dedup is safe; Append mode never dedups).
        That path (`_scan_segment_chunked`) and the binary-key host path
        still run their merges on the loop's thread.
        """
        # shared prologue/epilogue with the chunked path lives in
        # _resolve_read_names/_output_names/_slice_batches
        pk_types = [
            self._schema.arrow_schema.field(n).type
            for n in self._schema.primary_key_names
        ]
        if any(_is_binary_like(t) for t in pk_types):
            # binary primary keys: sort/dedup on host via arrow compute (the
            # reference compares binary pks too, macros.rs compare dispatch)
            return await self._scan_segment_host(
                ssts, predicate, projections, keep_builtin, batch_size,
                use_block_cache=use_block_cache,
            )
        total_rows = sum(s.meta.num_rows for s in ssts)
        if total_rows > self._scan_block_rows and len(ssts) > 1:
            fetched = self._resolve_read_names(projections, keep_builtin)
            has_binary = any(
                _is_binary_like(f.type)
                for f in self._schema.arrow_schema
                if f.name in fetched
            )
            if not has_binary:
                return await self._scan_segment_chunked(
                    ssts, predicate, projections, keep_builtin, batch_size,
                    use_block_cache=use_block_cache,
                )
            # binary columns keep the single-block hybrid path
        read_names = self._resolve_read_names(projections, keep_builtin)

        tables = await self._decode_segment(
            ssts, read_names, predicate, use_block_cache)
        if not tables:
            return []
        # the loop starts the merge and takes its batches; everything
        # between runs on one worker thread, and this stage is the await
        # itself, the wait for a thread included
        with scanstats.stage(scanstats.MERGE_WAIT):
            return await asyncio.to_thread(
                self._merge_segment, tables, predicate, read_names,
                keep_builtin, batch_size,
            )

    async def _decode_segment(
        self,
        ssts: list[SstFile],
        read_names: list[str],
        predicate: Predicate | None,
        use_block_cache: bool,
    ) -> list[pa.Table]:
        """The `io_decode` stage of a segment: every SST opened
        (`_open_sst`: what may await) and read on worker threads; the
        tables that hold rows, in the order of `ssts`. What the loop does
        here is await."""
        with scanstats.stage("io_decode"):
            opened = await asyncio.gather(
                *(self._open_sst(s, read_names, predicate, use_block_cache)
                  for s in ssts)
            )
            # A thread hop, and the wake-up of the loop that ends it, is
            # worth a batch of rows: an SST of more decodes on a thread of
            # its own, smaller ones share a hop up to a batch between them
            # (a compaction's inputs are thirty files of 2,000 rows a task:
            # eight hops, not thirty).
            jobs: list[list[int]] = []
            rows = 0
            for i, (sst, got) in enumerate(zip(ssts, opened)):
                if not callable(got):
                    continue
                if not jobs or rows + sst.meta.num_rows > DEFAULT_SCAN_BATCH_SIZE:
                    jobs.append([])
                    rows = 0
                jobs[-1].append(i)
                rows += sst.meta.num_rows

            def decode_job(job: list[int]) -> list[pa.Table]:
                return [opened[i]() for i in job]

            decoded = await asyncio.gather(
                *(asyncio.to_thread(decode_job, job) for job in jobs))
            for job, tables in zip(jobs, decoded):
                for i, table in zip(job, tables):
                    opened[i] = table
        return [t for t in opened if t.num_rows > 0]

    def _merge_segment(
        self,
        tables: list[pa.Table],
        predicate: Predicate | None,
        read_names: list[str],
        keep_builtin: bool,
        batch_size: int,
    ) -> list[pa.RecordBatch]:
        """The synchronous tail of a segment scan, decoded tables in and
        record batches out, as ONE call on a worker thread: `host_prep`,
        the planner and its merge (`host_merge`, or `h2d`, the kernel's
        dispatch, the `device_merge` wait and `d2h`) and `materialize`.
        The thread's context is the coroutine's (asyncio.to_thread copies
        it), so each stage reaches the same histogram, span, collector,
        ledger and deadline as it did on the loop."""
        schema = self._schema
        with scanstats.stage("host_prep"):
            tables = _order_tables_by_first_key(
                tables, tuple(schema.primary_key_names) + (SEQ_COLUMN_NAME,)
            )
            # NO combine_chunks here: it would copy EVERY column; the merge
            # touches only key/predicate lanes, which _merge_table combines
            # per-column on demand, and arrow take handles chunked input —
            # measured 35% of config-2 wall clock saved
            table = memtrace.tracked_concat_tables(tables, "host_prep")
        out_names = self._output_names(read_names, keep_builtin)

        # append mode with binary VALUE columns concatenates group bytes on
        # host and keeps the fused-kernel path (group starts come from the
        # device run-boundary mask)
        value_names = {schema.arrow_schema.names[i] for i in schema.value_idxes}
        has_binary_value = any(
            _is_binary_like(table.schema.field(v).type)
            for v in value_names if v in table.schema.names
        )
        if schema.update_mode == UpdateMode.APPEND and has_binary_value:
            (
                sorted_cols, perm, _keep, starts, kept, numeric_names,
                binary_names, bit_lanes,
            ) = self._fused_pass(table, predicate)
            result = self._materialize_append_mode(
                table, sorted_cols, np.asarray(perm), np.asarray(starts),
                int(kept), numeric_names, binary_names, out_names, bit_lanes,
            )
            return self._slice_batches(result, batch_size)

        # unified materializing merge: the planner picks host SIMD or the
        # index-only device kernel; either way the output is a row-index
        # vector and ONE arrow take materializes every column type
        idx = self._merge_table(table, predicate)
        if len(idx) == 0:
            return []
        with scanstats.stage("materialize"):
            # arrow take materializes fresh column buffers (the ONE copy
            # this plan shape pays); combine then flattens any chunking
            taken = memtrace.track(
                table.select(out_names).take(pa.array(idx)),
                "materialize", "copy",
            )
            result = memtrace.tracked_combine(taken, "materialize")
        batches = result.to_batches(max_chunksize=batch_size)
        return [b for b in batches if b.num_rows > 0]

    def _merge_table(self, table: pa.Table, predicate: Predicate | None) -> np.ndarray:
        """_plan_and_merge over a decoded arrow table, consumed through a
        chunk-aware ArrowLanes block: the host route (sortedness probe,
        predicate eval, mask compaction, key packing) reads per-chunk
        zero-copy views, so no per-column combine_chunks copy happens —
        only device routes fall back to `lanes.lane` (the ONE sanctioned
        contiguous materialization, cached across planner probes)."""
        lanes = colblock.ArrowLanes(table, stage="host_prep")

        pred_cols = filter_ops.pred_columns(predicate)
        binary_pred = any(
            _is_binary_like(table.schema.field(c).type)
            for c in pred_cols if c in table.schema.names
        )

        def host_mask_fn() -> np.ndarray:
            if binary_pred:
                return filter_ops.eval_predicate_host(predicate, table)
            return lanes.eval_chunked(
                lambda cols: filter_ops.eval_predicate_np(predicate, cols),
                sorted(pred_cols),
            )

        def itemsize_of(name: str) -> int:
            t = table.schema.field(name).type
            try:
                return max(1, t.bit_width // 8)
            except (ValueError, AttributeError):
                return 16  # variable-width: rough planning estimate

        return _plan_and_merge(
            self._schema, table.num_rows, lanes.lane, predicate,
            host_mask_fn, binary_pred, itemsize_of, lanes=lanes,
        )

    async def _scan_segment_host(
        self,
        ssts: list[SstFile],
        predicate: Predicate | None,
        projections: list[int] | None,
        keep_builtin: bool,
        batch_size: int,
        use_block_cache: bool = True,
    ) -> list[pa.RecordBatch]:
        """Host merge/dedup for schemas with binary primary keys: arrow
        compute sort + vectorized adjacent-row boundary detection. Numeric
        predicate columns still evaluate through the shared predicate
        engine."""
        import pyarrow.compute as pc

        schema = self._schema
        read_names = self._resolve_read_names(projections, keep_builtin)
        # Sequential chunked reads with immediate filtering bound peak memory
        # to (filtered rows so far + one raw chunk); filter BEFORE dedup
        # (reference plan order).
        filtered: list[pa.Table] = []
        chunk: list[SstFile] = []
        chunk_rows = 0

        async def flush() -> None:
            nonlocal chunk, chunk_rows
            if not chunk:
                return
            tables = await asyncio.gather(
                *(self.read_sst(s, read_names, predicate,
               use_block_cache=use_block_cache) for s in chunk)
            )
            tables = [t for t in tables if t.num_rows > 0]
            chunk, chunk_rows = [], 0
            if not tables:
                return
            t = memtrace.tracked_combine(
                memtrace.tracked_concat_tables(tables, "host_prep"),
                "host_prep",
            )
            if predicate is not None:
                mask = filter_ops.eval_predicate_host(predicate, t)
                t = t.filter(pa.array(mask))
            if t.num_rows:
                filtered.append(t)

        for s in ssts:
            if chunk and chunk_rows + s.meta.num_rows > self._scan_block_rows:
                await flush()
            chunk.append(s)
            chunk_rows += s.meta.num_rows
        await flush()
        if not filtered:
            return []
        table = memtrace.tracked_combine(
            memtrace.tracked_concat_tables(filtered, "host_prep"),
            "host_prep",
        )

        pk_names = schema.primary_key_names
        sort_keys = [(n, "ascending") for n in pk_names] + [(SEQ_COLUMN_NAME, "ascending")]
        table = memtrace.tracked_combine(
            memtrace.track(table.sort_by(sort_keys), "host_prep", "copy"),
            "host_prep",
        )

        if schema.update_mode == UpdateMode.OVERWRITE and table.num_rows > 1:
            n = table.num_rows
            next_differs = np.zeros(n, dtype=bool)
            next_differs[-1] = True
            for name in pk_names:
                col = memtrace.tracked_combine(table.column(name), "host_prep")
                neq = pc.fill_null(
                    pc.not_equal(col.slice(0, n - 1), col.slice(1, n)), True
                ).to_numpy(zero_copy_only=False)
                next_differs[: n - 1] |= neq
            table = table.filter(pa.array(next_differs))
        elif schema.update_mode == UpdateMode.APPEND:
            # binary value columns concat per group (BytesMergeOperator)
            value_names = {schema.arrow_schema.names[i] for i in schema.value_idxes}
            has_binary_value = any(
                _is_binary_like(schema.arrow_schema.field(v).type)
                for v in value_names
            )
            if has_binary_value and table.num_rows > 1:
                n = table.num_rows
                starts = np.zeros(n, dtype=bool)
                starts[0] = True
                for name in pk_names:
                    col = memtrace.tracked_combine(
                        table.column(name), "host_prep"
                    )
                    neq = pc.fill_null(
                        pc.not_equal(col.slice(1, n), col.slice(0, n - 1)), True
                    ).to_numpy(zero_copy_only=False)
                    starts[1:] |= neq
                start_idx = np.nonzero(starts)[0]
                ends = np.append(start_idx[1:], n)
                # resolve value columns BY NAME in the projected table (the
                # schema-level idxes shift under projection)
                all_names = schema.arrow_schema.names
                value_names_ordered = [all_names[i] for i in schema.value_idxes]
                op = BytesMergeOperator(
                    [
                        table.schema.names.index(v)
                        for v in value_names_ordered
                        if v in table.schema.names
                    ]
                )
                def _merge_groups() -> list[pa.RecordBatch]:
                    # per-group byte concatenation is CPU-bound host
                    # work: one thread hop for the whole batch (J018)
                    return [
                        op.merge(table.slice(s, e - s).to_batches()[0])
                        if e - s > 1
                        else table.slice(s, 1).to_batches()[0]
                        for s, e in zip(start_idx, ends)
                    ]

                groups = await asyncio.to_thread(_merge_groups)
                table = pa.Table.from_batches(groups)

        out_names = self._output_names(read_names, keep_builtin)
        result = memtrace.tracked_combine(
            table.select(out_names), "materialize"
        )
        batches = result.to_batches(max_chunksize=batch_size)
        return [b for b in batches if b.num_rows > 0]

    def _fused_pass(
        self,
        table: pa.Table,
        predicate: Predicate | None,
        extra_arrays: dict[str, np.ndarray] | None = None,
    ):
        """The shared fused device pass: numeric/binary split, SoA block,
        literal casting, and the jitted filter->sort->dedup kernel. Used by
        the single-block scan, the hierarchical merge levels, and aggregate
        pushdown (`extra_arrays` rides host-computed lanes, e.g. the dense
        series index, through the same permutation).

        An f64 lane the kernel only carries crosses as its i64 bits
        (`bit_lanes`, read back with `_host_lane`): a device's f64 need not
        be exact (ops/aggregate.py device_f64_is_exact), its i64 is. Where
        it is not exact, a predicate over f64 lanes evaluates on the host
        and rides in as a mask lane, and f64 sort keys are refused."""
        schema = self._schema
        pk_names = tuple(schema.primary_key_names)
        sort_keys = pk_names + (SEQ_COLUMN_NAME,)

        numeric_names, binary_names = [], []
        for name in table.schema.names:
            t = table.schema.field(name).type
            if _is_binary_like(t):
                binary_names.append(name)
            else:
                numeric_names.append(name)
        ensure(
            all(k in numeric_names for k in sort_keys),
            "primary key and seq columns must be numeric for the device path",
        )

        arrays = {
            name: arrow_column_to_numpy(
                memtrace.tracked_combine(table.column(name), "host_prep")
            )
            for name in numeric_names
        }
        if extra_arrays:
            arrays.update(extra_arrays)
        pred_cols = filter_ops.pred_columns(predicate)
        f64_lanes = {k for k, a in arrays.items() if a.dtype == np.float64}
        if not agg_ops.device_f64_is_exact():
            ensure(f64_lanes.isdisjoint(sort_keys),
                   "f64 primary key lanes do not sort exactly on this backend")
            if not f64_lanes.isdisjoint(pred_cols):
                with scanstats.stage("host_filter"):
                    arrays[_HOST_MASK] = filter_ops.eval_predicate_np(
                        predicate, {c: arrays[c] for c in pred_cols}
                    ).astype(np.uint8)
                predicate, pred_cols = None, set()
        bit_lanes = frozenset(f64_lanes - set(pred_cols) - set(sort_keys))
        for name in bit_lanes:
            arrays[name] = arrays[name].view(np.int64)
        with scanstats.stage("h2d"):
            block = Block.from_numpy(
                arrays, pad_multiple=_merge_rows(table.num_rows),
                pad_keys=sort_keys,
            )
            memtrace.device_staged(
                sum(int(a.nbytes) for a in arrays.values()), "h2d"
            )

        template, raw_literals = filter_ops.split_literals(predicate)
        literals = filter_ops.literal_arrays(
            template, raw_literals, {k: v.dtype for k, v in block.columns.items()}
        )
        do_dedup = schema.update_mode == UpdateMode.OVERWRITE and not binary_names
        kernel = _build_scan_kernel(
            tuple(block.names), sort_keys, pk_names, template, do_dedup,
            presorted=_rows_presorted(arrays, sort_keys),
        )
        with scanstats.stage("device_merge"):
            sorted_cols, perm, keep, starts, kept = kernel(
                block.columns, literals, block.num_valid
            )
        return (sorted_cols, perm, keep, starts, kept, numeric_names,
                binary_names, bit_lanes)

    async def _scan_segment_chunked(
        self,
        ssts: list[SstFile],
        predicate: Predicate | None,
        projections: list[int] | None,
        keep_builtin: bool,
        batch_size: int,
        use_block_cache: bool = True,
    ) -> list[pa.RecordBatch]:
        """Hierarchical scan: chunked device passes + a device merge tree."""
        schema = self._schema
        all_names = schema.arrow_schema.names
        read_names = self._resolve_read_names(projections, keep_builtin)
        pk_names = tuple(schema.primary_key_names)
        sort_keys = pk_names + (SEQ_COLUMN_NAME,)
        cap = self._scan_block_rows

        def greedy_partition(items: list, rows_of) -> list[list]:
            out, cur, cur_rows = [], [], 0
            for it in items:
                r = rows_of(it)
                if cur and cur_rows + r > cap:
                    out.append(cur)
                    cur, cur_rows = [], 0
                cur.append(it)
                cur_rows += r
            if cur:
                out.append(cur)
            return out

        def run_block(
            arrays: dict[str, np.ndarray], pred, defer: bool = False
        ):
            """Merge one in-memory block: the planner routes host SIMD vs the
            index-only device kernel (only key/predicate lanes ever cross the
            link; survivors gather from the HOST arrays). With `defer`, a
            device-routed merge returns a zero-arg closure producing the
            gathered block later (kernel already dispatched)."""
            n = len(arrays[sort_keys[0]])
            p_cols = filter_ops.pred_columns(pred)

            def host_mask_fn() -> np.ndarray:
                return filter_ops.eval_predicate_np(
                    pred, {c: arrays[c] for c in p_cols}
                )

            res = _plan_and_merge(
                schema, n, arrays.__getitem__, pred, host_mask_fn, False,
                lambda name: arrays[name].dtype.itemsize,
                defer_device=defer,
            )
            if callable(res):
                def gather():
                    idx = res()  # ONE device sync + index D2H per block
                    return {k: a[idx] for k, a in arrays.items()}
                return gather
            return {k: a[res] for k, a in arrays.items()}

        # level 0: filter + merge + dedup per SST chunk, with the NEXT
        # chunk's parquet decode prefetching on worker threads while this
        # chunk merges (the decode/compute overlap of SURVEY §7 risk (c))
        level: list[dict[str, np.ndarray]] = []
        chunks = greedy_partition(ssts, lambda s: s.meta.num_rows)

        async def read_chunk(chunk: list[SstFile]) -> list[pa.Table]:
            with scanstats.stage("io_decode"):
                tables = await asyncio.gather(
                    *(self.read_sst(s, read_names, predicate,
                       use_block_cache=use_block_cache) for s in chunk)
                )
            return [t for t in tables if t.num_rows > 0]

        next_task = asyncio.ensure_future(read_chunk(chunks[0])) if chunks else None
        pending = None  # chunk i-1's deferred device merge (double buffer)

        def settle() -> None:
            nonlocal pending
            if pending is not None:
                out = pending()
                pending = None
                if len(out[sort_keys[0]]):
                    level.append(out)

        try:
            for i in range(len(chunks)):
                tables = await next_task
                next_task = None
                if i + 1 < len(chunks):
                    next_task = asyncio.ensure_future(read_chunk(chunks[i + 1]))
                    await asyncio.sleep(0)  # let the prefetch reach its threads
                if not tables:
                    continue
                with scanstats.stage("host_prep"):
                    tables = _order_tables_by_first_key(tables, sort_keys)
                    table = memtrace.tracked_combine(
                        memtrace.tracked_concat_tables(tables, "host_prep"),
                        "host_prep",
                    )
                    arrays = {
                        name: arrow_column_to_numpy(
                            memtrace.tracked_combine(
                                table.column(name), "host_prep"
                            )
                        )
                        for name in table.schema.names
                    }
                # double buffer: chunk i's kernel was dispatched last
                # iteration and ran WHILE this chunk decoded and packed;
                # collect it only now, right before dispatching chunk i+1
                # (at most two chunks of key lanes live on device)
                out = run_block(arrays, predicate, defer=True)
                settle()
                if callable(out):
                    pending = out
                elif len(out[sort_keys[0]]):
                    level.append(out)
            settle()
        except BaseException:
            # a failed merge must not abandon the in-flight prefetch (its
            # reads would race a subsequent evict/close and its exception
            # would be logged as never-retrieved); a dispatched device merge
            # is harmless to drop — device arrays free with their refs
            if next_task is not None:
                next_task.cancel()
                try:
                    await next_task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
            raise
        # merge tree: combine sorted deduped runs until one remains
        while len(level) > 1:
            next_level = []
            for group in greedy_partition(level, lambda r: len(r[sort_keys[0]])):
                if len(group) == 1:
                    next_level.append(group[0])
                    continue
                cat = {
                    k: memtrace.tracked_concat(
                        [g[k] for g in group], "host_prep"
                    )
                    for k in group[0]
                }
                next_level.append(run_block(cat, None))
            if len(next_level) == len(level):
                # every pair exceeds the cap: merge only the two smallest
                # runs (guaranteed progress with minimal cap overshoot —
                # merging everything would defeat the memory bound)
                next_level.sort(key=lambda r: len(r[sort_keys[0]]))
                a, b = next_level[0], next_level[1]
                cat = {
                    k: memtrace.tracked_concat([a[k], b[k]], "host_prep")
                    for k in a
                }
                next_level = [run_block(cat, None)] + next_level[2:]
            level = next_level
        if not level:
            return []
        final = level[0]
        out_names = self._output_names(read_names, keep_builtin)
        cols = [
            _np_to_arrow(final[n], schema.arrow_schema.field(all_names.index(n)).type)
            for n in out_names
        ]
        out_schema = pa.schema(
            [schema.arrow_schema.field(all_names.index(n)) for n in out_names]
        )
        result = pa.RecordBatch.from_arrays(cols, schema=out_schema)
        return self._slice_batches(result, batch_size)

    async def scan_segment_downsample(
        self,
        ssts: list[SstFile],
        predicate: Predicate | None,
        ts_column: str,
        value_column: str,
        series_column: str,
        series_ids: np.ndarray,
        t0: int,
        bucket_ms: int,
        num_buckets: int,
        with_minmax: bool = True,
        use_block_cache: bool = True,
        packed_ok: bool = False,
    ) -> dict:
        """Aggregate pushdown: scan one segment and reduce it to dense
        [num_series, num_buckets] grids, so that no raw row leaves the
        reader (SURVEY's #1 offload target: scan->filter->aggregate).

        `series_ids` is a SORTED array of series keys; dense output row i
        corresponds to series_ids[i], rows with other keys are dropped.
        Dedup semantics are those of the materializing path (filter first,
        last value wins). Correct whenever duplicates cannot span segments
        (true for any schema whose primary key includes the timestamp, e.g.
        the metric-engine data table).

        The routes, by what the caller and the backend allow:
        - `packed_ok` (the metric engine): the HOST orders the rows it keeps
          by one (sid, ts) key, sorting and dedupping only where they need
          it (`_packed_downsample_pass`), and
          the surviving rows are ONE fold (`ops/aggregate.py fold_sorted`:
          the padded `downsample_fold` program, or the host reduceat lane);
        - otherwise the fused device pass sorts and dedups; on a backend
          whose f64 is exact (the CPU) the same program reduces the rows
          where they lie, and on one whose f64 is not (an accelerator) or
          under a mesh the sorted rows come back to the host once and take
          the fold, its selections on i64 order keys and its sums in the
          device's f64 where `device_sums_hold`, else on the host;
        - segments above `scan_block_rows` route through the hierarchical
          scan and fold each of its sorted batches: device memory stays
          bounded.

        The event loop's part is awaits only: `io_decode` awaits the reads
        of the segment's SSTs (`_decode_segment`, as a materialising scan:
        hops of a batch of rows, the block cache's probe and the footer
        walk on the worker), then `fold_wait` awaits `_fold_segment`, ONE
        call on a thread of the default pool (`asyncio_<n>`) that runs
        `host_prep`, `pack_sort` and the fold's stages (`fold_prep`,
        `fold_h2d`, `fold_kernel`, `fold_d2h`, or `fold_host`), the wait on
        the device among them. Always, whatever the size and the route.
        The chunked route alone still folds on the loop's thread.

        Returns host numpy grids: sum and count, plus min/max when
        `with_minmax` (no mean — callers derive it after combining partials).
        """
        spec = _FoldSpec(series_ids, t0, bucket_ms, num_buckets, with_minmax)
        total_rows = sum(s.meta.num_rows for s in ssts)
        if total_rows > self._scan_block_rows and len(ssts) > 1:
            # bounded-memory path: hierarchical scan yields merged, deduped,
            # pk-sorted batches; fold each into the grids
            grids = spec.empty_grids()
            batches = await self._scan_segment_chunked(
                ssts, predicate, None, False, batch_size=self._scan_block_rows
            )
            for b in batches:
                sp, hit = spec.dense_sid(arrow_column_to_numpy(b.column(series_column)))
                self._accumulate_sorted(
                    spec, grids,
                    arrow_column_to_numpy(b.column(ts_column)),
                    sp,
                    arrow_column_to_numpy(b.column(value_column)),
                    valid_np=hit if not hit.all() else None,
                )
            return grids

        read_names = self._resolve_read_names(None, False)
        tables = await self._decode_segment(
            ssts, read_names, predicate, use_block_cache)
        if not tables:
            return spec.empty_grids()
        # the loop starts the fold and takes its grids; everything between
        # runs on one worker thread, and this stage is the await itself,
        # the wait for a thread included
        with scanstats.stage(scanstats.FOLD_WAIT):
            return await asyncio.to_thread(
                self._fold_segment, tables, predicate, ts_column,
                value_column, series_column, spec, packed_ok,
            )

    def _fold_segment(
        self,
        tables: list[pa.Table],
        predicate: Predicate | None,
        ts_column: str,
        value_column: str,
        series_column: str,
        spec: "_FoldSpec",
        packed_ok: bool,
    ) -> dict:
        """The synchronous tail of a segment's pushdown, decoded tables in
        and host grids out, as ONE call on a worker thread: `host_prep`
        (order, concat, the dense series index), then `pack_sort` and one
        fold (`packed_ok`), or the fused pass and its fold. The thread's
        context is the coroutine's (asyncio.to_thread copies it), so each
        stage reaches the same histogram, span, collector, ledger and
        deadline as it did on the loop; the grids are made here and handed
        back, never shared with the loop while the call runs."""
        from horaedb_tpu.parallel.mesh import active_mesh

        grids = spec.empty_grids()
        with scanstats.stage("host_prep"):
            tables = _order_tables_by_first_key(
                tables,
                tuple(self._schema.primary_key_names) + (SEQ_COLUMN_NAME,),
            )
            table = memtrace.tracked_combine(
                memtrace.tracked_concat_tables(tables, "host_prep"),
                "host_prep",
            )
            sid, sid_hit = spec.dense_sid(
                arrow_column_to_numpy(
                    memtrace.tracked_combine(
                        table.column(series_column), "host_prep"
                    )
                )
            )

        if packed_ok:
            with scanstats.stage("pack_sort"):
                fast = self._packed_downsample_pass(
                    table, predicate, sid, sid_hit, ts_column, value_column,
                    len(spec.series_ids))
            if fast is not None:
                ts_s, sid_s, val_s = fast
                if len(ts_s):
                    self._accumulate_sorted(spec, grids, ts_s, sid_s, val_s)
                return grids

        # the hit mask rides the fused pass's permutation as an int lane so
        # set-membership misses stay excludable after the device sort; the
        # lane is skipped on the common all-hit query (no series subset)
        extra = {"__sid__": sid}
        all_hit = bool(sid_hit.all())
        if not all_hit:
            extra["__sidok__"] = sid_hit.astype(np.int32)
        (sorted_cols, _perm, keep, _starts, _kept, _num, _bin,
         bit_lanes) = self._fused_pass(table, predicate, extra_arrays=extra)
        row_ok = keep if all_hit else keep & (sorted_cols["__sidok__"] != 0)
        if active_mesh() is not None or not agg_ops.device_f64_is_exact():
            # the merged/deduped rows leave the fused pass for the sorted
            # reduction: sharded over the mesh, or (a backend whose f64 is
            # not exact) with its selections on integer lanes; misses keep
            # their monotone position and are zeroed via the weight column
            self._accumulate_sorted(
                spec, grids,
                np.asarray(sorted_cols[ts_column]).astype(np.int64),
                np.asarray(sorted_cols["__sid__"]).astype(np.int32),
                _host_lane(sorted_cols, value_column, bit_lanes),
                valid_np=np.asarray(row_ok),
            )
            return grids
        # device-side reduction of the surviving rows (row_ok is a mask)
        values = sorted_cols[value_column]
        if value_column in bit_lanes:
            values = jax.lax.bitcast_convert_type(values, jnp.float64)
        out = agg_ops.downsample(
            sorted_cols[ts_column].astype(jnp.int64),
            sorted_cols["__sid__"],
            values,
            row_ok,
            spec.t0,
            spec.bucket_ms,
            num_series=len(spec.series_ids),
            num_buckets=spec.num_buckets,
        )
        return {k: np.asarray(out[k]) for k in grids}

    def _accumulate_sorted(
        self, spec: "_FoldSpec", grids: dict, ts_np, sid_np, val_np,
        valid_np=None,
    ) -> None:
        """Fold one sorted run into `grids` (sorted-segment fast path).
        With an ambient multi-device mesh installed, rows shard over
        "rows" and the output grid over "series" (SURVEY §2.5's
        shard_map-over-SST-partitions); partials combine via psum/pmin/
        pmax over ICI. Single device: the local sorted kernel.
        `valid_np` excludes rows via the reduction's weight column
        (sid_np must stay monotone for excluded rows too)."""
        from horaedb_tpu.parallel.mesh import active_mesh

        # cooperative deadline between device-lane launches: each fold
        # is one kernel dispatch — an expired query stops dispatching
        # (host-side check; never traced into the kernel body)
        deadline_ctx.check("device_lane")
        num_series = len(spec.series_ids)
        mesh = active_mesh()
        if mesh is not None:
            with scanstats.stage("device_agg"):
                out = self._sharded_accumulate(
                    mesh, ts_np, sid_np, val_np, spec.t0, spec.bucket_ms,
                    num_series, spec.num_buckets, spec.with_minmax,
                    valid_np=valid_np,
                )
        else:
            out, run = agg_ops.fold_sorted(
                ts_np, sid_np, val_np, spec.t0, spec.bucket_ms,
                num_series=num_series, num_buckets=spec.num_buckets,
                with_minmax=spec.with_minmax, valid=valid_np,
            )
            # lane attribution: the implementation this fold ran (the
            # host reduceat or a device program), from the fold itself
            scanstats.note("agg_impl_" + run.impl)
        grids["sum"] += np.asarray(out["sum"])
        grids["count"] += np.asarray(out["count"])
        if spec.with_minmax:
            grids["min"] = np.minimum(grids["min"], np.asarray(out["min"]))
            grids["max"] = np.maximum(grids["max"], np.asarray(out["max"]))

    # packed-key budget: sid | ts-offset | seq-rank must fit in 63 bits.
    # Exceeding any budget falls back to the fused lexsort.
    _PACK_SID_BITS = 17
    _PACK_TS_BITS = 34   # ~198 days of ms offsets within one scan
    _PACK_SEQ_BITS = 12  # distinct write sequences among a segment's duplicates

    def _packed_downsample_pass(
        self, table, predicate, sid, sid_valid, ts_column, value_column, num_series
    ):
        """Single-key replacement for the fused kernel's 6-lane lexsort on
        the downsample pushdown path. The predicate evaluates on the host
        and only the rows it and the series set keep are ordered, by one
        u64 key (dense sid, ts offset). The pass does as much as the rows
        ask for, and counts which (`horaedb_pushdown_pack_total{order}`):
        - `in_order`: the keys already rise strictly (a compacted segment:
          sorted, deduplicated SSTs concatenated in first-key order), so the
          survivors are the answer as they lie;
        - `sorted`: one stable argsort (timsort: close to linear over a
          segment's few per-SST runs) and no two keys equal;
        - `dedup`: some (sid, ts) repeats, and only then is `__seq__` ranked
          and packed under the key: among one cell's survivors the newest
          write wins, the last in concatenation order among equal seqs,
          matching the fused kernel's filter-first/last-value semantics.

        Returns (ts, sid, values) as pk-sorted, deduped, fully-valid host
        lanes for accumulate_sorted, or None when the shape exceeds the
        pack budgets (huge spans, append mode, more than 2^12 distinct seqs
        among survivors that need dedup) — the caller then runs the general
        fused pass.

        CONTRACT (why scan_segment_downsample gates this on `packed_ok`):
        dedup here is by (sid, ts), NOT the full schema pk. The caller must
        guarantee every non-(series, ts) pk column is pinned — e.g. the
        metric engine pins metric_id via an eq predicate and field_id is
        constant — otherwise distinct-pk rows sharing (tsid, ts) would
        wrongly collapse."""
        from horaedb_tpu.storage.config import UpdateMode

        if self._schema.update_mode != UpdateMode.OVERWRITE:
            return None
        if num_series >= (1 << self._PACK_SID_BITS):
            return None
        ts_np = arrow_column_to_numpy(
            memtrace.tracked_combine(table.column(ts_column), "host_prep")
        )
        if len(ts_np) == 0:
            self._count_pack("in_order")
            return (np.empty(0, np.int64),) * 3
        ts_min = int(ts_np.min())
        span = int(ts_np.max()) - ts_min
        if span >= (1 << self._PACK_TS_BITS):
            return None
        mask = sid_valid
        if predicate is not None:
            mask = mask & self._predicate_mask(predicate, table, {ts_column: ts_np})
        idx = np.flatnonzero(mask)
        ts_k = ts_np[idx]
        sid_k = sid[idx]
        key = (
            (sid_k.astype(np.uint64) << np.uint64(self._PACK_TS_BITS))
            | (ts_k - ts_min).astype(np.uint64)
        )
        order = "in_order"
        if len(key) > 1 and not (key[1:] > key[:-1]).all():
            perm = np.argsort(key, kind="stable")
            key = key[perm]
            order = "sorted"
            if (key[1:] == key[:-1]).any():
                order = "dedup"
                perm = self._newest_of_each_key(table, idx, perm, key)
                if perm is None:
                    return None
            idx, ts_k, sid_k = idx[perm], ts_k[perm], sid_k[perm]
        self._count_pack(order)
        val_np = arrow_column_to_numpy(
            memtrace.tracked_combine(table.column(value_column), "host_prep")
        )
        return ts_k, sid_k.astype(np.int32, copy=False), val_np[idx]

    def _newest_of_each_key(self, table, idx, perm, key_s):
        """The positions in `perm` (the survivors `idx` stably sorted by
        their (sid, ts) key, `key_s` the sorted keys) of the newest write of
        each key: the largest `__seq__`, and the last in concatenation
        order among equal ones. None past the seq-rank budget."""
        seq = arrow_column_to_numpy(
            memtrace.tracked_combine(table.column(SEQ_COLUMN_NAME), "host_prep")
        )[idx[perm]]
        uniq_seq = np.unique(seq)
        if len(uniq_seq) > (1 << self._PACK_SEQ_BITS):
            return None
        shift = np.uint64(self._PACK_SEQ_BITS)
        ranked = (key_s << shift) | np.searchsorted(uniq_seq, seq).astype(np.uint64)
        # stable over an order that is already the concatenation order
        # within each key, so equal seqs keep it; it moves rows only within
        # a key, so `key_s` still marks where each key's run ends
        sub = np.argsort(ranked, kind="stable")
        last = np.empty(len(sub), dtype=bool)
        last[:-1] = key_s[:-1] != key_s[1:]
        last[-1] = True
        return perm[sub[last]]

    @staticmethod
    def _predicate_mask(predicate, table, lanes: dict) -> np.ndarray:
        """The predicate over a decoded table as the merge route evaluates
        it (`_merge_table`): numpy over the column lanes (`lanes` holds
        those already converted) where every column it reads is numeric,
        arrow compute where one is binary. A numpy leaf is one array
        operation where arrow's is four calls, and each call lets go of
        the GIL and takes it back: on a worker that shares it with three
        others and the loop, each take can wait."""
        cols = filter_ops.pred_columns(predicate)
        if any(_is_binary_like(table.schema.field(c).type) for c in cols):
            return filter_ops.eval_predicate_host(predicate, table)
        for c in cols - lanes.keys():
            lanes[c] = arrow_column_to_numpy(
                memtrace.tracked_combine(table.column(c), "host_prep"))
        return filter_ops.eval_predicate_np(predicate, lanes)

    @staticmethod
    def _count_pack(order: str) -> None:
        agg_ops.PACK_TOTAL.labels(order).inc()
        scanstats.note("pack_" + order)

    @staticmethod
    def _sharded_accumulate(
        mesh, ts_np, sid_np, val_np, t0, bucket_ms,
        num_series: int, num_buckets: int, with_minmax: bool,
        valid_np=None,
    ) -> dict:
        """One sorted run reduced over the ambient mesh — delegates to
        the first-class mesh layer (parallel/mesh.py::mesh_downsample),
        which owns the series padding, per-lane row pads, and the
        accelerator dtype rule the sharded lane grew up with here."""
        from horaedb_tpu.parallel.mesh import mesh_downsample

        return mesh_downsample(
            mesh, ts_np, sid_np, val_np, t0, bucket_ms,
            num_series, num_buckets, with_minmax=with_minmax,
            valid_np=valid_np, sorted_input=True,
        )

    # -- shared prologue/epilogue ---------------------------------------------
    def _resolve_read_names(self, projections: list[int] | None, keep_builtin: bool) -> list[str]:
        """Columns to fetch: projection + forced pk/__seq__ (types.rs:203-216),
        plus __reserved__ when builtins are kept."""
        proj = self._schema.fill_required_projections(projections)
        all_names = self._schema.arrow_schema.names
        read_names = list(all_names) if proj is None else [all_names[i] for i in sorted(proj)]
        if keep_builtin and RESERVED_COLUMN_NAME not in read_names:
            read_names.append(RESERVED_COLUMN_NAME)
        return read_names

    @staticmethod
    def _output_names(read_names: list[str], keep_builtin: bool) -> list[str]:
        """Output = everything fetched minus builtins unless keep_builtin —
        matching the reference plan's output schema after MergeExec."""
        return [n for n in read_names if keep_builtin or not StorageSchema.is_builtin_name(n)]

    @staticmethod
    def _slice_batches(result: pa.RecordBatch, batch_size: int) -> list[pa.RecordBatch]:
        if result.num_rows == 0:
            return []
        return [result.slice(i, batch_size) for i in range(0, result.num_rows, batch_size)]

    # -- host materialization ------------------------------------------------
    def _materialize_append_mode(
        self,
        table: pa.Table,
        sorted_cols: dict[str, jax.Array],
        perm: np.ndarray,
        starts: np.ndarray,
        kept: int,
        numeric_names: list[str],
        binary_names: list[str],
        out_names: list[str],
        bit_lanes: frozenset,
    ) -> pa.RecordBatch:
        """Append mode with binary values: groups collapse by concatenating
        value bytes (BytesMergeOperator) on host; group extents come from the
        device run-boundary mask."""
        value_names = {
            self._schema.arrow_schema.names[i] for i in self._schema.value_idxes
        }
        start_idx = np.nonzero(starts[:kept])[0]
        ends = np.append(start_idx[1:], kept)
        cols = []
        for name in out_names:
            f = table.schema.field(name)
            if name in binary_names:
                src = memtrace.track(
                    memtrace.tracked_combine(
                        table.column(name), "materialize"
                    ).take(pa.array(perm[:kept])),
                    "materialize", "copy",
                )
                if name in value_names:
                    vals = src.to_pylist()
                    joined = [
                        b"".join(v for v in vals[s:e] if v is not None)
                        for s, e in zip(start_idx, ends)
                    ]
                    cols.append(pa.array(joined, type=f.type))
                else:
                    cols.append(src.take(pa.array(start_idx)))
            else:
                np_col = _host_lane(sorted_cols, name, bit_lanes)[:kept]
                # non-value numeric columns take the group's first row; numeric
                # value columns in append mode also take first (reference only
                # concatenates binary value columns, operator.rs:59-111)
                cols.append(_np_to_arrow(np_col[start_idx], f.type))
        return pa.RecordBatch.from_arrays(
            cols, schema=pa.schema([table.schema.field(n) for n in out_names])
        )


class _Footer:
    """A parquet footer as the reader keeps it (`ParquetReader._meta_cache`):
    the file's metadata, its arrow schema and, once a read has pruned by
    them, the row groups' min/max statistics as numpy lanes
    (`_footer_lanes`). Racing reads may both build the lanes; they build
    the same, and one assignment wins."""

    __slots__ = ("meta", "schema", "lanes")

    def __init__(self, meta, schema):
        self.meta = meta
        self.schema = schema
        self.lanes: dict | None = None


def _unsigned_columns(arrow_schema) -> set[str]:
    return {
        name
        for name in arrow_schema.names
        if pa.types.is_unsigned_integer(arrow_schema.field(name).type)
    }


def _row_group_stats(meta, arrow_schema):
    """`{column: (min, max)}` of each row group in turn, from the footer's
    metadata objects, in the numeric domain predicates use (`_stat_value`).
    Python over every column chunk of the file, all of it holding the GIL
    (some 12 ms for 528 row groups of six columns on a sandbox's CPU)."""
    unsigned = _unsigned_columns(arrow_schema)
    for rg in range(meta.num_row_groups):
        stats: dict[str, tuple] = {}
        g = meta.row_group(rg)
        for ci in range(g.num_columns):
            col = g.column(ci)
            st = col.statistics
            if st is not None and st.has_min_max:
                name = col.path_in_schema
                lo = _stat_value(st.min, name in unsigned)
                hi = _stat_value(st.max, name in unsigned)
                if lo > hi:  # u64 range straddling 2**63 wrapped; stats unusable
                    continue
                stats[name] = (lo, hi)
        yield stats


def _footer_lanes(stats: list[dict], arrow_schema) -> dict:
    """`filter_ops.prune_lanes`' lanes from `_row_group_stats`' dicts: for
    a column whose statistics are numbers `(lo, hi, usable)` over the row
    groups, uint64 for an unsigned column, int64 for the other integers
    (timestamps are epoch ms by now), float64 for floats, never a lossy
    cast; None for a column with statistics of another kind (binary,
    boolean, decimal, date), whose leaves take the scalar prune."""
    n = len(stats)
    found: dict[str, tuple[list, list, list]] = {}
    for rg, group in enumerate(stats):
        for name, (lo, hi) in group.items():
            at, los, his = found.setdefault(name, ([], [], []))
            at.append(rg)
            los.append(lo)
            his.append(hi)
    unsigned = _unsigned_columns(arrow_schema)
    lanes: dict = {}
    for name, (at, los, his) in found.items():
        kinds = {type(v) for v in los} | {type(v) for v in his}
        if kinds == {int}:
            dt = np.dtype(np.uint64 if name in unsigned else np.int64)
        elif kinds == {float}:
            dt = np.dtype(np.float64)
        else:
            lanes[name] = None
            continue
        lo, hi = np.zeros(n, dtype=dt), np.zeros(n, dtype=dt)
        usable = np.zeros(n, dtype=bool)
        try:
            lo[at] = np.array(los, dtype=dt)
            hi[at] = np.array(his, dtype=dt)
        except OverflowError:  # an integer outside its column's own domain
            lanes[name] = None
            continue
        usable[at] = True
        lanes[name] = (lo, hi, usable)
    return lanes


def _select_row_groups(footer: _Footer, predicate) -> list[int]:
    """Row groups whose min/max statistics can satisfy the predicate: the
    list `filter_ops.prune_range` over `_row_group_stats` gives, from the
    footer's lanes in a handful of array operations. The metadata objects
    are walked by the first read that prunes by a footer, and by a read
    whose predicate has a leaf the lanes cannot decide; never without a
    predicate."""
    n = footer.meta.num_row_groups
    if predicate is None:
        return list(range(n))
    stats = None

    def walked() -> list[dict]:
        nonlocal stats
        if stats is None:
            stats = list(_row_group_stats(footer.meta, footer.schema))
        return stats

    lanes = footer.lanes
    if lanes is None:
        lanes = footer.lanes = _footer_lanes(walked(), footer.schema)
    keep = filter_ops.prune_lanes(
        predicate, lanes, n,
        lambda node: np.fromiter(
            (filter_ops.prune_range(node, s) for s in walked()),
            dtype=bool, count=n))
    served, noted = ("lanes", "footer_lanes") if stats is None else ("walk", "footer_walks")
    FOOTER_PRUNES.labels(served).inc()
    scanstats.note(noted)
    return np.flatnonzero(keep).tolist()


def _read_pruned(
    pf: pq.ParquetFile,
    columns: list[str] | None,
    predicate: Predicate | None,
    rg_cache=None,   # optional (get(rg), put(rg, table)) hooks
    meta_sink=None,  # optional callback stashing the footer
    keep_groups: list[int] | None = None,  # from a selection that already ran
) -> pa.Table:
    footer = _Footer(pf.metadata, pf.schema_arrow)
    if keep_groups is None:
        keep_groups = _select_row_groups(footer, predicate)
    if meta_sink is not None:
        meta_sink(footer)
    if not keep_groups:
        return pf.schema_arrow.empty_table()
    if rg_cache is not None:
        # per-row-group block cache: pruning still applies (keys are
        # individual row groups), repeat reads of the hot groups skip decode
        get, put = rg_cache
        parts = []
        for rg in keep_groups:
            t = get(rg)
            if t is None:
                t = pf.read_row_group(rg, columns=columns, use_threads=True)
                memtrace.track(t, "materialize", "alloc")
                put(rg, t)
            parts.append(t)
        return memtrace.tracked_concat_tables(parts, "materialize")
    return pf.read_row_groups(keep_groups, columns=columns, use_threads=True)


def _stat_value(v, is_unsigned: bool = False):
    """Normalize parquet statistics to the numeric domain predicates use:
    - timestamp columns report datetime.datetime; literals are epoch ms;
    - uint64 columns are stored as signed int64 physically, so ids >= 2**63
      (seahash ids routinely are) come back negative and must re-wrap."""
    import calendar
    import datetime

    if isinstance(v, datetime.datetime):
        # exact integer epoch ms — float .timestamp()*1000 truncates ~1% of
        # millisecond values down by 1, which would mis-prune row groups
        return calendar.timegm(v.utctimetuple()) * 1000 + v.microsecond // 1000
    if is_unsigned and isinstance(v, int) and v < 0:
        return v + (1 << 64)
    return v


def _np_to_arrow(arr: np.ndarray, t: pa.DataType) -> pa.Array:
    if t == pa.timestamp("ms"):
        return pa.array(arr.astype("datetime64[ms]"))
    return pa.array(arr, type=t)
