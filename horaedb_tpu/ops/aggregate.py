"""Segment reductions: group-by-tag aggregation and time-bucket downsampling.

These are the kernels behind BASELINE configs 1-4 (range-aggregate,
group-by-tag avg/min/max, 5-minute downsample). The design maps each
(group, time-bucket) cell to a flat segment index and reduces with XLA
scatter-adds (`jax.ops.segment_*`) — one pass over the data, no sort needed,
entirely fusible with the predicate mask from filter.py.

Invalid/padding rows are routed to an out-of-range segment index, which XLA's
scatter drop-semantics discard for free — no host-side compaction on the
aggregate path (SURVEY §7 risk (e) resolved by reduction, not masking).

Dense i32 indices + f32 accumulation are deliberate: TPUs emulate 64-bit
integer lanes, so hot aggregation runs on native-width types. Host code maps
u64 TSIDs to dense series indices before dispatch (ops/__init__ docstring).
"""

from __future__ import annotations

import bisect
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from horaedb_tpu.common.error import ensure
from horaedb_tpu.common.xprof import xjit
from horaedb_tpu.ops.sort import f64_order_i64, pow2_rows
from horaedb_tpu.server.metrics import GLOBAL_METRICS


def _masked_index(index: jax.Array, valid: jax.Array, num_segments: int) -> jax.Array:
    """Invalid rows -> index == num_segments (dropped by segment ops)."""
    return jnp.where(valid, index, num_segments).astype(jnp.int32)


def masked_cell_keys(series_idx, bucket, ok, num_series: int, num_buckets: int):
    """Cell-id construction shared by every downsample path: returns
    (safe, flat) where `safe` keeps masked rows at an IN-RANGE clipped id
    (their contribution rides the weight column) and `flat` routes them to
    the num_cells sentinel (scatter drop semantics, for min/max).

    Masked rows must NOT get sentinel keys on the sum/count path: sentinel
    interleaving breaks the sorted runs the block compaction exploits and
    trips its adaptive scatter fallback whenever a predicate is active.
    Both the sid and the bucket are clipped BEFORE forming the flat id —
    an out-of-window ts would otherwise spill into the neighbouring
    series' id range and destroy monotonicity. With the clip, keys stay
    monotone in (sid, ts) for any series-slice/time-window masking."""
    safe = jnp.clip(series_idx.astype(jnp.int32), 0, num_series - 1) \
        * num_buckets + jnp.clip(bucket, 0, num_buckets - 1)
    flat = jnp.where(ok, safe, num_series * num_buckets)
    return safe, flat


def masked_minmax(values, idx, valid, num_segments: int):
    """Scatter-based min/max per segment with sentinel-index drop semantics
    (`idx` must route invalid rows to num_segments; invalid values fill
    +/-inf). The SCATTER-path helper: compaction-eligible paths use
    blockagg.sorted_segment_min_max (masked-reduce block compaction)
    instead."""
    mn = jax.ops.segment_min(
        jnp.where(valid, values, jnp.inf), idx, num_segments + 1
    )[:-1]
    mx = jax.ops.segment_max(
        jnp.where(valid, values, -jnp.inf), idx, num_segments + 1
    )[:-1]
    return mn, mx


def masked_segment_stats(
    values: jax.Array,
    idx: jax.Array,
    valid: jax.Array,
    num_segments: int,
    with_minmax: bool = True,
):
    """Shared masked segment-reduction core (also used by the sharded scan in
    parallel/scan.py): `idx` must already route invalid rows to num_segments.
    Returns (sum, count, min|None, max|None) flat arrays of len num_segments.

    Scatters are the expensive op on TPU — min/max are skipped when not
    requested, and values/ones stay flat 1-D (stacking features breaks the
    (8,128) tile layout and measures ~4x slower).
    """
    # integers widen to 64-bit accumulation (exact, wrap-proof for narrow
    # int sums), matching blockagg._scatter_sum_count; floats keep
    # their own width (the engine's precision contract, data.py)
    vals = jnp.asarray(values)
    if jnp.issubdtype(vals.dtype, jnp.unsignedinteger):
        vals = vals.astype(jnp.uint64)
    elif not jnp.issubdtype(vals.dtype, jnp.floating):
        vals = vals.astype(jnp.int64)  # bool included
    s = jax.ops.segment_sum(jnp.where(valid, vals, 0), idx, num_segments + 1)[:-1]
    c = jax.ops.segment_sum(valid.astype(vals.dtype), idx, num_segments + 1)[:-1]
    if not with_minmax:
        return s, c, None, None
    mn, mx = masked_minmax(values, idx, valid, num_segments)
    return s, c, mn, mx


@xjit(kernel="grouped_stats", static_argnames=("num_segments",))
def grouped_stats(
    values: jax.Array,
    index: jax.Array,
    valid: jax.Array,
    num_segments: int,
) -> dict[str, jax.Array]:
    """sum / count / min / max / mean per segment, one fused pass.

    Empty segments report count 0, sum 0, min +inf, max -inf, mean NaN.
    Out-of-range indices are DROPPED regardless of `valid` (scatter
    out-of-bounds drop semantics, the pre-dispatch contract). On the
    accelerator sort path ONE device sort feeds all four stats: sum/count
    via the block-rank compaction, min/max via the masked-reduce
    compaction. Otherwise (CPU, sparse grids, non-f32) everything
    scatters, dtype-preserving.
    """
    from horaedb_tpu.ops.blockagg import (
        _F32_EXACT,
        segment_sum_count,
        sorted_segment_min_max,
        sorted_segment_sum_count,
        unsorted_strategy,
    )

    # the dispatcher's sort path clips indices into range, so out-of-range
    # rows must be folded into the mask here to keep the drop semantics;
    # integer values keep the exact dtype-preserving scatter (the block
    # compaction accumulates f32, which would round int sums above 2^24)
    valid = valid & (index >= 0) & (index < num_segments)
    idx = _masked_index(index, valid, num_segments)
    vals_j = jnp.asarray(values)
    if num_segments < _F32_EXACT and jnp.issubdtype(vals_j.dtype, jnp.floating):
        masked = jnp.where(valid, vals_j, 0)
        if unsorted_strategy(idx.shape[0], num_segments, masked.dtype) == "sort":
            # one device sort feeds all four stats (sentinels drop at the
            # tail bucket); min/max use the masked-reduce compaction
            k2, v2 = jax.lax.sort((idx, masked), num_keys=1)
            s, c = sorted_segment_sum_count(k2, v2, num_segments, impl="block")
            mn, mx = sorted_segment_min_max(k2, v2, num_segments, impl="block")
        else:
            s, c = segment_sum_count(idx, masked, num_segments, impl="scatter")
            mn, mx = masked_minmax(values, idx, valid, num_segments)
    else:
        s, c, mn, mx = masked_segment_stats(values, idx, valid, num_segments)
    return {"sum": s, "count": c, "min": mn, "max": mx, "mean": s / c}


def bucket_of(ts: jax.Array, t0, bucket_ms) -> jax.Array:
    """Time-bucket index relative to t0. i64-safe, result is i32-dense."""
    return ((ts - t0) // bucket_ms).astype(jnp.int32)


_I64_MAX = np.iinfo(np.int64).max
_I64_MIN = np.iinfo(np.int64).min
# NaN rows: smallest key on the min lane, largest on the max lane, so a NaN
# in a cell wins both reductions exactly as it propagates through float
# min/max; one step inside the empty-cell fills of segment_min/segment_max
_NAN_LOW, _NAN_HIGH = _I64_MIN + 1, _I64_MAX - 1


_F32_MAX = float(np.finfo(np.float32).max)
# below this magnitude the low half of an f32 pair is no longer a normal f32
_F32_PAIR_MIN = float(np.finfo(np.float32).tiny) * 2.0 ** 24


def device_f64_is_exact() -> bool:
    """Only the CPU backend holds f64 as f64. An accelerator holds 64-bit
    integers exactly but emulates f64 as a pair of f32: about 48 mantissa
    bits and f32's exponent range, so 1e300 reads back inf and a stored
    sample loses its last bits on the way there and back (measured on a
    TPU v5e, PR 25). Callers keep what must stay exact on integer lanes
    (`f64_order_keys`, i64 bit views) or on the host."""
    return jax.devices()[0].platform == "cpu"


def device_sums_hold(values: np.ndarray) -> bool:
    """Whether the device can accumulate `values` in its f64: always on the
    CPU; on an accelerator only when every finite magnitude, and their sum,
    stays inside the range an f32 pair carries."""
    if device_f64_is_exact():
        return True
    mag = np.abs(values[np.isfinite(values)])
    small = mag[mag > 0]
    return float(mag.sum()) <= _F32_MAX and (
        small.size == 0 or float(small.min()) >= _F32_PAIR_MIN
    )


def f64_order_keys(values: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host side of the exact device min/max: (min-lane, max-lane) i64 keys
    whose signed order is the f64 total order of `values`. A selection must
    return a stored sample bit for bit and the device's f64 may not be one
    (`device_f64_is_exact`), so min/max reduce these keys on every backend
    and `f64_from_order_keys` maps the winners back. The two lanes are one
    array unless the block holds a NaN."""
    keys = f64_order_i64(np.asarray(values))
    nan = np.isnan(values)
    if not nan.any():
        return keys, keys
    return np.where(nan, _NAN_LOW, keys), np.where(nan, _NAN_HIGH, keys)


def f64_from_order_keys(keys: np.ndarray) -> np.ndarray:
    """Inverse of `f64_order_keys` over reduced cells: empty cells (the
    integer reduction's fill) read +inf on the min lane and -inf on the max
    lane, NaN markers read NaN."""
    keys = np.asarray(keys, dtype=np.int64)
    out = (keys ^ ((keys >> 63) & _I64_MAX)).view(np.float64)  # self-inverse
    out[(keys == _NAN_LOW) | (keys == _NAN_HIGH)] = np.nan
    out[keys == _I64_MAX] = np.inf
    out[keys == _I64_MIN] = -np.inf
    return out


# smallest padded length of a fold: every row count a one-host hour of 10 s
# samples can cut (1 to 361) is ONE row class, so one compiled program
_MIN_FOLD_ROWS = 512
# grid classes stop here: a larger grid keeps its own shape (one program a
# shape, as before) instead of up to four times the cells in padding
_MAX_CLASS_CELLS = 1 << 24

FOLDS_TOTAL = GLOBAL_METRICS.counter(
    "horaedb_pushdown_folds_total",
    help="Folds of the aggregate pushdown (one sorted run reduced to its "
         "grids), by the implementation that ran: a device program's "
         "(scatter, runs, block, ...) or the host lane (reduceat).",
    labelnames=("impl",),
)
FOLD_ROWS_TOTAL = GLOBAL_METRICS.counter(
    "horaedb_pushdown_rows_total",
    help="Rows the pushdown's folds took in: real = the scan's rows, "
         "padded = the rows added to reach the program's row class.",
    labelnames=("kind",),
)
for _kind in ("real", "padded"):
    FOLD_ROWS_TOTAL.labels(_kind)
del _kind
PACK_TOTAL = GLOBAL_METRICS.counter(
    "horaedb_pushdown_pack_total",
    help="Packed passes of the aggregate pushdown (a segment's surviving "
         "rows put in (series, ts) order before its fold), by the work they "
         "took: in_order (the rows arrived in order), sorted (one sort, no "
         "(series, ts) repeated) or dedup (repeats, settled by __seq__).",
    labelnames=("order",),
)
for _order in ("in_order", "sorted", "dedup"):
    PACK_TOTAL.labels(_order)
del _order


# the largest compiled row class a smaller fold may ride instead of
# compiling its own: 2^19 rows are 15 MB of lanes, a few milliseconds
_MAX_RIDE_ROWS = 1 << 19


class _RowClasses:
    """The row classes the fold's program has been run at, by its other
    classes (grid, flags, implementation, dtypes). A fold takes the smallest
    of them that holds its rows (up to `_MAX_RIDE_ROWS`) before it adds its
    own: a window that starts at a random second cuts row counts of every
    size out of a segment, down to a handful, and the small classes are too
    rare for any warm-up to meet — once the largest class a panel needs has
    compiled, no cut of it compiles again."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rows: dict[tuple, list[int]] = {}

    def pick(self, key: tuple, natural: int) -> int:
        with self._lock:
            have = self._rows.setdefault(key, [])
            for rows in have:
                if natural <= rows <= _MAX_RIDE_ROWS:
                    return rows
            at = bisect.bisect_left(have, natural)
            if at == len(have) or have[at] != natural:
                # once: a class past the ride limit is met again by every
                # fold of its size
                have.insert(at, natural)
            return natural


_ROW_CLASSES = _RowClasses()


class FoldRun(NamedTuple):
    """What one fold ran as: the implementation and, for a device program,
    its classes (the padded row count and the padded grid)."""

    impl: str
    rows_real: int
    rows_class: int      # 0 on the host lane: nothing is padded there
    grid_class: tuple    # (series, buckets) of the program's grid


def fold_classes(n: int, num_series: int, num_buckets: int) -> tuple[int, int, int]:
    """(rows, series, buckets) a fold of n rows into a num_series x
    num_buckets grid is padded to: power-of-two classes on all three axes,
    so that compiled programs are shared by every window and panel of a
    class (the batcher pads its three axes the same way)."""
    rows = pow2_rows(n, floor=_MIN_FOLD_ROWS)
    series, buckets = pow2_rows(num_series, floor=1), pow2_rows(num_buckets, floor=1)
    if series * buckets > _MAX_CLASS_CELLS:
        series, buckets = num_series, num_buckets
    return rows, series, buckets


def _fold_grids(ts, series_idx, values, row_ok, order_key, t0, bucket_ms,
                num_series: int, num_buckets: int, with_minmax: bool,
                impl: str | None):
    """The fold's arithmetic, traceable: rows SORTED by (series, ts) to flat
    [num_series * num_buckets] grids. Buckets, the in-grid mask, the cell
    keys, then sum and count through the sorted-segment reduction `impl`
    names (ops/blockagg.py) and, with `with_minmax`, min and max.

    `row_ok` (bool or None) excludes rows without breaking the sorted runs:
    an excluded row keeps its monotone key and rides the weight column with
    weight 0. `order_key` (i64 or None) is the min lane of `f64_order_keys`:
    the selections then reduce it (its max lane is made here: a NaN row is
    the smallest key on one lane and the largest on the other) and come
    back as i64 keys for `f64_from_order_keys`; without it they reduce
    `values` themselves."""
    from horaedb_tpu.ops.blockagg import (
        _F32_EXACT,
        _scatter_min_max,
        _scatter_sum_count,
        sorted_segment_min_max,
        sorted_segment_sum_count,
    )

    num_cells = num_series * num_buckets
    bucket = ((ts - t0) // bucket_ms).astype(jnp.int32)
    ok = (
        (bucket >= 0) & (bucket < num_buckets)
        & (series_idx >= 0) & (series_idx < num_series)
    )
    if row_ok is not None:
        ok = ok & row_ok
    safe, _flat = masked_cell_keys(series_idx, bucket, ok, num_series, num_buckets)
    # a grid too large for exact f32 cell-id recovery takes plain scatters
    big = num_cells >= _F32_EXACT

    def min_max(lane):
        if big:
            return _scatter_min_max(safe, lane, num_cells, valid=ok)
        return sorted_segment_min_max(safe, lane, num_cells, impl=impl, valid=ok)

    # typed zero fill: a weak 0.0 would promote integer values to float and
    # bypass the dtype-preserving integer route
    masked = jnp.where(ok, values, jnp.zeros((), values.dtype))
    weights = ok.astype(values.dtype)
    if big:
        s, c = _scatter_sum_count(safe, masked, num_cells, w=weights)
    else:
        s, c = sorted_segment_sum_count(
            safe, masked, num_cells, impl=impl, weights=weights,
        )
    out = {"sum": s, "count": c}
    if with_minmax and order_key is not None:
        key_max = jnp.where(order_key == _NAN_LOW, _NAN_HIGH, order_key)
        out["min"] = min_max(order_key)[0]
        out["max"] = min_max(key_max)[1]
    elif with_minmax:
        out["min"], out["max"] = min_max(values)
    return out


# The pushdown's fold as ONE compiled program a class: its shapes are the
# padded rows (`fold_classes`) and its static arguments the padded grid,
# `with_minmax` and the implementation; `t0` and `bucket_ms` are operands,
# so a window's start never retraces.
downsample_fold = xjit(
    _fold_grids, kernel="downsample_fold",
    static_argnames=("num_series", "num_buckets", "with_minmax", "impl"),
)


def _device_impl(choice: str, dtype) -> str:
    """The implementation the program runs for the dispatcher's choice. An
    f32 lane takes the choice. A wider lane (f64, integers) needs one that
    keeps its dtype: the plain scatter where 64-bit lanes are native, the
    segmented scan over the sorted runs on an accelerator, which emulates
    them and serialises a scatter over them."""
    if dtype == np.float32 or choice == "runs":
        return choice
    return "scatter" if device_f64_is_exact() else "runs"


def _padded(lane: np.ndarray, rows: int, fill=None) -> np.ndarray:
    """`lane` at `rows` rows: the tail repeats its last row (no fill given),
    which keeps a sorted lane sorted."""
    out = np.empty(rows, lane.dtype)
    out[:len(lane)] = lane
    if fill is None:
        fill = lane[-1] if len(lane) else 0
    out[len(lane):] = fill
    return out


def fold_sorted(
    ts,
    series_idx,
    values,
    t0,
    bucket_ms,
    num_series: int,
    num_buckets: int,
    with_minmax: bool = True,
    valid=None,
) -> tuple[dict, FoldRun]:
    """One fold of the aggregate pushdown: concrete rows SORTED by (series,
    ts) — the engine's scan order — to host [num_series, num_buckets] grids
    (sum, count and, with `with_minmax`, min and max), and what ran.

    The calibrated registry dispatcher is asked once. A host lane
    (np.add.reduceat over run boundaries) computes the whole grid on the
    host, with no f32-exact ceiling. Otherwise the rows are padded to their
    class (`fold_classes`, or a larger one the program has already run at:
    `_RowClasses`) and ONE program (`downsample_fold`) reduces them: padding rows
    repeat the last row's key with weight 0, padded series and buckets are
    sliced off here, and the grids come back in one transfer.

    `valid` (optional bool) excludes rows (predicate / set-membership miss)
    WITHOUT breaking the sorted runs: excluded rows must keep a monotone
    series_idx (the searchsorted position, not -1).

    f64 values take their min/max over i64 order keys built on the host
    (`f64_order_keys`), whatever the backend: a selection is a stored sample
    bit for bit. On an accelerator, values whose sums its f64 cannot carry
    (`device_sums_hold`) take the host lane, recorded as the dispatcher's
    choice like any other; an f64 jax array is refused there, since it has
    already lost bits.

    Stages (storage/scanstats.py): `fold_prep` (choice, order keys,
    padding), `fold_h2d`, `fold_kernel` (dispatch and wait), `fold_d2h`; the
    host lane is `fold_host`."""
    from horaedb_tpu.common import tracing
    from horaedb_tpu.ops import agg_registry
    from horaedb_tpu.storage import scanstats

    with scanstats.stage("fold_prep"):
        f64 = jnp.result_type(values) == jnp.float64
        ensure(
            device_f64_is_exact() or not (f64 and isinstance(values, jax.Array)),
            "an f64 device array has already lost bits on this backend: "
            "hand the fold the host array",
        )
        ts, series_idx, values = np.asarray(ts), np.asarray(series_idx), np.asarray(values)
        n = len(values)
        if f64 and not device_sums_hold(values):
            choice = agg_registry.record_choice("reduceat")
        else:
            choice = agg_registry.choose_sorted(
                n, num_series * num_buckets, concrete=True)
        host = agg_registry.is_host_impl(choice)
        if not host:
            impl = _device_impl(choice, values.dtype)
            rows, series, buckets = fold_classes(n, num_series, num_buckets)
            keyed = with_minmax and f64
            rows = _ROW_CLASSES.pick(
                (series, buckets, with_minmax, impl, values.dtype.str, keyed), rows)
            row_ok = np.zeros(rows, bool)  # the padding rows stay excluded
            row_ok[:n] = True if valid is None else valid
            lanes = (
                _padded(ts.astype(np.int64, copy=False), rows),
                _padded(series_idx.astype(np.int32, copy=False), rows),
                _padded(values, rows, 0),
                row_ok,
                _padded(f64_order_keys(values)[0], rows, 0) if keyed else None,
            )
    if host:
        with scanstats.stage("fold_host"):
            out = agg_registry.host_downsample_sorted(
                ts, series_idx, values, t0, bucket_ms,
                num_series=num_series, num_buckets=num_buckets,
                with_minmax=with_minmax, valid=valid, impl=choice,
            )
            out.pop("mean")
        run = FoldRun(choice, n, 0, (num_series, num_buckets))
    else:
        with scanstats.stage("fold_h2d"):
            # jaxlint: disable=J001 the stage's fence: each lane names its own seconds
            operands = jax.block_until_ready(jax.device_put(lanes))
        with scanstats.stage("fold_kernel"):
            # jaxlint: disable=J001 dispatch + wait IS this stage; the grids are fetched right after
            flat = jax.block_until_ready(downsample_fold(
                *operands, np.int64(t0), np.int64(bucket_ms),
                num_series=series, num_buckets=buckets,
                with_minmax=with_minmax, impl=impl,
            ))
        with scanstats.stage("fold_d2h"):
            # jaxlint: disable=J001 the fold's one read-back: every grid in one device_get
            grids = jax.device_get(flat)
            out = {
                k: g.reshape(series, buckets)[:num_series, :num_buckets]
                for k, g in grids.items()
            }
            if keyed:
                # the winners of the i64 order keys, back to f64 on the host
                out["min"] = f64_from_order_keys(out["min"])
                out["max"] = f64_from_order_keys(out["max"])
        run = FoldRun(impl, n, rows, (series, buckets))
    padding = max(0, run.rows_class - n)
    FOLDS_TOTAL.labels(run.impl).inc()
    FOLD_ROWS_TOTAL.labels("real").inc(n)
    FOLD_ROWS_TOTAL.labels("padded").inc(padding)
    scanstats.note("folds")
    scanstats.note("fold_rows_real", n)
    scanstats.note("fold_rows_padded", padding)
    scanstats.note(
        f"fold_class_{run.rows_class}x{run.grid_class[0]}x{run.grid_class[1]}")
    tracing.add_attr(
        agg_impl=run.impl, fold_rows_class=run.rows_class,
        fold_grid_class=f"{run.grid_class[0]}x{run.grid_class[1]}",
    )
    return out, run


def downsample_sorted(
    ts,
    series_idx,
    values,
    t0,
    bucket_ms,
    num_series: int,
    num_buckets: int,
    with_minmax: bool = True,
    valid=None,
) -> dict:
    """Downsample over rows SORTED by (series, ts) — the engine's natural
    scan-output order (pk = ids + timestamp), which makes the flat cell index
    monotone — to [num_series, num_buckets] grids: sum, count, mean and,
    with `with_minmax`, min and max.

    Two entries, one body (`_fold_grids`). Concrete inputs are one fold of
    the pushdown (`fold_sorted`: the registry's choice, the padded program
    or the host lane, host grids back). Traced inputs (inside a caller's own
    jit or shard_map) run the body in that program, min/max over `values`
    themselves."""
    if any(isinstance(x, jax.core.Tracer) for x in (ts, series_idx, values, valid)):
        flat = _fold_grids(
            jnp.asarray(ts), jnp.asarray(series_idx), jnp.asarray(values),
            None if valid is None else jnp.asarray(valid), None, t0, bucket_ms,
            num_series, num_buckets, with_minmax, None,
        )
        out = {k: g.reshape(num_series, num_buckets) for k, g in flat.items()}
        out["mean"] = out["sum"] / out["count"]
        return out
    out, _run = fold_sorted(
        ts, series_idx, values, t0, bucket_ms, num_series, num_buckets,
        with_minmax=with_minmax, valid=valid,
    )
    with np.errstate(invalid="ignore", divide="ignore"):
        out["mean"] = out["sum"] / out["count"]
    return out


@xjit(kernel="lane_sum_count", static_argnames=("num_cells", "lanes"))
def lane_segment_sum_count(k, v, num_cells: int, lanes: int = 8, w=None):
    """Experimental lane-parallel scatter: rows reshape to [lanes, n/lanes]
    and each lane scatter-adds into its OWN partial grid (vmap batches the
    scatters), then the lanes tree-reduce. If XLA vectorizes the batched
    scatter across lanes, this trades lanes x grid memory for lanes-fold
    scatter parallelism — an A/B candidate against the block compaction on
    real hardware (queued from round-1 profiling). Works for unsorted input.
    `w` (optional) is each row's count contribution (predicate weights).
    """
    n = k.shape[0]
    m = n - n % lanes
    k2 = jnp.clip(k[:m], 0, num_cells).astype(jnp.int32).reshape(lanes, -1)
    v2 = v[:m].astype(jnp.float32).reshape(lanes, -1)
    w2 = (
        jnp.ones_like(v2) if w is None
        else w[:m].astype(jnp.float32).reshape(lanes, -1)
    )

    def one(kl, vl, wl):
        s = jax.ops.segment_sum(vl, kl, num_cells + 1)[:-1]
        c = jax.ops.segment_sum(wl, kl, num_cells + 1)[:-1]
        return s, c

    s, c = jax.vmap(one)(k2, v2, w2)
    s, c = s.sum(axis=0), c.sum(axis=0)
    if m < n:
        kt = jnp.clip(k[m:], 0, num_cells).astype(jnp.int32)
        vt = v[m:].astype(jnp.float32)
        wt = jnp.ones_like(vt) if w is None else w[m:].astype(jnp.float32)
        s = s + jax.ops.segment_sum(vt, kt, num_cells + 1)[:-1]
        c = c + jax.ops.segment_sum(wt, kt, num_cells + 1)[:-1]
    return s, c


@xjit(kernel="stacked_downsample",
      static_argnames=("num_series", "num_buckets"))
def stacked_downsample(
    ts: jax.Array,
    series_idx: jax.Array,
    values: jax.Array,
    valid: jax.Array,
    t0: jax.Array,
    bucket_ms,
    num_series: int,
    num_buckets: int,
    order_keys,
) -> dict[str, jax.Array]:
    """Downsample grids for a STACK of coalesced queries in one launch —
    the query batcher's device lane (server/batching.py): inputs carry a
    leading query axis ([B, R] row lanes padded to shared power-of-two
    buckets, per-query `t0` as a [B] dynamic operand so start offsets
    never retrace), output is [B, num_series, num_buckets] per stat.

    Lane-offset flattening keeps bit-exact parity with solo execution
    while outrunning a vmapped scatter ~2x on CPU (measured): every row
    gets the flat cell id `lane * num_series * num_buckets + sid *
    num_buckets + bucket`, masked rows route to the one shared sentinel,
    and ONE segment reduction over the flattened [B*R] lanes fills every
    query's grid. Lanes own disjoint id ranges and each lane's rows stay
    contiguous and in scan order, so a cell accumulates exactly the rows
    — in exactly the order — its query's solo reduction would. Shapes
    are static in (B, R, num_series, num_buckets) — the batcher pads all
    three axes to power-of-two classes, so compiled executables are
    shared across launches and retraces stay caught by xprof.

    Accumulation dtype follows the inputs (f64 on the x64 CPU path, the
    engine's precision contract — see SampleManager.query_downsample).

    `order_keys` (the [B, R] i64 min and max lanes of `f64_order_keys`)
    carry the selection: "min"/"max" come back as i64 keys for
    `f64_from_order_keys`, exact on every backend; the f64 value lane feeds
    sum and count only."""
    nb, cells = t0.shape[0], num_series * num_buckets
    bucket = ((ts - t0[:, None]) // bucket_ms).astype(jnp.int32)
    ok = (
        valid & (bucket >= 0) & (bucket < num_buckets)
        & (series_idx >= 0) & (series_idx < num_series)
    )
    lane = jnp.arange(nb, dtype=jnp.int32)[:, None]
    safe = jnp.clip(series_idx, 0, num_series - 1) * num_buckets \
        + jnp.clip(bucket, 0, num_buckets - 1)
    flat = jnp.where(ok, lane * cells + safe, nb * cells)
    flat, ok = flat.reshape(-1), ok.reshape(-1)
    s, c, _mn, _mx = masked_segment_stats(
        values.reshape(-1), flat, ok, nb * cells, with_minmax=False
    )
    # masked rows already route to the sentinel cell, which is sliced off
    kmin, kmax = order_keys
    mn = jax.ops.segment_min(kmin.reshape(-1), flat, nb * cells + 1)[:-1]
    mx = jax.ops.segment_max(kmax.reshape(-1), flat, nb * cells + 1)[:-1]
    shape = (nb, num_series, num_buckets)
    s, c = s.reshape(shape), c.reshape(shape)
    return {"sum": s, "count": c, "min": mn.reshape(shape),
            "max": mx.reshape(shape), "mean": s / c}


@xjit(kernel="downsample", static_argnames=("num_series", "num_buckets"))
def downsample(
    ts: jax.Array,
    series_idx: jax.Array,
    values: jax.Array,
    valid: jax.Array,
    t0,
    bucket_ms,
    num_series: int,
    num_buckets: int,
) -> dict[str, jax.Array]:
    """Per-(series, bucket) stats as dense [num_series, num_buckets] grids —
    the 5m-avg downsample of BASELINE config 4.
    """
    bucket = bucket_of(ts, t0, bucket_ms)
    in_grid = valid & (bucket >= 0) & (bucket < num_buckets) \
        & (series_idx >= 0) & (series_idx < num_series)
    flat = series_idx.astype(jnp.int32) * num_buckets + bucket
    stats = grouped_stats(values, flat, in_grid, num_series * num_buckets)
    return {k: v.reshape(num_series, num_buckets) for k, v in stats.items()}


@xjit(kernel="segment_last_value", static_argnames=("num_segments",))
def segment_last_value(
    values: jax.Array,
    seq: jax.Array,
    index: jax.Array,
    valid: jax.Array,
    num_segments: int,
) -> jax.Array:
    """Value of the max-seq row per segment — dedup-as-reduction for
    aggregation pipelines that don't need full row materialization.
    Implemented as an argmax over (seq) per segment via segment_max on a
    packed (seq, position) key."""
    n = values.shape[0]
    idx = _masked_index(index, valid, num_segments)
    # Two-stage argmax (no packed-key arithmetic: real sequences are ns-clock
    # file ids ~1.8e18, so seq*n would overflow int64): find each segment's
    # max seq, then take the latest row achieving it.
    seq_i = seq.astype(jnp.int64)
    max_seq = jax.ops.segment_max(
        jnp.where(valid, seq_i, jnp.iinfo(jnp.int64).min), idx, num_segments + 1
    )
    winner = valid & (seq_i == max_seq[idx])
    pos = jnp.arange(n, dtype=jnp.int64)
    best_pos = jax.ops.segment_max(jnp.where(winner, pos, -1), idx, num_segments + 1)[:-1]
    return jnp.where(best_pos >= 0, values[jnp.clip(best_pos, 0, n - 1)], jnp.nan)
