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

import jax
import jax.numpy as jnp
import numpy as np

from horaedb_tpu.common.error import ensure
from horaedb_tpu.common.xprof import xjit
from horaedb_tpu.ops.sort import f64_order_i64


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
    monotone. sum/count dispatch to the sorted-segment compaction
    (ops/blockagg.py; MXU one-hot matmuls instead of a scatter, with
    an automatic XLA fallback); min/max, when requested, use the
    masked-reduce compaction (sorted_segment_min_max, scatter fallback).

    `valid` (optional bool) excludes rows (predicate / set-membership miss)
    WITHOUT breaking the sorted runs: excluded rows must keep a monotone
    series_idx (e.g. the searchsorted position, not -1) and are zeroed via
    the compaction's weight column.

    Concrete (non-traced) inputs consult the calibrated registry
    dispatcher first: when the measured winner is a host lane
    (np.add.reduceat over run boundaries), the WHOLE grid computes on host
    — no device dispatch at all, and no f32-exact grid-size ceiling (host
    keys are i64).

    Concrete f64 values take their min/max over i64 order keys built on the
    host (`f64_order_keys`), whatever the backend. On an accelerator, values
    whose sums its f64 cannot carry (`device_sums_hold`) take the host
    reduceat lane, recorded as the dispatcher's choice like any other; an
    f64 jax array is refused there, since it has already lost bits.
    """
    from horaedb_tpu.ops import agg_registry
    from horaedb_tpu.ops.blockagg import (
        _F32_EXACT,
        _scatter_min_max,
        _scatter_sum_count,
        sorted_segment_min_max,
        sorted_segment_sum_count,
    )

    num_cells = num_series * num_buckets
    traced = any(
        isinstance(x, jax.core.Tracer)
        for x in (ts, series_idx, values, valid)
    )
    # resolve the dispatcher ONCE and thread the choice through both
    # reductions below — re-resolving per reduction would triple-count
    # horaedb_agg_impl_total and re-read env/cache on the scan hot path
    choice: str | None = None
    order_keys = None
    if not traced:
        f64 = jnp.result_type(values) == jnp.float64
        ensure(
            device_f64_is_exact() or not (f64 and isinstance(values, jax.Array)),
            "an f64 device array has already lost bits on this backend: "
            "hand downsample_sorted the host array",
        )
        if f64 and not device_sums_hold(np.asarray(values)):
            choice = agg_registry.record_choice("reduceat")
        else:
            choice = agg_registry.choose_sorted(
                jnp.shape(values)[0], num_cells, concrete=True
            )
        if agg_registry.is_host_impl(choice):
            return agg_registry.host_downsample_sorted(
                ts, series_idx, values, t0, bucket_ms,
                num_series=num_series, num_buckets=num_buckets,
                with_minmax=with_minmax, valid=valid, impl=choice,
            )
        if with_minmax and f64:
            order_keys = f64_order_keys(np.asarray(values))
    ts = jnp.asarray(ts)
    series_idx = jnp.asarray(series_idx)
    values = jnp.asarray(values)
    bucket = ((ts - t0) // bucket_ms).astype(jnp.int32)
    ok = (
        (bucket >= 0) & (bucket < num_buckets)
        & (series_idx >= 0) & (series_idx < num_series)
    )
    if valid is not None:
        ok = ok & jnp.asarray(valid)
    safe, _flat = masked_cell_keys(series_idx, bucket, ok, num_series, num_buckets)
    # a grid too large for exact f32 cell-id recovery takes plain scatters
    big = num_cells >= _F32_EXACT

    def min_max(lane):
        if big:
            return _scatter_min_max(safe, lane, num_cells, valid=ok)
        return sorted_segment_min_max(safe, lane, num_cells, impl=choice, valid=ok)

    # typed zero fill: a weak 0.0 would promote integer values to float and
    # bypass the dtype-preserving integer scatter route
    masked = jnp.where(ok, values, jnp.zeros((), values.dtype))
    weights = ok.astype(values.dtype)
    if big:
        s, c = _scatter_sum_count(safe, masked, num_cells, w=weights)
    else:
        s, c = sorted_segment_sum_count(
            safe, masked, num_cells, impl=choice, weights=weights,
        )
    shape = (num_series, num_buckets)
    out = {
        "sum": s.reshape(shape),
        "count": c.reshape(shape),
        "mean": (s / c).reshape(shape),
    }
    if with_minmax and order_keys is not None:
        # a selection returns a stored sample bit for bit: reduce the i64
        # order keys and map the winners back to f64 on the host
        kmin, kmax = order_keys
        mn, mx = min_max(kmin)
        if kmax is not kmin:  # the block holds a NaN: its own max lane
            mx = min_max(kmax)[1]
        mn, mx = f64_from_order_keys(mn), f64_from_order_keys(mx)
    elif with_minmax:
        mn, mx = min_max(values)
    if with_minmax:
        out["min"] = mn.reshape(shape)
        out["max"] = mx.reshape(shape)
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
