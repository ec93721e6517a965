"""Sharded scan→filter→aggregate over the device mesh.

This is the distributed form of ops/aggregate.py: rows shard over the "rows"
mesh axis, the series/group dimension shards over "series", and partial
(sum, count, min, max) grids combine with psum/pmin/pmax over the rows axis —
the ICI collectives that replace the reference's single-node k-way merge of
per-SST streams (SURVEY §2.5: "sharded shuffle/merge collectives").

The output grids stay sharded over "series" (PartitionSpec("series") on the
leading dim), so a 10M-series group-by never materializes on a single chip.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horaedb_tpu.common import colblock
from horaedb_tpu.common import deadline as deadline_ctx
from horaedb_tpu.common import memtrace
from horaedb_tpu.common.error import ensure
from horaedb_tpu.common.jaxcompat import shard_map
from horaedb_tpu.common.xprof import xjit
from horaedb_tpu.ops import filter as filter_ops
from horaedb_tpu.ops.filter import Predicate
from horaedb_tpu.server.metrics import GLOBAL_METRICS

H2D_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_h2d_transfer_seconds",
    help="Host->device placement time per sharded-scan input batch "
         "(dispatch only unless a scanstats collector fences transfers).",
)
H2D_BYTES = GLOBAL_METRICS.counter(
    "horaedb_h2d_transfer_bytes_total",
    help="Bytes placed onto the mesh by sharded scans.",
)


def _local_grids(ts, sid, vals, valid, t0, bucket_ms, series_lo, local_series,
                 num_buckets, with_minmax, sorted_input=False, sorted_impl=None,
                 unsorted_impl=None):
    """Partial grids for this shard's rows, restricted to the series slice
    [series_lo, series_lo + local_series).

    sum and count share ONE variadic scatter (stacked features) — scatters
    are the expensive op on TPU (random-index updates don't vectorize), so
    the kernel issues as few as possible; min/max add two more and are only
    computed when requested.

    `sorted_input=True` declares rows ordered by (sid, ts) — the engine's
    natural scan-output order. The sum/count reduction then dispatches to
    the sorted-segment strategies (ops/blockagg.py; `sorted_impl=None`
    resolves through the calibrated registry dispatcher in
    ops/agg_registry.py at trace time, restricted to traceable impls —
    host lanes cannot ride shard_map); results are identical either way,
    sortedness only affects speed.
    """
    local_sid = sid - series_lo
    bucket = ((ts - t0) // bucket_ms).astype(jnp.int32)
    in_slice = (local_sid >= 0) & (local_sid < local_series)
    ok = valid & in_slice & (bucket >= 0) & (bucket < num_buckets)
    num_cells = local_series * num_buckets
    from horaedb_tpu.ops.aggregate import masked_cell_keys, masked_minmax

    # `safe` (in-range, mask rides the weight column) feeds sum/count;
    # `flat` (sentinel drop) feeds min/max — see masked_cell_keys.
    safe, flat = masked_cell_keys(local_sid, bucket, ok, local_series, num_buckets)
    # Rows OUTSIDE this shard's contiguous series slice go to the sentinel
    # key instead of a clipped in-range key: in (sid, ts) order they form a
    # contiguous prefix/suffix, so sentinel runs stay whole — clipping them
    # to local_sid 0/local_series-1 would fragment them into one run per
    # (foreign series x bucket) and trip the block compaction's
    # distinct-per-block check on sparse shards. Predicate/bucket misses
    # keep clipped keys (their mask rides the weight column).
    safe = jnp.where(in_slice, safe, num_cells)
    # typed zero fill: a weak 0.0 would promote integer vals to f32 and
    # bypass the dtype-preserving integer scatter route
    vals_masked = jnp.where(ok, vals, jnp.zeros((), vals.dtype))
    from horaedb_tpu.ops.blockagg import (
        _F32_EXACT,
        segment_sum_count,
        sorted_segment_min_max,
        sorted_segment_sum_count,
        unsorted_strategy,
    )

    mn = mx = None
    if sorted_input and num_cells < _F32_EXACT:
        s, c = sorted_segment_sum_count(
            safe, vals_masked, num_cells, impl=sorted_impl,
            weights=ok.astype(vals.dtype),
        )
        if with_minmax:
            mn, mx = sorted_segment_min_max(
                safe, vals_masked, num_cells, impl=sorted_impl, valid=ok
            )
    elif (
        num_cells < _F32_EXACT
        and unsorted_strategy(
            safe.shape[0], num_cells, vals_masked.dtype, unsorted_impl
        ) == "sort"
    ):
        # Unsorted rows, compaction-eligible: ONE device sort feeds both
        # reductions (sort ~4 ns/row replaces up to four 9 ns/row scatters).
        # Post-sort, sentinel keys are contiguous at the tail, so no weight
        # column is needed — invalid rows drop via the sentinel bucket.
        k2, v2 = lax.sort((flat, vals_masked), num_keys=1)
        s, c = sorted_segment_sum_count(k2, v2, num_cells, impl="block")
        if with_minmax:
            mn, mx = sorted_segment_min_max(k2, v2, num_cells, impl="block")
    else:
        s, c = segment_sum_count(
            safe, vals_masked, num_cells, impl="scatter",
            weights=ok.astype(vals.dtype),
        )
        if with_minmax:
            mn, mx = masked_minmax(vals, flat, ok, num_cells)
    shape = (local_series, num_buckets)
    if not with_minmax:
        return s.reshape(shape), c.reshape(shape), None, None
    return s.reshape(shape), c.reshape(shape), mn.reshape(shape), mx.reshape(shape)


@lru_cache(maxsize=128)
def build_sharded_downsample(
    mesh: Mesh,
    num_series: int,
    num_buckets: int,
    predicate: Predicate | None = None,
    with_minmax: bool = True,
    sorted_input: bool = False,
    sorted_impl: str | None = None,
    unsorted_impl: str | None = None,
):
    """Compile the sharded downsample step for a fixed grid shape.

    `sorted_impl` / `unsorted_impl` pin the reduction strategy into this
    executable (part of the memo key — required for in-process A/B, since
    the env default is read once at trace time).

    Returns fn(ts, sid, vals, valid, literals, t0, bucket_ms) -> dict of
    [num_series, num_buckets] grids sharded P("series", None). Inputs are
    1-D row arrays sharded P("rows") (row count must divide the rows axis).
    `with_minmax=False` halves the scatter count for mean/sum-only queries.

    Memoized: repeat queries with the same mesh/grid/predicate template reuse
    the jitted executable. Pass predicates through `split_literals` first (or
    literal-free) so a changed constant hits the cache.
    """
    series_par = mesh.shape["series"]
    ensure(num_series % series_par == 0,
           f"num_series={num_series} must divide over series axis={series_par}")
    local_series = num_series // series_par
    template, _ = filter_ops.split_literals(predicate)
    keys = ("sum", "count", "min", "max", "mean") if with_minmax else ("sum", "count", "mean")

    def step(ts, sid, vals, valid, literals, t0, bucket_ms):
        cols = {"__ts__": ts, "__sid__": sid, "__val__": vals}
        if template is not None:
            valid = valid & filter_ops.eval_predicate(template, cols, literals)
        s_idx = lax.axis_index("series")
        lo = (s_idx * local_series).astype(sid.dtype)
        s, c, mn, mx = _local_grids(
            ts, sid, vals, valid, t0, bucket_ms, lo, local_series, num_buckets,
            with_minmax, sorted_input=sorted_input, sorted_impl=sorted_impl,
            unsorted_impl=unsorted_impl,
        )
        # combine partials across the row shards (ICI all-reduce)
        s = lax.psum(s, "rows")
        c = lax.psum(c, "rows")
        out = {"sum": s, "count": c, "mean": s / c}
        if with_minmax:
            out["min"] = lax.pmin(mn, "rows")
            out["max"] = lax.pmax(mx, "rows")
        return out

    row_spec = P("rows")
    grid_spec = P("series", None)
    mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(row_spec, row_spec, row_spec, row_spec, P(), P(), P()),
        out_specs={k: grid_spec for k in keys},
    )
    return xjit(mapped, kernel="sharded_downsample")


def sharded_downsample(
    mesh: Mesh,
    ts,
    sid,
    vals,
    valid,
    t0,
    bucket_ms,
    num_series: int,
    num_buckets: int,
    predicate: Predicate | None = None,
    with_minmax: bool = True,
    sorted_input: bool = False,
):
    """One-shot wrapper: splits predicate literals so repeat queries with new
    constants reuse the memoized executable."""
    # cooperative deadline before the device dispatch (host side, outside
    # the traced body): an expired query launches no kernel
    deadline_ctx.check("device_lane")
    template, literals = filter_ops.split_literals(predicate)
    fn = build_sharded_downsample(
        mesh, num_series, num_buckets, template, with_minmax, sorted_input
    )
    lit_arrays = filter_ops.literal_arrays(
        template, literals,
        {"__ts__": ts.dtype, "__sid__": sid.dtype, "__val__": vals.dtype},
    )
    return fn(ts, sid, vals, valid, lit_arrays,
              jnp.asarray(t0, dtype=ts.dtype), jnp.asarray(bucket_ms, dtype=ts.dtype))


@lru_cache(maxsize=64)
def build_multisegment_downsample(
    mesh: Mesh,
    num_series: int,
    num_buckets: int,
):
    """3-axis scan step over a ("seg", "rows", "series") mesh — the
    TPU-native form of the reference's per-segment plan union
    (UnionExec over time segments, storage.rs:343-369):

    - "seg" shards independent time segments (no collective crosses it —
      segments are separate LSM windows; the pipeline-parallel analog);
    - "rows" data-parallels each segment's rows (psum/pmin/pmax combines);
    - "series" shards the output grids.

    Inputs are [n_segments, rows] arrays sharded P("seg", "rows") plus a
    per-segment t0 vector sharded P("seg"); output grids are
    [n_segments, num_series, num_buckets] sharded P("seg", "series", None).
    """
    series_par = mesh.shape["series"]
    ensure(num_series % series_par == 0,
           f"num_series={num_series} must divide over series axis={series_par}")
    local_series = num_series // series_par

    def step(ts, sid, vals, valid, t0_seg, bucket_ms):
        # shard-local shapes: [segs_local, rows_local]; the kernel handles
        # exactly one segment per seg-shard
        ensure(
            ts.shape[0] == 1,
            # jaxlint: disable=J002 trace-time assert formats a STATIC shape, not a tracer
            f"n_segments must equal the seg mesh axis "
            f"(got {ts.shape[0]} local segments per shard)",
        )
        s_idx = lax.axis_index("series")
        lo = (s_idx * local_series).astype(sid.dtype)
        s, c, mn, mx = _local_grids(
            ts[0], sid[0], vals[0], valid[0], t0_seg[0], bucket_ms,
            lo, local_series, num_buckets, True,
        )
        s = lax.psum(s, "rows")
        c = lax.psum(c, "rows")
        mn = lax.pmin(mn, "rows")
        mx = lax.pmax(mx, "rows")
        out = {"sum": s, "count": c, "min": mn, "max": mx, "mean": s / c}
        return {k: v[None] for k, v in out.items()}

    row_spec = P("seg", "rows")
    grid_spec = P("seg", "series", None)
    mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(row_spec, row_spec, row_spec, row_spec, P("seg"), P()),
        out_specs={k: grid_spec for k in ("sum", "count", "min", "max", "mean")},
    )
    return xjit(mapped, kernel="multisegment_downsample")


def sharded_grouped_stats(
    mesh: Mesh,
    group_idx,
    vals,
    valid,
    num_groups: int,
    predicate: Predicate | None = None,
    with_minmax: bool = True,
):
    """Group-by aggregation (BASELINE config 3) = downsample with one bucket:
    group ids play the series role, bucket axis is singleton."""
    ts = jnp.zeros_like(group_idx)
    out = sharded_downsample(
        mesh, ts, group_idx, vals, valid,
        t0=0, bucket_ms=1, num_series=num_groups, num_buckets=1,
        predicate=predicate, with_minmax=with_minmax,
    )
    return {k: v[:, 0] for k, v in out.items()}


def shard_rows(mesh: Mesh, arrays: tuple, pad_value=0):
    """Place 1-D host arrays onto the mesh row-sharded (pads to a multiple of
    the rows axis; returns (device_arrays, valid_mask)). Placement is timed
    into `horaedb_h2d_transfer_seconds` — the transfer lane VERDICT r02
    found dominating "kernel-bound" configs; when a scanstats collector is
    attached the puts are fenced so the histogram carries true transfer
    time, not just dispatch.

    `pad_value` is one scalar for every lane, or a per-lane sequence
    (len == len(arrays)). Per-lane pads matter for sorted inputs: the
    sid lane must pad with an OUT-OF-RANGE sentinel (>= the padded
    series count) so tail pad rows keep the keys monotone — a scalar 0
    would plant series-0 keys after larger ones and violate the sorted-
    segment kernels' contract (ops/blockagg.py), where only the weight
    column and the valid mask kept results right by accident."""
    import time

    import numpy as np

    from horaedb_tpu.storage import scanstats

    # cooperative deadline before the H2D transfer: expired queries ship
    # no bytes to the device
    deadline_ctx.check("device_lane")
    rows_par = mesh.shape["rows"]
    n = len(arrays[0])
    pad = (-n) % rows_par
    sharding = NamedSharding(mesh, P("rows"))
    pads = (list(pad_value) if isinstance(pad_value, (tuple, list))
            else [pad_value] * len(arrays))
    ensure(len(pads) == len(arrays),
           f"per-lane pad_value needs {len(arrays)} entries, got {len(pads)}")
    # pad on host BEFORE the timer: the pad fill is host_prep work and
    # must not inflate the transfer lane (the exact misattribution the
    # histogram exists to prevent). Pad-free lanes stage AS-IS — the
    # jax.device_put below reads the caller's block lanes in place (no
    # intermediate staging copy); only a genuine pad pays one aligned
    # tracked copy per lane
    padded = []
    nbytes = 0
    for a, pv in zip(arrays, pads):
        if pad:
            g = colblock.aligned_empty(n + pad, a.dtype)
            g[:n] = a
            g[n:] = pv
            memtrace.track(g, "host_prep", "copy")
            a = g
        padded.append(a)
        nbytes += a.nbytes
    valid = np.ones(n + pad, dtype=bool)
    if pad:
        valid[n:] = False
    t0 = time.perf_counter()
    out = [jax.device_put(a, sharding) for a in padded]
    valid_dev = jax.device_put(valid, sharding)
    if scanstats.active():  # fence only for attribution (production path
        # stays async so H2D overlaps kernel dispatch)
        # jaxlint: disable=J001 h2d attribution fence; profiling runs only
        jax.block_until_ready(out + [valid_dev])
    H2D_SECONDS.observe(time.perf_counter() - t0)
    H2D_BYTES.inc(nbytes + valid.nbytes)
    return tuple(out), valid_dev
