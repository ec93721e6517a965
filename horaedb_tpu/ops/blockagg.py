"""Block-rank compaction kernels for the hot aggregation path (pure XLA).

The profile (bench.py) shows XLA's scatter-add dominating the downsample
pipeline: random-index updates serialize on TPU (~9ns/row measured). But the
engine's data is SORTED by primary key (SSTs sort on write; the scan kernel
re-sorts merged segments), which this kernel exploits:

  sorted_segment_sum_count(k, v, num_cells):
    phase 1 (per row-block of B rows, lax.map over chunks):
      - run boundaries + block-local dense rank (cumsum over <=B distinct
        cells in the block);
      - one-hot(rank) [B, R] matmul against (v, 1) feature columns on the
        MXU -> per-rank (sum, count) partials, plus each rank's global cell
        id recovered with a second one-hot matmul against k*boundary;
    phase 2: scatter-add the (num_blocks * R) rank partials into the
      dense [num_cells] grid — B/R times fewer scatter rows than scattering
      raw samples (8x for B=512, R=64).

  A block with more than R distinct cells can't compact (its rank overflows
  R); `distinct_cells_per_block_max` is a cheap dense pre-check and callers
  fall back to plain segment_sum for such batches. Time-series workloads
  average many samples per (series, bucket) cell, so the fast path is the
  common case.

  f32 one-hot matmuls keep cell-id recovery exact for num_cells < 2**24.

History: a hand-written Pallas/mosaic variant of phase 1 lived here behind
HORAEDB_PALLAS=1. The on-chip A/B (v5e, 64M rows, 2.88M cells) measured
the pure-XLA form at 375M rows/s vs the mosaic kernel's 43M — XLA's own
fusion of the one-hot matmul pipeline beats the manual schedule, so the
mosaic path was deleted (VERDICT r02 #8 / r03 weak #8: "make it win or
delete it"). benchmarks/results_tpu.jsonl r02 holds the measurement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from horaedb_tpu.common.error import ensure
from horaedb_tpu.common.xprof import xjit

DEFAULT_BLOCK = 512
DEFAULT_RANKS = 64
_F32_EXACT = 1 << 24


def _distinct_max(k_sorted: jax.Array, block: int) -> jax.Array:
    """Traced form of the pre-check: max distinct cells in any row block as
    a device scalar (usable inside jit/shard_map)."""
    n = k_sorted.shape[0]
    nb = n // block
    if nb == 0:
        return jnp.zeros((), jnp.int32)
    k2 = k_sorted[: nb * block].reshape(nb, block)
    prev = jnp.concatenate([jnp.full((nb, 1), -1, k2.dtype), k2[:, :-1]], axis=1)
    return jnp.max(jnp.sum(k2 != prev, axis=1)).astype(jnp.int32)


def distinct_cells_per_block_max(k_sorted: jax.Array, block: int = DEFAULT_BLOCK) -> int:
    """Cheap dense pre-check: max distinct cells in any row block (counts a
    cell continuing from the previous block as new, matching the kernel).
    Concrete inputs only — inside jit use _distinct_max."""
    return int(_distinct_max(k_sorted, block))


# Row blocks per lax.map step in the pure-XLA path: bounds the materialized
# one-hot to chunk*block*ranks f32 (256*512*64*4 = 32 MB HBM peak). The
# one-hot is the path's HBM-traffic driver (~n*ranks*4 bytes total), which
# is why the defaults moved to block=512/ranks=64: same 8x compaction ratio,
# 4x less one-hot traffic than 2048/256 — measured 398M rows/s vs 66M on a
# v5e chip (64M rows, 2.88M cells).
XLA_CHUNK = 256


@xjit(kernel="block_sum_count", static_argnames=("num_cells", "block",
                                                 "ranks", "bf16_onehot",
                                                 "scan_prologue"))
def _block_sum_count_xla(k_sorted, v, num_cells, block, ranks, w=None,
                         bf16_onehot=False, scan_prologue=False):
    """Pure-XLA form of the block-rank compaction (same algorithm as the
    Pallas phase 1, expressed as chunked one-hot matmuls): the per-row
    scatter becomes an MXU contraction per row-block plus ONE scatter over
    nb*ranks partials — block/ranks-fold fewer scatter rows than scattering
    raw samples. Unlike the mosaic kernel this compiles everywhere,
    including remoted-TPU paths where custom-kernel compilation stalls.

    One einsum carries THREE feature columns — value, count weight, and the
    boundary-masked cell id — so the one-hot is read from HBM exactly once
    (the id-recovery einsum used to double the traffic).

    `w` (optional, f32) is each row's COUNT contribution: predicate-masked
    rows pass w=0 (with the value pre-masked to 0) while keeping their TRUE
    sorted cell id — masking via sentinel keys would interleave run breaks
    through the sorted stream and blow the per-block distinct-cell budget,
    forcing the adaptive scatter fallback exactly when a filter is active.

    ROOFLINE §1 experiment flags (both static, registry names in
    ops/agg_registry.py):
    - `bf16_onehot`: materialize the one-hot in bf16 and contract bf16
      (value, weight) features with f32 accumulation — halves the one-hot
      HBM traffic that dominates the kernel's model. Cell ids do NOT ride
      the einsum (bf16 would corrupt them above ~2^8); they recover
      EXACTLY via a boundary-masked integer max-reduce, the same trick the
      min/max kernel uses. Counts stay exact (0/1 is exact in bf16, f32
      accumulation); value sums carry the documented bf16 input-rounding
      budget (agg_registry.BF16_L1_BUDGET) that the calibrator verifies
      against a live f64 oracle before the lane may win.
    - `scan_prologue`: compute the block-local rank with a boundary-
      segmented `lax.associative_scan` instead of `cumsum` (log-depth
      vector-unit prologue instead of a linear chain)."""
    n = k_sorted.shape[0]
    nb = n // block
    ones = w is None
    k2 = k_sorted[: nb * block].reshape(nb, block).astype(jnp.int32)
    v2 = v[: nb * block].reshape(nb, block).astype(jnp.float32)
    w2 = None if ones else w[: nb * block].reshape(nb, block).astype(jnp.float32)
    pad = (-nb) % XLA_CHUNK
    if pad:
        k2 = jnp.concatenate(
            [k2, jnp.full((pad, block), num_cells, jnp.int32)]
        )
        v2 = jnp.concatenate([v2, jnp.zeros((pad, block), jnp.float32)])
        if not ones:
            w2 = jnp.concatenate([w2, jnp.zeros((pad, block), jnp.float32)])
    nsteps = k2.shape[0] // XLA_CHUNK
    k3 = k2.reshape(nsteps, XLA_CHUNK, block)
    v3 = v2.reshape(nsteps, XLA_CHUNK, block)
    w3 = None if ones else w2.reshape(nsteps, XLA_CHUNK, block)

    def step(xs):
        if ones:
            k, vv = xs  # [chunk, block]
            ww = jnp.ones_like(vv)
        else:
            k, vv, ww = xs
        prev = jnp.concatenate(
            [jnp.full((XLA_CHUNK, 1), -1, jnp.int32), k[:, :-1]], axis=1
        )
        boundary = k != prev
        b_i32 = boundary.astype(jnp.int32)
        if scan_prologue:
            rank = jax.lax.associative_scan(jnp.add, b_i32, axis=1) - 1
        else:
            rank = jnp.cumsum(b_i32, axis=1) - 1
        in_rank = rank < ranks
        oh_bool = (
            (rank[..., None]
             == jax.lax.broadcasted_iota(jnp.int32, (XLA_CHUNK, block, ranks), 2))
            & in_rank[..., None]
        )
        if bf16_onehot:
            # bf16 one-hot x bf16 (value, weight) features, f32 accumulate:
            # native MXU mode, half the materialized-one-hot traffic. Ids
            # recover via an exact integer max-reduce over the boundary row
            # (unused ranks yield -1 -> routed to the drop sentinel).
            oh = oh_bool.astype(jnp.bfloat16)
            feats = jnp.stack([vv, ww], axis=-1).astype(jnp.bfloat16)
            out = jnp.einsum(
                "cbr,cbf->crf", oh, feats,
                preferred_element_type=jnp.float32,
            )
            cells = jnp.max(
                jnp.where(oh_bool & boundary[..., None], k[..., None], -1),
                axis=1,
            )
            cells = jnp.where(cells < 0, num_cells, cells)
            return out[..., 0], out[..., 1], cells
        oh = oh_bool.astype(jnp.float32)
        # Precision.HIGHEST keeps f32 operands on the MXU: the default bf16
        # multiply would corrupt recovered cell ids above ~2^8 (each rank
        # sums exactly one nonzero term, so f32 recovery is exact < 2^24)
        # and erode value sums.
        feats = jnp.stack(
            [vv, ww, (k * boundary).astype(jnp.float32)], axis=-1
        )  # [chunk, block, 3]
        out = jnp.einsum(
            "cbr,cbf->crf", oh, feats, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        # unused ranks carry (0, 0) partials into cell 0 — harmless adds
        return out[..., 0], out[..., 1], jnp.round(out[..., 2]).astype(jnp.int32)

    args = (k3, v3) if ones else (k3, v3, w3)
    sums, counts, cells = jax.lax.map(step, args)  # [nsteps, chunk, ranks]
    flat_cells = cells.reshape(-1)
    grid_sum = jax.ops.segment_sum(sums.reshape(-1), flat_cells, num_cells + 1)[:-1]
    grid_cnt = jax.ops.segment_sum(counts.reshape(-1), flat_cells, num_cells + 1)[:-1]
    if nb * block < n:
        kt = jnp.clip(k_sorted[nb * block:], 0, num_cells).astype(jnp.int32)
        vt = v[nb * block:].astype(jnp.float32)
        wt = (
            jnp.ones_like(vt) if ones
            else w[nb * block:].astype(jnp.float32)
        )
        grid_sum = grid_sum + jax.ops.segment_sum(vt, kt, num_cells + 1)[:-1]
        grid_cnt = grid_cnt + jax.ops.segment_sum(wt, kt, num_cells + 1)[:-1]
    return grid_sum, grid_cnt


@xjit(kernel="block_min_max", static_argnames=("num_cells", "block", "ranks"))
def _block_min_max_xla(k_sorted, v, num_cells, block, ranks, valid=None):
    """min/max companion of _block_sum_count_xla: per-block rank masking +
    a fused masked-reduce over the block axis (XLA fuses the where into the
    reduction — no matmul, no materialized one-hot), then ONE scatter-min/
    max over nb*ranks partials. Measured 360M rows/s vs 57M for the raw
    scatter on a v5e chip (100M rows, 1K cells).

    `valid` (optional bool) excludes rows that keep in-range sorted keys
    (the weights contract of the sum/count path); rows with sentinel keys
    >= num_cells are dropped via the final scatter either way."""
    n = k_sorted.shape[0]
    nb = n // block
    k2 = k_sorted[: nb * block].reshape(nb, block).astype(jnp.int32)
    v2 = v[: nb * block].reshape(nb, block).astype(jnp.float32)
    ones = valid is None
    ok2 = None if ones else valid[: nb * block].reshape(nb, block)
    pad = (-nb) % XLA_CHUNK
    if pad:
        k2 = jnp.concatenate([k2, jnp.full((pad, block), num_cells, jnp.int32)])
        v2 = jnp.concatenate([v2, jnp.zeros((pad, block), jnp.float32)])
        if not ones:
            ok2 = jnp.concatenate([ok2, jnp.zeros((pad, block), bool)])
    nsteps = k2.shape[0] // XLA_CHUNK
    k3 = k2.reshape(nsteps, XLA_CHUNK, block)
    v3 = v2.reshape(nsteps, XLA_CHUNK, block)
    ok3 = None if ones else ok2.reshape(nsteps, XLA_CHUNK, block)

    def step(xs):
        if ones:
            kk, vv = xs
            mask_extra = None
        else:
            kk, vv, mask_extra = xs
        prev = jnp.concatenate(
            [jnp.full((XLA_CHUNK, 1), -1, jnp.int32), kk[:, :-1]], axis=1
        )
        boundary = kk != prev
        rank = jnp.cumsum(boundary.astype(jnp.int32), axis=1) - 1
        in_rank = rank < ranks
        oh = (
            (rank[..., None]
             == jax.lax.broadcasted_iota(jnp.int32, (XLA_CHUNK, block, ranks), 2))
            & in_rank[..., None]
        )
        ohv = oh if mask_extra is None else oh & mask_extra[..., None]
        mn = jnp.min(jnp.where(ohv, vv[..., None], jnp.inf), axis=1)
        mx = jnp.max(jnp.where(ohv, vv[..., None], -jnp.inf), axis=1)
        # rank -> cell id via a max-reduce over the boundary row (exact int,
        # no f32 recovery needed); unused ranks yield -1
        cells = jnp.max(
            jnp.where(oh & boundary[..., None], kk[..., None], -1), axis=1
        )
        return mn, mx, cells

    args = (k3, v3) if ones else (k3, v3, ok3)
    mn, mx, cells = jax.lax.map(step, args)
    flat_cells = jnp.where(cells < 0, num_cells, cells).reshape(-1)
    flat_cells = jnp.minimum(flat_cells, num_cells)
    g_mn = jax.ops.segment_min(mn.reshape(-1), flat_cells, num_cells + 1)[:-1]
    g_mx = jax.ops.segment_max(mx.reshape(-1), flat_cells, num_cells + 1)[:-1]
    if nb * block < n:
        kt = k_sorted[nb * block:]
        vt = v[nb * block:].astype(jnp.float32)
        okt = None if ones else valid[nb * block:]
        idx = jnp.clip(kt, 0, num_cells).astype(jnp.int32)
        if okt is not None:
            idx = jnp.where(okt, idx, num_cells)
        g_mn = jnp.minimum(
            g_mn, jax.ops.segment_min(vt, idx, num_cells + 1)[:-1]
        )
        g_mx = jnp.maximum(
            g_mx, jax.ops.segment_max(vt, idx, num_cells + 1)[:-1]
        )
    return g_mn, g_mx


def _acc_lane(v):
    """A value lane in its accumulation dtype: floats keep their width (the
    engine's precision contract, data.py); integers widen to 64 bits, exact
    and wrap-proof for narrow sums; bool included."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        return v
    if jnp.issubdtype(v.dtype, jnp.unsignedinteger):
        return v.astype(jnp.uint64)
    return v.astype(jnp.int64)


def _lane_limits(dtype):
    """(fill of an empty min cell, fill of an empty max cell) of a lane, as
    jax.ops.segment_min/segment_max fill them."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf, -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max, info.min


def _runs_reduce(k_sorted, num_cells, lanes):
    """Per-cell reductions of MONOTONE cell ids without a scatter: a
    segmented inclusive scan over the sorted runs (Hillis-Steele: log2(n)
    shifted elementwise passes, any dtype), then each cell reads the scan at
    its run's last row, found by a binary search over the ids. `lanes` is a
    list of (values, op, identity); the result is one [num_cells] array a
    lane, `identity` where a cell has no row. Ids outside [0, num_cells)
    are dropped. The order of a cell's additions is a tree's, not the
    rows': sums agree with a sequential scatter to rounding, selections bit
    for bit.

    Why it exists: an accelerator that emulates 64-bit lanes serialises a
    scatter over them (0.154 s for 512 K rows of f64 sums and i64 order
    keys on a TPU v5e, against 0.0027 s for this, PERF.md section 6)."""
    n = k_sorted.shape[0]
    k = k_sorted.astype(jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), k[1:] != k[:-1]])
    vals = [v for v, _op, _ident in lanes]
    d = 1
    while d < n:
        shifted_first = jnp.concatenate([jnp.ones((d,), bool), first[:-d]])
        vals = [
            jnp.where(first, v, op(
                jnp.concatenate([jnp.full((d,), ident, v.dtype), v[:-d]]), v))
            for v, (_v, op, ident) in zip(vals, lanes)
        ]
        first = first | shifted_first
        d *= 2
    cells = jnp.arange(num_cells, dtype=jnp.int32)
    last = jnp.clip(jnp.searchsorted(k, cells, side="right") - 1, 0, n - 1)
    hit = k[last] == cells
    return [
        jnp.where(hit, v[last], jnp.asarray(ident, v.dtype))
        for v, (_v, _op, ident) in zip(vals, lanes)
    ]


@xjit(kernel="runs_sum_count", static_argnames=("num_cells",))
def _runs_sum_count(k_sorted, v, num_cells, w=None):
    vf = _acc_lane(v)
    cw = jnp.ones_like(vf) if w is None else w.astype(vf.dtype)
    s, c = _runs_reduce(k_sorted, num_cells, [(vf, jnp.add, 0), (cw, jnp.add, 0)])
    return s, c


@xjit(kernel="runs_min_max", static_argnames=("num_cells",))
def _runs_min_max(k_sorted, v, num_cells, valid=None):
    hi, lo = _lane_limits(v.dtype)
    v_lo = v if valid is None else jnp.where(valid, v, jnp.asarray(hi, v.dtype))
    v_hi = v if valid is None else jnp.where(valid, v, jnp.asarray(lo, v.dtype))
    mn, mx = _runs_reduce(
        k_sorted, num_cells, [(v_lo, jnp.minimum, hi), (v_hi, jnp.maximum, lo)])
    return mn, mx


def _runs_or_scatter(runs_fn, scatter_fn, k_sorted, *operands):
    """`runs` needs monotone ids (a cell is ONE run); a stream that is not
    (off the sorted contract) takes the scatter, which needs nothing."""
    if isinstance(k_sorted, jax.core.Tracer):
        return jax.lax.cond(jnp.all(k_sorted[1:] >= k_sorted[:-1]),
                            runs_fn, scatter_fn, k_sorted, *operands)
    monotone = bool(np.all(np.diff(np.asarray(k_sorted)) >= 0))
    return (runs_fn if monotone else scatter_fn)(k_sorted, *operands)


def _scatter_min_max(k, v, num_cells, valid=None):
    idx = jnp.clip(k, 0, num_cells).astype(jnp.int32)
    if valid is not None:
        idx = jnp.where(valid, idx, num_cells)
    mn = jax.ops.segment_min(v, idx, num_cells + 1)[:-1]
    mx = jax.ops.segment_max(v, idx, num_cells + 1)[:-1]
    return mn, mx


def sorted_segment_min_max(
    k_sorted,
    v,
    num_cells: int,
    block: int = DEFAULT_BLOCK,
    ranks: int = DEFAULT_RANKS,
    impl: str | None = None,
    valid=None,
):
    """(min, max) per cell for SORTED cell ids. Same adaptive structure as
    sorted_segment_sum_count: block-rank compaction (masked reduces, no
    matmul) with a scatter fallback when any block exceeds the rank budget.
    `impl` takes the registry vocabulary: 'scatter'/'scatter_fused'/'lanes'
    map to the plain scatter (no fused/lane min-max variant exists),
    'runs' is the segmented scan over the sorted runs (any dtype),
    'reduceat' is the host run-boundary lane (concrete inputs only), and
    every block_* name uses the masked-reduce compaction at its block/rank
    config (bf16/scan flags are sum-count-only and are ignored here). Rows
    excluded via `valid` must keep in-range
    sorted keys; rows may also carry sentinel keys >= num_cells (dropped by
    every impl's final scatter/clip) provided sentinel runs stay contiguous
    in the stream. +/-inf fills mark empty cells.

    Non-f32 lanes always take a dtype-preserving implementation (scatter,
    runs, host reduceat): the block path computes in f32, and a lax.cond joining
    f32/f64 branches would be a trace-time type error anyway."""
    ensure(num_cells < _F32_EXACT, f"num_cells {num_cells} exceeds f32-exact range")
    traced = (
        isinstance(k_sorted, jax.core.Tracer) or isinstance(v, jax.core.Tracer)
    )
    impl = impl or _sorted_impl()
    ensure(impl in _SORTED_IMPL_NAMES,
           f"unknown sorted impl {impl!r} ({'|'.join(_SORTED_IMPL_NAMES)})")
    if impl == "auto":
        from horaedb_tpu.ops import agg_registry

        impl = agg_registry.choose_sorted(
            k_sorted.shape[0], num_cells, concrete=not traced
        )
    if jnp.asarray(v).dtype != jnp.float32 and impl not in _ANY_DTYPE_IMPLS:
        impl = "scatter"
    if impl == "reduceat":
        ensure(not traced,
               "sorted impl 'reduceat' is a host lane; it cannot run on "
               "traced values inside jit")
        from horaedb_tpu.ops import agg_registry

        return agg_registry.host_reduceat_min_max(
            k_sorted, v, num_cells, valid=valid
        )
    if impl == "runs":
        return _runs_or_scatter(
            lambda k, vv, ok: _runs_min_max(k, vv, num_cells, valid=ok),
            lambda k, vv, ok: _scatter_min_max(k, vv, num_cells, valid=ok),
            k_sorted, v, valid)
    if impl in ("scatter", "scatter_fused", "lanes"):
        return _scatter_min_max(k_sorted, v, num_cells, valid=valid)
    if impl != "block":
        block, ranks = _BLOCK_VARIANTS[impl][:2]

    def fast(k, vv, ok=None):
        return _block_min_max_xla(k, vv, num_cells, block, ranks, valid=ok)

    if isinstance(k_sorted, jax.core.Tracer):
        if valid is None:
            return jax.lax.cond(
                _distinct_max(k_sorted, block) > ranks,
                lambda k, vv: _scatter_min_max(k, vv, num_cells),
                lambda k, vv: fast(k, vv),
                k_sorted, v,
            )
        return jax.lax.cond(
            _distinct_max(k_sorted, block) > ranks,
            lambda k, vv, ok: _scatter_min_max(k, vv, num_cells, valid=ok),
            fast,
            k_sorted, v, valid,
        )
    if distinct_cells_per_block_max(k_sorted, block) > ranks:
        return _scatter_min_max(k_sorted, v, num_cells, valid=valid)
    return fast(k_sorted, v, valid)


def _scatter_sum_count(k_sorted, v, num_cells, w=None):
    k = jnp.clip(k_sorted, 0, num_cells).astype(jnp.int32)
    # dtype-preserving for floats (f64 stays f64 — the engine's precision
    # contract, data.py; f32 stays the TPU trade-off). Integer inputs widen
    # to 64-bit accumulation: exact (the reason ints route here instead of
    # the f32 block compaction) and wrap-proof for narrow int sums.
    vf = _acc_lane(v)
    cw = jnp.ones_like(vf) if w is None else w.astype(vf.dtype)
    s = jax.ops.segment_sum(vf, k, num_cells + 1)[:-1]
    c = jax.ops.segment_sum(cw, k, num_cells + 1)[:-1]
    return s, c


@xjit(kernel="scatter_fused", static_argnames=("num_cells",))
def _scatter_fused_sum_count(k_sorted, v, num_cells, w=None):
    """ONE stacked (value, weight) segment-sum with indices_are_sorted=True
    instead of two scalar scatters — the sorted contract lets XLA skip the
    scatter's conflict handling, and stacking halves the scatter passes
    (the TPU tiling penalty that rules stacking out in
    aggregate.masked_segment_stats does not apply to the CPU backend this
    lane wins on; on accelerators it simply loses the calibration A/B).
    f32 accumulation — the dispatcher routes non-f32 inputs to the
    dtype-preserving scatter before this is reachable."""
    k = jnp.clip(k_sorted, 0, num_cells).astype(jnp.int32)
    vf = v.astype(jnp.float32)
    cw = jnp.ones_like(vf) if w is None else w.astype(jnp.float32)
    feats = jnp.stack([vf, cw], axis=-1)  # [n, 2]
    out = jax.ops.segment_sum(
        feats, k, num_cells + 1, indices_are_sorted=True
    )[:-1]
    return out[:, 0], out[:, 1]


# registry block-compaction variants: impl name -> (block, ranks,
# bf16_onehot, scan_prologue). The vocabulary lives in
# ops/agg_registry.py; execution stays here.
_BLOCK_VARIANTS = {
    "block": (DEFAULT_BLOCK, DEFAULT_RANKS, False, False),
    "block_wide": (2048, 256, False, False),
    "block_r32": (DEFAULT_BLOCK, 32, False, False),
    "block_bf16": (DEFAULT_BLOCK, DEFAULT_RANKS, True, False),
    "block_scan": (DEFAULT_BLOCK, DEFAULT_RANKS, False, True),
}

# the implementations that keep the value lane's own dtype (f64, integers);
# every other one accumulates f32
_ANY_DTYPE_IMPLS = ("scatter", "runs", "reduceat")

_SORTED_IMPL_NAMES = (
    "auto", "scatter", "scatter_fused", "lanes", "reduceat", "runs",
    *_BLOCK_VARIANTS,
)


def _unsorted_impl() -> str:
    """Strategy override for UNSORTED input: HORAEDB_UNSORTED_IMPL in
    {auto, scatter, sort, bincount}. auto = the calibrated registry choice
    for concrete inputs; under jit, device-sort + block compaction on
    accelerators (when the grid is f32-exact), plain scatter on CPU."""
    import os

    return os.environ.get("HORAEDB_UNSORTED_IMPL", "auto")


def unsorted_strategy(n: int, num_cells: int, dtype, impl: str | None = None) -> str:
    """Resolve the unsorted-reduction strategy to 'sort' or 'scatter'.

    auto gates: density (below ~8 rows/cell the post-sort stream fails the
    distinct-per-block check — block=512/ranks=64 needs >= block/ranks rows
    per cell — and the compaction would fall back to scatter anyway, making
    the device sort pure waste), backend (CPU scatter is not the
    bottleneck), f32-exact grid size, and dtype (wider floats keep the
    dtype-preserving scatter; the block compaction accumulates f32). All
    static at trace time, so the choice compiles away."""
    impl = impl or _unsorted_impl()
    if impl != "auto":
        return impl
    return (
        "sort"
        if n >= 8 * num_cells
        and jax.default_backend() != "cpu"
        and num_cells < _F32_EXACT
        and dtype == jnp.float32
        else "scatter"
    )


def segment_sum_count(k, v, num_cells: int, impl: str | None = None, weights=None):
    """(sum, count) per cell for UNSORTED cell ids (invalid rows must carry
    id >= num_cells; their values must be pre-masked to 0). `weights`
    (optional) is each row's count contribution — pass the predicate mask
    when invalid rows keep in-range cell ids instead of sentinels.

    'sort' device-sorts the rows (lax.sort runs ~4 ns/row on v5e — far
    cheaper than a 9 ns/row scatter it replaces TWO of) and reduces with the
    sorted block compaction: measured 2.1x the raw double-scatter on a v5e
    chip (64M rows, 2.88M cells). 'bincount' is the host hash-grouping lane
    (concrete inputs only). 'auto' on concrete inputs asks the calibrated
    registry (ops/agg_registry.py); under jit it resolves by the static
    density/backend heuristic at trace time and jitted callers bake the
    choice into the executable."""
    traced = isinstance(k, jax.core.Tracer) or isinstance(v, jax.core.Tracer)
    resolved = impl or _unsorted_impl()
    if resolved == "auto" and not traced:
        from horaedb_tpu.ops import agg_registry

        resolved = agg_registry.choose_unsorted(
            k.shape[0], num_cells, concrete=True
        )
    impl = unsorted_strategy(
        k.shape[0], num_cells, jnp.asarray(v).dtype, resolved
    )
    if impl == "bincount":
        ensure(not traced,
               "unsorted impl 'bincount' is a host lane; it cannot run on "
               "traced values inside jit")
        from horaedb_tpu.ops import agg_registry

        return agg_registry.host_bincount_sum_count(
            k, v, num_cells, weights=weights
        )
    if impl == "scatter":
        return _scatter_sum_count(k, v, num_cells, w=weights)
    ensure(impl == "sort", f"unknown unsorted impl {impl!r}")
    ensure(num_cells < _F32_EXACT, f"num_cells {num_cells} exceeds f32-exact range")
    kc = jnp.clip(k, 0, num_cells).astype(jnp.int32)
    if weights is None:
        k2, v2 = jax.lax.sort((kc, v), num_keys=1)
        return sorted_segment_sum_count(k2, v2, num_cells, impl="block")
    k2, v2, w2 = jax.lax.sort((kc, v, weights), num_keys=1)
    return sorted_segment_sum_count(k2, v2, num_cells, impl="block", weights=w2)


def _sorted_impl() -> str:
    """Strategy override: HORAEDB_SORTED_IMPL naming any registry impl
    (ops/agg_registry.py; `HORAEDB_AGG_IMPL` takes precedence inside the
    registry's dispatcher). auto = the calibrated per-platform choice."""
    import os

    return os.environ.get("HORAEDB_SORTED_IMPL", "auto")


def sorted_segment_sum_count(
    k_sorted,
    v,
    num_cells: int,
    block: int = DEFAULT_BLOCK,
    ranks: int = DEFAULT_RANKS,
    impl: str | None = None,
    weights=None,
):
    """(sum, count) per cell for SORTED cell ids (invalid rows must carry
    id >= num_cells). Adaptive: falls back to plain segment_sum when any
    block holds more than `ranks` distinct cells (the rank compaction would
    drop rows). Trace-safe: under jit/shard_map the adaptive check becomes
    a lax.cond between the compacted and scatter paths.

    `weights` (optional) is each row's count contribution; pass the
    predicate mask (0/1) instead of sentinel keys so masked rows keep their
    sorted cell id and the stream stays compactable (values must then be
    pre-masked to 0).

    `impl` overrides the strategy explicitly (A/B harnesses) with any
    registry name (ops/agg_registry.py): scatter | scatter_fused | lanes |
    runs (segmented scan over the sorted runs, any dtype, no scatter) |
    reduceat (host, concrete inputs only) | block | block_wide | block_r32
    | block_bf16 | block_scan. None reads HORAEDB_SORTED_IMPL at trace
    time; 'auto' asks the calibrated registry dispatcher — note that
    jitted callers bake the strategy into their compiled executable, so
    flipping the env var mid-process does not retrace existing caches."""
    ensure(num_cells < _F32_EXACT, f"num_cells {num_cells} exceeds f32-exact range")
    traced = (
        isinstance(k_sorted, jax.core.Tracer) or isinstance(v, jax.core.Tracer)
    )
    impl = impl or _sorted_impl()
    # fail loudly on removed/unknown strategy names (e.g. the deleted
    # 'pallas') rather than silently measuring a different path
    ensure(impl in _SORTED_IMPL_NAMES,
           f"unknown sorted impl {impl!r} ({'|'.join(_SORTED_IMPL_NAMES)})")
    if impl == "auto":
        from horaedb_tpu.ops import agg_registry

        impl = agg_registry.choose_sorted(
            k_sorted.shape[0], num_cells, concrete=not traced
        )
    if jnp.asarray(v).dtype != jnp.float32 and impl not in _ANY_DTYPE_IMPLS:
        # non-f32 inputs take a dtype-preserving route: the compactions
        # accumulate f32, which loses exactness for integer sums above
        # 2^24 (scatter, runs and the host reduceat widen ints to 64-bit
        # instead — exact), and a cond joining f32/f64 branches cannot trace
        impl = "scatter"
    if impl == "reduceat":
        ensure(not traced,
               "sorted impl 'reduceat' is a host lane; it cannot run on "
               "traced values inside jit")
        from horaedb_tpu.ops import agg_registry

        return agg_registry.host_reduceat_sum_count(
            k_sorted, v, num_cells, weights=weights
        )
    if impl == "scatter":
        return _scatter_sum_count(k_sorted, v, num_cells, w=weights)
    if impl == "runs":
        return _runs_or_scatter(
            lambda k, vv, ww: _runs_sum_count(k, vv, num_cells, w=ww),
            lambda k, vv, ww: _scatter_sum_count(k, vv, num_cells, w=ww),
            k_sorted, v, weights)
    if impl == "scatter_fused":
        return _scatter_fused_sum_count(k_sorted, v, num_cells, w=weights)
    if impl == "lanes":
        from horaedb_tpu.ops.aggregate import lane_segment_sum_count

        return lane_segment_sum_count(k_sorted, v, num_cells, w=weights)
    if impl != "block":
        block, ranks, bf16_onehot, scan_prologue = _BLOCK_VARIANTS[impl]
    else:
        bf16_onehot = scan_prologue = False

    def fast(k, vv, ww=None):
        return _block_sum_count_xla(
            k, vv, num_cells, block, ranks, w=ww,
            bf16_onehot=bf16_onehot, scan_prologue=scan_prologue,
        )

    if isinstance(k_sorted, jax.core.Tracer):
        # inside jit: runtime branch (int() on the pre-check would raise
        # ConcretizationTypeError; both branches compile, one executes)
        if weights is None:
            return jax.lax.cond(
                _distinct_max(k_sorted, block) > ranks,
                lambda k, vv: _scatter_sum_count(k, vv, num_cells),
                lambda k, vv: fast(k, vv),
                k_sorted, v,
            )
        return jax.lax.cond(
            _distinct_max(k_sorted, block) > ranks,
            lambda k, vv, ww: _scatter_sum_count(k, vv, num_cells, w=ww),
            fast,
            k_sorted, v, weights,
        )
    if distinct_cells_per_block_max(k_sorted, block) > ranks:
        return _scatter_sum_count(k_sorted, v, num_cells, w=weights)
    return fast(k_sorted, v, weights)
