"""Self-calibrating aggregation kernel registry (ROOFLINE §1's queued
experiments, made dispatchable).

The segment-reduction workhorse behind downsample/group-by used to be a
hard-coded impl per platform (`HORAEDB_SORTED_IMPL` defaulting to scatter
on CPU, the block compaction on accelerators). The measured record says
that is wrong twice over:

- the sort-vs-hash-vs-scatter winner flips with group density AND with the
  box (arXiv:2411.13245): on one CI container XLA's scatter runs the bench
  shape at 35 M rows/s and beats every host lane; on another the same
  scatter manages 4.7 M while a host `np.add.reduceat` over run boundaries
  does 24.5 M — a 5× swing in OPPOSITE directions for identical code;
- ROOFLINE §1 queues three never-built block-compaction variants
  (ranks=32, bf16 one-hot, associative_scan prologue) whose value can only
  be decided by measurement on the hardware at hand.

So: every interchangeable (sum, count) strategy registers here with its
capability envelope (traceable under jit? host-only? platform limits?),
and `choose_sorted`/`choose_unsorted` pick by a micro-A/B run once per
(platform, density class) and persisted under the data root — the
aggregate-path analog of storage/read.py's `_HostCalib`/`_LinkProfile`
measured-not-assumed planning. The choice is exported as
`horaedb_agg_impl_total{impl=...}` and pinnable via `HORAEDB_AGG_IMPL`.

Execution stays in ops/blockagg.py (device lanes) and this module (host
lanes); blockagg's `sorted_segment_sum_count(impl=...)` accepts every name
registered here, so the registry is metadata + measurement + choice, not a
parallel code path.

The host lanes are the one place in the engine allowed to call
`np.add.reduceat`/`np.minimum.reduceat` on the aggregate path — jaxlint
J006 rejects new ad-hoc host reductions and one-hot materializations
outside the registry modules (docs/static-analysis.md).
"""

from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from dataclasses import dataclass

import numpy as np

from horaedb_tpu.common.calib_cache import CalibCache
from horaedb_tpu.common.error import ensure
from horaedb_tpu.server.metrics import GLOBAL_METRICS

AGG_IMPL_TOTAL = GLOBAL_METRICS.counter(
    "horaedb_agg_impl_total",
    help="Aggregation kernel the calibrated dispatcher selected, per "
         "dispatch (trace-time dispatches count once per compile).",
    labelnames=("impl",),
)
# pre-register the universal fallback so the family renders on /metrics
# from boot (same pattern as horaedb_scan_path_total)
AGG_IMPL_TOTAL.labels("scatter")

# bf16 one-hot value-lane error budget per grid cell, vs the f64 oracle:
# |err| <= BF16_L1_BUDGET * sum(|v|) + BF16_ATOL. Inputs round to bf16
# (rel ~2^-9 each), so the cell-sum error is bounded by the cell's L1 mass,
# not its (possibly cancelling) sum. Counts stay exact: 0/1 weights and
# one-hot entries are exactly representable in bf16 and partials accumulate
# f32. The calibrator verifies the budget against a live f64 oracle before
# ever letting the lane win (and records the rejection if it fails).
BF16_L1_BUDGET = 2.0 ** -7
BF16_ATOL = 1e-3

CALIB_VERSION = 2


@dataclass(frozen=True)
class AggImpl:
    """One registered (sum, count) strategy.

    `traceable`: usable on jax tracers (inside jit/shard_map). Host lanes
    are not — they need concrete arrays.
    `platforms`: backends the impl is worth measuring on (() = all).
    """

    name: str
    kind: str  # "device" | "host"
    traceable: bool
    platforms: tuple[str, ...]
    description: str


SORTED_IMPLS: dict[str, AggImpl] = {
    impl.name: impl
    for impl in (
        AggImpl("scatter", "device", True, (),
                "two plain segment-sum scatters (dtype-preserving)"),
        AggImpl("scatter_fused", "device", True, (),
                "ONE stacked (value, weight) scatter with "
                "indices_are_sorted — halves the scatter passes"),
        AggImpl("block", "device", True, (),
                "block-rank one-hot compaction, block=512 ranks=64"),
        AggImpl("block_wide", "device", True, (),
                "block-rank compaction, block=2048 ranks=256 (the r02 "
                "loser, kept measurable)"),
        AggImpl("block_r32", "device", True, (),
                "ROOFLINE §1 exp 1: ranks=32 halves one-hot traffic; "
                "density-triggered scatter fallback covers sparse blocks"),
        AggImpl("block_bf16", "device", True, (),
                "ROOFLINE §1 exp 2: bf16 one-hot for the value/count "
                "features (ids recovered exactly via boundary max-reduce); "
                "gated by the f64-oracle error budget"),
        AggImpl("block_scan", "device", True, (),
                "ROOFLINE §1 exp 3: boundary-segmented associative_scan "
                "rank prologue instead of cumsum"),
        AggImpl("lanes", "device", True, (),
                "lane-parallel vmap scatter over partial grids"),
        AggImpl("runs", "device", True, (),
                "segmented scan over the sorted runs and a binary search "
                "per cell: no scatter, the value lane's own dtype — what "
                "64-bit lanes take on an accelerator, whose scatter over "
                "them serialises"),
        AggImpl("reduceat", "host", False, ("cpu",),
                "host run-boundary lane: np.add.reduceat over "
                "searchsorted/diff boundaries — near memory-bandwidth "
                "bound on sorted CPU input"),
    )
}

UNSORTED_IMPLS: dict[str, AggImpl] = {
    impl.name: impl
    for impl in (
        AggImpl("scatter", "device", True, (),
                "two plain segment-sum scatters"),
        AggImpl("sort", "device", True, (),
                "device sort + block compaction"),
        AggImpl("bincount", "host", False, ("cpu",),
                "host np.bincount pair (hash-style grouping)"),
    )
}


def sorted_impl_names(platform: str | None = None,
                      concrete: bool = True) -> list[str]:
    """Registered sorted-lane names eligible on `platform` (None = all)."""
    return [
        i.name for i in SORTED_IMPLS.values()
        if (not i.platforms or platform is None or platform in i.platforms)
        and (concrete or i.traceable)
    ]


def unsorted_impl_names(platform: str | None = None,
                        concrete: bool = True) -> list[str]:
    return [
        i.name for i in UNSORTED_IMPLS.values()
        if (not i.platforms or platform is None or platform in i.platforms)
        and (concrete or i.traceable)
    ]


def is_host_impl(name: str) -> bool:
    impl = SORTED_IMPLS.get(name) or UNSORTED_IMPLS.get(name)
    return impl is not None and impl.kind == "host"


# ---------------------------------------------------------------------------
# host lanes (the only sanctioned np.*.reduceat on the aggregate path)
# ---------------------------------------------------------------------------


def _acc_dtype(v: np.ndarray) -> np.dtype:
    """Accumulation dtype mirroring blockagg._scatter_sum_count: floats keep
    their width (the engine's precision contract), integers widen to 64-bit
    exact accumulation."""
    if np.issubdtype(v.dtype, np.floating):
        return v.dtype
    if np.issubdtype(v.dtype, np.unsignedinteger):
        return np.dtype(np.uint64)
    return np.dtype(np.int64)


def _run_starts(k: np.ndarray) -> np.ndarray:
    b = np.flatnonzero(k[1:] != k[:-1])
    starts = np.empty(len(b) + 1, np.int64)
    starts[0] = 0
    starts[1:] = b + 1
    return starts


def host_reduceat_sum_count(k_sorted, v, num_cells: int, weights=None):
    """(sum, count) per cell over SORTED host arrays via run-boundary
    `np.add.reduceat` — no per-row scatter at all; the only scatter left is
    one unique-index assignment over the runs. Contract matches
    blockagg.sorted_segment_sum_count: invalid rows either carry sentinel
    ids >= num_cells (contiguous runs, dropped here by the cell filter) or
    ride the `weights` column with values pre-masked to 0."""
    k = np.asarray(k_sorted)
    v = np.asarray(v)
    acc = _acc_dtype(v)
    gs = np.zeros(num_cells, acc)
    gc = np.zeros(num_cells, acc)
    n = k.shape[0]
    if n == 0:
        return gs, gc
    starts = _run_starts(k)
    sums = np.add.reduceat(v.astype(acc, copy=False), starts)
    if weights is None:
        ends = np.empty(len(starts), np.int64)
        ends[:-1] = starts[1:]
        ends[-1] = n
        cnts = (ends - starts).astype(acc)
    else:
        cnts = np.add.reduceat(
            np.asarray(weights).astype(acc, copy=False), starts
        )
    cells = k[starts]
    ok = (cells >= 0) & (cells < num_cells)
    cok, sok, nok = cells[ok], sums[ok], cnts[ok]
    if len(cok) and not np.all(cok[1:] >= cok[:-1]):
        # non-monotone key stream (e.g. sid clipping folded two series
        # onto one): a cell can span several runs, so ACCUMULATE — plain
        # assignment would keep only the last run (silent data loss).
        # ufunc.at is slower, but this is the off-contract slow path.
        np.add.at(gs, cok, sok)
        np.add.at(gc, cok, nok)
    else:
        # monotone + consecutive-distinct runs => unique cells: assign
        gs[cok] = sok
        gc[cok] = nok
    return gs, gc


def host_reduceat_min_max(k_sorted, v, num_cells: int, valid=None):
    """(min, max) per cell over SORTED host arrays via
    np.minimum/np.maximum.reduceat; +/-inf fills mark empty cells, matching
    blockagg.sorted_segment_min_max."""
    k = np.asarray(k_sorted)
    v = np.asarray(v)
    vd = v.dtype if np.issubdtype(v.dtype, np.floating) else np.dtype(np.float64)
    gmn = np.full(num_cells, np.inf, vd)
    gmx = np.full(num_cells, -np.inf, vd)
    n = k.shape[0]
    if n == 0:
        return gmn, gmx
    if valid is not None:
        valid = np.asarray(valid)
        v_lo = np.where(valid, v, vd.type(np.inf))
        v_hi = np.where(valid, v, vd.type(-np.inf))
    else:
        v_lo = v_hi = v.astype(vd, copy=False)
    starts = _run_starts(k)
    mns = np.minimum.reduceat(v_lo, starts)
    mxs = np.maximum.reduceat(v_hi, starts)
    cells = k[starts]
    ok = (cells >= 0) & (cells < num_cells)
    cok = cells[ok]
    if len(cok) and not np.all(cok[1:] >= cok[:-1]):
        # non-monotone stream: a cell spans several runs — reduce, don't
        # assign (mirrors host_reduceat_sum_count's accumulate fallback)
        np.minimum.at(gmn, cok, mns[ok])
        np.maximum.at(gmx, cok, mxs[ok])
    else:
        gmn[cok] = mns[ok]
        gmx[cok] = mxs[ok]
    return gmn, gmx


def host_bincount_sum_count(k, v, num_cells: int, weights=None):
    """(sum, count) per cell for UNSORTED host arrays via np.bincount —
    the hash-grouping analog (arXiv:2411.13245's other contender). Sentinel
    ids >= num_cells drop via the minlength+slice trick."""
    k = np.asarray(k)
    v = np.asarray(v)
    acc = _acc_dtype(v)
    if k.shape[0] == 0:
        return np.zeros(num_cells, acc), np.zeros(num_cells, acc)
    kc = np.clip(k, 0, num_cells).astype(np.int64, copy=False)
    gs = np.bincount(kc, weights=v, minlength=num_cells + 1)[:-1]
    if weights is None:
        gc = np.bincount(kc, minlength=num_cells + 1)[:-1].astype(acc)
    else:
        gc = np.bincount(
            kc, weights=np.asarray(weights), minlength=num_cells + 1
        )[:-1]
    # bincount with weights accumulates f64; fold back to the contract dtype
    return gs.astype(acc, copy=False), gc.astype(acc, copy=False)


# host sum/count lanes by registered impl name: the host_downsample_*
# pipelines (and bench A/B) dispatch through these, so a NEW host impl
# must register here too or every caller fails loudly with a KeyError
# instead of silently measuring the wrong lane
HOST_SORTED_FNS = {"reduceat": host_reduceat_sum_count}
HOST_UNSORTED_FNS = {"bincount": host_bincount_sum_count}


def host_downsample_sorted(
    ts,
    series_idx,
    values,
    t0,
    bucket_ms,
    num_series: int,
    num_buckets: int,
    with_minmax: bool = True,
    valid=None,
    impl: str = "reduceat",
) -> dict:
    """Full host-lane downsample over rows SORTED by (series, ts): the
    numpy mirror of aggregate.downsample_sorted for concrete CPU inputs
    when the dispatcher picks a host lane. Accumulates in the value
    dtype (f64 in the engine's CPU precision contract). `impl` names the
    registered host sum/count lane — an unregistered name KeyErrors
    loudly rather than silently timing/running a different lane."""
    ts = np.asarray(ts)
    sid = np.asarray(series_idx)
    v = np.asarray(values)
    # scalar coercion: jnp scalars mixed into numpy arithmetic would pull
    # the whole pipeline back onto the jax dispatch path
    t0 = int(np.asarray(t0))
    bucket_ms = int(np.asarray(bucket_ms))
    bucket = ((ts.astype(np.int64) - t0) // bucket_ms).astype(np.int64)
    ok = (
        (bucket >= 0) & (bucket < num_buckets)
        & (sid >= 0) & (sid < num_series)
    )
    if valid is not None:
        ok = ok & np.asarray(valid)
    safe = (
        np.clip(sid.astype(np.int64), 0, num_series - 1) * num_buckets
        + np.clip(bucket, 0, num_buckets - 1)
    )
    num_cells = num_series * num_buckets
    all_ok = bool(ok.all())
    acc = _acc_dtype(v)
    vm = v.astype(acc, copy=False) if all_ok else \
        np.where(ok, v, v.dtype.type(0)).astype(acc, copy=False)
    s, c = HOST_SORTED_FNS[impl](
        safe, vm, num_cells,
        weights=None if all_ok else ok.astype(acc),
    )
    shape = (num_series, num_buckets)
    with np.errstate(invalid="ignore", divide="ignore"):
        out = {
            "sum": s.reshape(shape),
            "count": c.reshape(shape),
            "mean": (s / c).reshape(shape),
        }
    if with_minmax:
        mn, mx = host_reduceat_min_max(
            safe, v, num_cells, valid=None if all_ok else ok
        )
        out["min"] = mn.reshape(shape)
        out["max"] = mx.reshape(shape)
    return out


def host_downsample_unsorted(
    ts,
    series_idx,
    values,
    t0,
    bucket_ms,
    num_series: int,
    num_buckets: int,
    with_minmax: bool = True,
    valid=None,
    impl: str = "bincount",
) -> dict:
    """Host-lane downsample for UNSORTED rows (the hash-grouping
    contender in bench A/B); `impl` names the registered host unsorted
    sum/count lane (KeyError on unregistered names). min/max, when
    requested, use np.minimum.at / np.maximum.at — correct but
    scatter-speed; the lane exists for the sum/count shapes where
    bincount wins."""
    ts = np.asarray(ts)
    sid = np.asarray(series_idx)
    v = np.asarray(values)
    t0 = int(np.asarray(t0))
    bucket_ms = int(np.asarray(bucket_ms))
    bucket = ((ts.astype(np.int64) - t0) // bucket_ms).astype(np.int64)
    ok = (
        (bucket >= 0) & (bucket < num_buckets)
        & (sid >= 0) & (sid < num_series)
    )
    if valid is not None:
        ok = ok & np.asarray(valid)
    safe = (
        np.clip(sid.astype(np.int64), 0, num_series - 1) * num_buckets
        + np.clip(bucket, 0, num_buckets - 1)
    )
    num_cells = num_series * num_buckets
    acc = _acc_dtype(v)
    all_ok = bool(ok.all())
    vm = v.astype(acc, copy=False) if all_ok else \
        np.where(ok, v, v.dtype.type(0)).astype(acc, copy=False)
    s, c = HOST_UNSORTED_FNS[impl](
        safe, vm, num_cells, weights=None if all_ok else ok.astype(acc)
    )
    shape = (num_series, num_buckets)
    with np.errstate(invalid="ignore", divide="ignore"):
        out = {
            "sum": s.reshape(shape),
            "count": c.reshape(shape),
            "mean": (s / c).reshape(shape),
        }
    if with_minmax:
        vd = v.dtype if np.issubdtype(v.dtype, np.floating) else np.dtype(np.float64)
        mn = np.full(num_cells, np.inf, vd)
        mx = np.full(num_cells, -np.inf, vd)
        kk = safe[ok]
        np.minimum.at(mn, kk, v[ok])
        np.maximum.at(mx, kk, v[ok])
        out["min"] = mn.reshape(shape)
        out["max"] = mx.reshape(shape)
    return out


# ---------------------------------------------------------------------------
# execution shims (one entry point per registry, used by the calibrator
# and by bench A/B — production callers go through blockagg's dispatcher)
# ---------------------------------------------------------------------------


def run_sorted(name: str, k_sorted, v, num_cells: int, weights=None):
    """Execute one registered sorted impl on concrete or traced inputs."""
    ensure(name in SORTED_IMPLS, f"unknown sorted agg impl {name!r}")
    if name == "reduceat":
        return host_reduceat_sum_count(k_sorted, v, num_cells, weights=weights)
    from horaedb_tpu.ops.blockagg import sorted_segment_sum_count

    return sorted_segment_sum_count(
        k_sorted, v, num_cells, impl=name, weights=weights
    )


def run_unsorted(name: str, k, v, num_cells: int, weights=None):
    ensure(name in UNSORTED_IMPLS, f"unknown unsorted agg impl {name!r}")
    if name == "bincount":
        return host_bincount_sum_count(k, v, num_cells, weights=weights)
    from horaedb_tpu.ops.blockagg import segment_sum_count

    return segment_sum_count(k, v, num_cells, impl=name, weights=weights)


# ---------------------------------------------------------------------------
# calibration cache
# ---------------------------------------------------------------------------

# density regimes calibrate separately: the block compactions need >=
# block/ranks rows per cell to engage at all, and reduceat's per-run cost
# amortizes with density — one winner does not serve both regimes
DENSE_ROWS_PER_CELL = 8

# last dispatcher decision, context-local first (accurate for code that
# dispatches and attributes in the same coroutine/thread — read.py's
# scanstats note), process-global fallback for observers in OTHER contexts
# (promql's span attr: best-effort, may mislabel under concurrent scans)
_last_choice_ctx: "contextvars.ContextVar[str | None]" = \
    contextvars.ContextVar("horaedb_agg_last_choice", default=None)
_last_choice_global: str = "scatter"

# persistence shared with ops/decode.py (common/calib_cache.py); the
# inventory fields self-invalidate the file when the impl set changes
_calib_cache = CalibCache(
    env_var="HORAEDB_AGG_CACHE",
    filename="agg_calib.json",
    version=CALIB_VERSION,
    tmp_prefix=".agg_calib.",
    inventory=lambda: {
        "sorted_impls": sorted(SORTED_IMPLS),
        "unsorted_impls": sorted(UNSORTED_IMPLS),
    },
)


def configure_cache_dir(path: str) -> None:
    """Point the calibration cache under the engine's data root (called by
    storage bring-up); HORAEDB_AGG_CACHE overrides with a full file path."""
    _calib_cache.configure_dir(path)


def cache_path() -> str:
    return _calib_cache.path()


def reset_cache(memory_only: bool = False) -> None:
    """Drop the in-memory view (tests); optionally leave the file."""
    _calib_cache.reset(memory_only)


_load_cache = _calib_cache.load
_store_entry = _calib_cache.store_entry
_calibrate_lock = threading.Lock()


def density_class(n: int, num_cells: int) -> str:
    return "dense" if n >= DENSE_ROWS_PER_CELL * max(1, num_cells) else "sparse"


def _calib_shape(klass: str) -> tuple[int, int]:
    """Micro-A/B problem size: big enough that per-dispatch overhead does
    not decide the winner, small enough to stay well under a second per
    impl pass on any sane box. Env-tunable for tests."""
    try:
        n = int(os.environ.get("HORAEDB_AGG_CALIB_N", str(1 << 18)))
    except ValueError:
        n = 1 << 18
    cells = max(1, n // 16) if klass == "dense" else 2 * n
    return n, cells


def _time_impl(fn, iters: int = 2) -> float:
    """Seconds per pass, forcing completion via np.asarray (host arrays
    pass through free; device arrays sync)."""
    out = fn()
    np.asarray(out[0]), np.asarray(out[1])  # warm / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    np.asarray(out[0]), np.asarray(out[1])
    return (time.perf_counter() - t0) / iters


def _bf16_within_budget(s, oracle_sum, l1) -> bool:
    err = np.abs(np.asarray(s, dtype=np.float64) - oracle_sum)
    return bool(np.all(err <= BF16_L1_BUDGET * l1 + BF16_ATOL))


def _calibrate(kind: str, platform: str, klass: str) -> dict:
    """Measure every eligible impl on a synthetic stream of the density
    class and return {impl, device_impl, ab, ...} — persisted by caller."""
    n, cells = _calib_shape(klass)
    rng = np.random.default_rng(0xA66)
    k = np.sort(rng.integers(0, cells, n)).astype(np.int32)
    v = rng.normal(size=n).astype(np.float32)
    if kind == "unsorted":
        k = rng.permutation(k).astype(np.int32)
        names = unsorted_impl_names(platform)
        runner, impls = run_unsorted, UNSORTED_IMPLS
    else:
        names = sorted_impl_names(platform)
        runner, impls = run_sorted, SORTED_IMPLS
    oracle_sum = np.bincount(k, weights=v.astype(np.float64), minlength=cells)
    l1 = np.bincount(k, weights=np.abs(v.astype(np.float64)), minlength=cells)
    ab: dict[str, float] = {}
    rejected: dict[str, str] = {}
    for name in names:
        try:
            s, _c = runner(name, k, v, cells)
            if not _bf16_within_budget(s, oracle_sum, l1):
                # every lane is held to the bf16 budget here (it is the
                # loosest bound we accept); in practice only block_bf16
                # comes near it
                rejected[name] = "exceeds f64-oracle error budget"
                continue
            secs = _time_impl(lambda name=name: runner(name, k, v, cells))
            ab[name] = round(n / max(secs, 1e-9))
        except Exception as e:  # noqa: BLE001 — an impl that cannot run
            # on this backend loses by forfeit, it must not kill dispatch
            rejected[name] = f"{type(e).__name__}: {e}"[:200]
    if not ab:
        ab = {"scatter": 0.0}
    best = max(ab, key=ab.get)
    device_ab = {x: r for x, r in ab.items() if impls[x].traceable}
    entry = {
        "impl": best,
        "device_impl": max(device_ab, key=device_ab.get) if device_ab else "scatter",
        "ab": ab,
        "rejected": rejected,
        "n": n,
        "num_cells": cells,
        "calibrated_unix": int(time.time()),
    }
    return entry


def calibration_entry(kind: str, n: int, num_cells: int,
                      platform: str | None = None) -> tuple[dict, str]:
    """(entry, source) for the (platform, kind, density) regime; source is
    'cache' (warm) or 'calibrated' (cold micro-A/B just ran)."""
    if platform is None:
        import jax

        platform = jax.devices()[0].platform
    klass = density_class(n, num_cells)
    key = f"{platform}/{kind}/{klass}"

    def cached() -> "dict | None":
        return (_load_cache().get("entries") or {}).get(key)

    entry = cached()
    if entry is not None:
        return entry, "cache"
    # folds run on worker threads, several at once: the first to meet a
    # regime measures it alone (a micro-A/B timed beside seven others
    # measures them), the others wait and read its entry
    with _calibrate_lock:
        entry = cached()
        if entry is not None:
            return entry, "cache"
        entry = _calibrate(kind, platform, klass)
        _store_entry(key, entry)
    return entry, "calibrated"


def _record(name: str) -> str:
    global _last_choice_global
    _last_choice_ctx.set(name)
    _last_choice_global = name
    AGG_IMPL_TOTAL.labels(name).inc()
    return name


def record_choice(name: str) -> str:
    """A lane the caller chose for a reason of its own (aggregate.py: sums
    an accelerator's f64 cannot carry take the host lane), counted and
    attributed like a dispatcher decision."""
    return _record(name)


def choose_sorted(n: int, num_cells: int, *, concrete: bool = True,
                  platform: str | None = None) -> str:
    """Resolve the sorted-lane impl: HORAEDB_AGG_IMPL pin > legacy
    HORAEDB_SORTED_IMPL pin > calibration cache (micro-A/B on first use).
    `concrete=False` (tracer inputs) restricts to traceable impls."""
    pinned = os.environ.get("HORAEDB_AGG_IMPL")
    if pinned:
        ensure(pinned in SORTED_IMPLS,
               f"HORAEDB_AGG_IMPL={pinned!r} is not one of "
               f"{sorted(SORTED_IMPLS)}")
        if concrete or SORTED_IMPLS[pinned].traceable:
            return _record(pinned)
    legacy = os.environ.get("HORAEDB_SORTED_IMPL", "auto")
    if legacy != "auto" and legacy in SORTED_IMPLS:
        if concrete or SORTED_IMPLS[legacy].traceable:
            return _record(legacy)
    entry, _source = calibration_entry("sorted", n, num_cells,
                                       platform=platform)
    name = entry["impl"]
    if not concrete and not SORTED_IMPLS.get(
        name, SORTED_IMPLS["scatter"]
    ).traceable:
        name = entry.get("device_impl", "scatter")
    return _record(name)


def choose_unsorted(n: int, num_cells: int, *, concrete: bool = True,
                    platform: str | None = None) -> str:
    pinned = os.environ.get("HORAEDB_UNSORTED_IMPL", "auto")
    if pinned != "auto" and pinned in UNSORTED_IMPLS:
        if concrete or UNSORTED_IMPLS[pinned].traceable:
            return _record(pinned)
    entry, _source = calibration_entry("unsorted", n, num_cells,
                                       platform=platform)
    name = entry["impl"]
    if not concrete and not UNSORTED_IMPLS.get(
        name, UNSORTED_IMPLS["scatter"]
    ).traceable:
        name = entry.get("device_impl", "scatter")
    return _record(name)


def last_choice() -> str:
    """Most recent dispatcher decision for attribution: exact when the
    dispatch happened in the current context (same coroutine/thread, e.g.
    the scanstats note right after a fold); otherwise the process-global
    last decision — best-effort under concurrency."""
    ctx = _last_choice_ctx.get()
    return ctx if ctx is not None else _last_choice_global


# ---------------------------------------------------------------------------
# CLI sweep — the queued ROOFLINE §1 experiments, one command
# ---------------------------------------------------------------------------


def _sweep(n: int) -> dict:
    """Measure every registered impl at a dense sorted shape of n rows on
    the default backend and return a JSON-able report (run it on the chip
    before trusting a calibrated choice there)."""
    import jax

    platform = jax.devices()[0].platform
    cells = max(1, n // 22)  # ~TSBS density (the config-4 shape)
    rng = np.random.default_rng(7)
    k = np.sort(rng.integers(0, cells, n)).astype(np.int32)
    v = rng.normal(size=n).astype(np.float32)
    report: dict = {
        "metric": "agg_registry_sweep",
        "platform": platform,
        "n_rows": n,
        "num_cells": cells,
        "sorted_ab": {},
        "unsorted_ab": {},
    }
    for name in sorted_impl_names(platform):
        try:
            secs = _time_impl(lambda name=name: run_sorted(name, k, v, cells))
            report["sorted_ab"][name] = round(n / max(secs, 1e-9))
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            report["sorted_ab"][name] = f"error: {e}"[:120]
    ku = rng.permutation(k).astype(np.int32)
    for name in unsorted_impl_names(platform):
        try:
            secs = _time_impl(lambda name=name: run_unsorted(name, ku, v, cells))
            report["unsorted_ab"][name] = round(n / max(secs, 1e-9))
        except Exception as e:  # noqa: BLE001
            report["unsorted_ab"][name] = f"error: {e}"[:120]
    return report


def main(argv: list[str] | None = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sweep", type=int, nargs="?", const=1 << 22,
                    metavar="N_ROWS",
                    help="measure every registered impl at N_ROWS and "
                         "print one JSON line")
    args = ap.parse_args(argv)
    if args.sweep:
        print(json.dumps(_sweep(args.sweep)))
        return
    ap.print_help()


if __name__ == "__main__":
    main()
