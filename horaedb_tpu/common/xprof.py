"""Device-side profiling: instrumented jit + a process-wide kernel catalog.

PR 2 made the host side observable (request traces, per-stage lane
histograms) and PR 3 added dispatcher provenance — but the device side
stayed a black box: nothing recorded when JAX recompiled a hot kernel or
how much of a slow request was compile time rather than steady-state
execution.

`xjit` wraps `jax.jit` and every hot-path jitted entry point (ops/,
parallel/, storage/read.py — enforced by jaxlint J007) routes through it:

    @xjit(kernel="block_sum_count", static_argnames=("num_cells",))
    def _block_sum_count_xla(...): ...

    fn = xjit(mapped, kernel="sharded_downsample")   # inline form

Per kernel it records:

- compile/retrace events: `horaedb_jit_compile_total{kernel}` and
  `horaedb_jit_compile_seconds{kernel}` on /metrics, plus the
  arg-signature (shapes/dtypes/static values) that triggered the
  retrace — the #1 question when a steady workload suddenly stalls is
  "what shape churned the cache";
- distinct-signature count: `horaedb_jit_cache_entries{kernel}`.

The jitted function carries the `kernel=` label as its name, so the
device's trace reads `jit_<kernel>` (never `jit_kernel`), and every call
runs under a `TraceAnnotation("xjit.<kernel>")`, so the host side of a
dispatch — and a compile under it — has a name on the profiler's
timeline. What a kernel should cost comes from the benchmark's work
functions and the device trace (bench_chip/work/), not from here.

Beside the catalog, `register_metrics()` hangs two `jax.monitoring`
listeners: EVERY XLA compile of the process, the eager `jnp` ones no xjit
sees included, lands in `horaedb_xla_compile_seconds` (count = compiles
and loads from the persistent cache, sum = their seconds) and every
persistent-cache hit in `horaedb_xla_cache_hits_total`. A compile outside
an xjit call also goes to the request's `compile` lane.

Detection mechanism: the traced wrapper body only executes when JAX
(re)traces — a cache hit never enters Python beyond the jit dispatch — so
a sentinel in the body is an EXACT retrace detector with zero
steady-state cost beyond one contextvar set/reset per call. No per-call
device sync, ever (the overhead bar tests/test_xprof.py pins).

Honest accounting notes:
- `compile_seconds` is the wall time of the triggering call (trace +
  XLA compile + async dispatch) — the latency the REQUEST paid, which is
  the quantity operators attribute. Nested retraces (an xjit kernel
  traced inside an outer xjit compile) count their trace time under both
  kernels, so per-kernel compile sums can exceed wall clock, exactly
  like overlapping scanstats stages.

Knobs:
    HORAEDB_XPROF       off -> xjit degrades to plain jax.jit (no
                        telemetry, no catalog)
"""

from __future__ import annotations

import functools
import os
import threading
import time
from contextvars import ContextVar

import jax
from jax.profiler import TraceAnnotation

__all__ = ["xjit", "XJit", "catalog", "snapshot", "kernel_entries", "reset",
           "register_metrics", "xla_totals"]

# Compile-latency buckets: traces are >=ms, XLA compiles span 10ms-minutes.
COMPILE_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
    10.0, 30.0, 60.0, 120.0,
)

# The horaedb_jit_* families, created on first use instead of at import:
# this module sits BELOW ops/ (every hot kernel module imports it), and a
# top-level `from horaedb_tpu.server.metrics import ...` would close the
# cycle server -> config -> storage -> ops -> xprof -> server. Registration
# is idempotent; server/main.py calls register_metrics() at boot so the
# zero-state families render on /metrics before the first compile.
_metric_families = None
_xla_families = None
_metrics_lock = threading.Lock()


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def register_metrics():
    """(compile_total, compile_seconds, cache_entries) families of the
    xjit kernels, creating them in the process registry on first call,
    and with them the two `horaedb_xla_*` families and the jax.monitoring
    listeners that feed them."""
    global _metric_families, _xla_families
    if _metric_families is None:
        with _metrics_lock:
            if _metric_families is None:
                from horaedb_tpu.server.metrics import GLOBAL_METRICS

                _metric_families = (
                    GLOBAL_METRICS.counter(
                        "horaedb_jit_compile_total",
                        help="JIT trace/compile events per instrumented "
                             "kernel (a steady workload should flatline "
                             "after warmup; growth = retrace churn).",
                        labelnames=("kernel",),
                    ),
                    GLOBAL_METRICS.histogram(
                        "horaedb_jit_compile_seconds",
                        help="Wall seconds the triggering call paid for a "
                             "trace+compile, per kernel.",
                        labelnames=("kernel",),
                        buckets=COMPILE_BUCKETS,
                    ),
                    GLOBAL_METRICS.gauge(
                        "horaedb_jit_cache_entries",
                        help="Distinct arg-signatures seen per instrumented "
                             "kernel (the lower bound of the jit cache's "
                             "entry count).",
                        labelnames=("kernel",),
                    ),
                )
                _xla_families = (
                    GLOBAL_METRICS.histogram(
                        "horaedb_xla_compile_seconds",
                        help="Every XLA backend compile of the process, "
                             "eager jnp operations included: count = "
                             "compiles and persistent-cache loads, sum = "
                             "their seconds.",
                        buckets=COMPILE_BUCKETS,
                    ),
                    GLOBAL_METRICS.counter(
                        "horaedb_xla_cache_hits_total",
                        help="Compiles answered by JAX's persistent "
                             "compilation cache.",
                    ),
                )
                jax.monitoring.register_event_duration_secs_listener(
                    _on_duration)
                jax.monitoring.register_event_listener(_on_event)
    return _metric_families


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event != _BACKEND_COMPILE:
        return
    _xla_families[0].observe(duration)
    if _TRACE_BOX.get() is None:
        # an eager compile (or any outside an XJit call): nobody else hands
        # it to the request's compile lane; inside one, _record_compile does
        _scanstats().record("compile", duration)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _xla_families[1].inc()


def xla_totals() -> dict:
    """Every XLA compile since boot (the `xla` object of /debug/kernels)."""
    register_metrics()
    compiles, hits = (f.labels() for f in _xla_families)
    return {
        "compiles": compiles.count,
        "compile_seconds": round(compiles.sum, 6),
        "cache_hits": int(hits.value),
    }

# Sentinel box: a list the traced wrapper appends the triggering signature
# to. Context-local so concurrent asyncio requests cannot claim each
# other's compiles; None outside an XJit.__call__ (where the compile
# listener, not the wrapper, owns the request's compile lane).
_TRACE_BOX: ContextVar["list | None"] = ContextVar("horaedb_xprof_box",
                                                   default=None)

_REG_LOCK = threading.Lock()
# kernel name -> shared telemetry. Memoized builders (lru_cache'd kernel
# factories) create one XJit per shape variant and may EVICT them; the
# telemetry lives on this per-name object instead of the instance so (a)
# an evicted instance — and its compiled executables — is garbage like
# any other jitted function (the registry never pins it), and (b) the
# compile history it accumulated survives the eviction.
_REGISTRY: dict[str, "_KernelStats"] = {}

_MAX_SIGNATURES = 64      # per-instance signature memory bound
_SIG_LEAVES = 16          # leaves rendered per signature


def _signature(args: tuple, kwargs: dict) -> str:
    """Render the call's abstract signature: dtype[shape] per array leaf,
    repr for static/aux leaves. Runs at TRACE time only (leaves are
    tracers), so cost is irrelevant."""
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    parts = []
    for leaf in leaves[:_SIG_LEAVES]:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(
                f"{getattr(dtype, 'name', dtype)}"
                f"[{','.join(str(d) for d in shape)}]"
            )
        else:
            parts.append(repr(leaf)[:32])
    if len(leaves) > _SIG_LEAVES:
        parts.append(f"+{len(leaves) - _SIG_LEAVES} more")
    return "(" + ", ".join(parts) + ")"


_scanstats_mod = None


def _scanstats():
    """Lazy storage.scanstats import (runtime only: common/ must not
    import storage/ at module load — scanstats itself imports this
    package's tracing)."""
    global _scanstats_mod
    if _scanstats_mod is None:
        from horaedb_tpu.storage import scanstats

        _scanstats_mod = scanstats
    return _scanstats_mod


class _KernelStats:
    """Per-kernel-NAME telemetry, shared by every XJit instance carrying
    the name (one per memoized shape variant). Own lock — instances come
    and go, the stats object is process-lifetime."""

    __slots__ = ("kernel", "lock", "instances", "compiles",
                 "compile_seconds", "signatures", "last_compile_ms")

    def __init__(self, kernel: str):
        self.kernel = kernel
        self.lock = threading.Lock()
        self.instances = 0          # XJit constructions, not live objects
        self.compiles = 0
        self.compile_seconds = 0.0
        self.signatures: dict[str, int] = {}
        self.last_compile_ms: float | None = None

    def snapshot(self) -> dict:
        with self.lock:
            sigs = dict(self.signatures)
            return {
                "kernel": self.kernel,
                "instances": self.instances,
                "compiles": self.compiles,
                "compile_seconds": round(self.compile_seconds, 6),
                "cache_entries": len(sigs),
                "signatures": sigs,
                "last_compile_ms": self.last_compile_ms,
            }


def _stats_for(kernel: str) -> _KernelStats:
    with _REG_LOCK:
        stats = _REGISTRY.get(kernel)
        if stats is None:
            stats = _REGISTRY[kernel] = _KernelStats(kernel)
        return stats


class XJit:
    """One instrumented jit-wrapped callable. Exposes the jit surface the
    codebase uses (`__call__`, `lower`) plus telemetry accessors."""

    def __init__(self, fn, kernel: str, jit_kwargs: dict):
        self.kernel = kernel
        self._fn = fn
        self._jit_kwargs = jit_kwargs
        self._stats = _stats_for(kernel)
        with self._stats.lock:
            self._stats.instances += 1

        def _traced(*args, **kwargs):
            box = _TRACE_BOX.get()
            if box is not None:
                box.append(_signature(args, kwargs))
            return fn(*args, **kwargs)

        # __wrapped__ lets inspect.signature (which jax uses to resolve
        # static_argnames to positions) see the REAL parameter list
        # through the (*args, **kwargs) wrapper
        functools.update_wrapper(_traced, fn)
        # the program's name on the device: `jit_<kernel>` in the trace's
        # XLA Modules line, not the Python function's (seven are `kernel`)
        _traced.__name__ = _traced.__qualname__ = kernel
        self._jitted = jax.jit(_traced, **jit_kwargs)
        self._annotation = f"xjit.{kernel}"

    def __call__(self, *args, **kwargs):
        box: list = []
        token = _TRACE_BOX.set(box)
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(self._annotation):
                out = self._jitted(*args, **kwargs)
        finally:
            _TRACE_BOX.reset(token)
        if box:
            self._record_compile(box[-1], time.perf_counter() - t0)
        _scanstats().kernel_use(self.kernel)
        return out

    def lower(self, *args, **kwargs):
        """AOT lowering passthrough (plan-shape tests)."""
        return self._jitted.lower(*args, **kwargs)

    # -- telemetry ----------------------------------------------------------

    def _record_compile(self, sig: str, dt: float) -> None:
        stats = self._stats
        with stats.lock:
            stats.compiles += 1
            stats.compile_seconds += dt
            stats.signatures[sig] = stats.signatures.get(sig, 0) + 1
            while len(stats.signatures) > _MAX_SIGNATURES:
                stats.signatures.pop(next(iter(stats.signatures)))
            stats.last_compile_ms = time.time() * 1000.0
            n_sigs = len(stats.signatures)
        compile_total, compile_seconds, cache_entries = register_metrics()
        compile_total.labels(self.kernel).inc()
        compile_seconds.labels(self.kernel).observe(dt)
        cache_entries.labels(self.kernel).set(n_sigs)
        # feed the query-scoped collector + the stage histogram + the
        # active trace span: compile becomes a first-class lane next to
        # io/transfer/kernel in the roofline attribution
        _scanstats().record("compile", dt)

    def stats(self) -> dict:
        """This kernel NAME's merged telemetry (shared across shape
        variants)."""
        return self._stats.snapshot()


def xjit(fn=None, *, kernel: str | None = None, **jit_kwargs):
    """Instrumented drop-in for `jax.jit`.

    Decorator factory (`@xjit(kernel="...", static_argnames=...)`),
    bare decorator (`@xjit`), or inline wrapper (`xjit(f, kernel="...")`).
    `kernel` is the catalog/metric label; defaults to the function name.
    All other kwargs pass through to `jax.jit`. `HORAEDB_XPROF=off`
    degrades to plain `jax.jit` (no telemetry, no catalog entry).
    """
    if fn is None:
        return lambda f: xjit(f, kernel=kernel, **jit_kwargs)
    if os.environ.get("HORAEDB_XPROF", "on").lower() in ("off", "0", "false"):
        return jax.jit(fn, **jit_kwargs)
    name = kernel or getattr(fn, "__name__", "kernel").lstrip("_") or "kernel"
    return XJit(fn, name, jit_kwargs)


# -- process-wide catalog ---------------------------------------------------


def _all_stats() -> list[_KernelStats]:
    with _REG_LOCK:
        return list(_REGISTRY.values())


def catalog() -> list[dict]:
    """Per-kernel telemetry, compiled-kernels first (the
    GET /debug/kernels payload)."""
    out = [s.snapshot() for s in _all_stats()]
    out.sort(key=lambda d: (-d["compiles"], d["kernel"]))
    return out


def kernel_entries(names) -> list[dict]:
    """Catalog entries for the named kernels only (query EXPLAIN embeds
    just the kernels the request invoked)."""
    wanted = set(names)
    with _REG_LOCK:
        stats = [v for k, v in sorted(_REGISTRY.items()) if k in wanted]
    return [s.snapshot() for s in stats]


def snapshot() -> dict:
    """Process totals (bench.py's compile/steady split)."""
    total = 0
    seconds = 0.0
    for s in _all_stats():
        with s.lock:
            total += s.compiles
            seconds += s.compile_seconds
    return {
        "kernels": len(_REGISTRY),
        "total_compiles": total,
        "total_compile_seconds": round(seconds, 6),
    }


def reset() -> None:
    """Zero per-kernel counters (tests). Kernel names stay registered —
    the wrapped functions are module-level; only their telemetry clears.
    Prometheus counters are monotone by contract and are NOT reset."""
    for s in _all_stats():
        with s.lock:
            s.compiles = 0
            s.compile_seconds = 0.0
            s.signatures.clear()
            s.last_compile_ms = None
