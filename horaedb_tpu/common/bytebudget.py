"""Unified byte-budget pool registry.

The engine grew four byte-bounded caches, each tracking its own bytes
with its own gauge family and its own eviction discipline:

- ``scan``      — decoded row-group block cache (storage/read.py
                  `_blk_cache`, per ParquetReader)
- ``sidecar``   — encoded-lane sidecar cache (storage/read.py
                  `_enc_cache`, per ParquetReader)
- ``result``    — serving result cache (serving/cache.py RESULT_CACHE)
- ``rollup``    — decoded rollup artifacts (storage/rollup.py _CACHE)

This module re-homes them behind ONE registry: each cache keeps its own
data structure and locking, but registers a *provider* (a weakly-held
owner + an accessor returning (bytes, entries)) and routes eviction
counts through `note_eviction`. The registry exports the unified
`horaedb_pool_bytes{pool}` / `horaedb_pool_entries{pool}` /
`horaedb_pool_capacity_bytes{pool}` / `horaedb_pool_evictions_total{pool}`
families and the `GET /debug/memory` occupancy snapshot.

Providers rather than pushed deltas because pools are process-global
while some owners are not: every ParquetReader carries its own scan +
sidecar caches, and readers come and go with engines (tests open dozens
per process). A pushed-delta gauge would drift up with every dropped
reader; the weakref-provider snapshot sums only the caches that are
still alive, so `horaedb_pool_bytes` is resident-byte honest by
construction. `refresh()` is called on every /metrics render and
/debug/memory hit — a handful of attribute reads per pool."""

from __future__ import annotations

import threading
import weakref

from horaedb_tpu.server.metrics import GLOBAL_METRICS

# The four pools, pre-registered so the families render from boot.
POOLS = ("scan", "sidecar", "result", "rollup")

POOL_BYTES = GLOBAL_METRICS.gauge(
    "horaedb_pool_bytes",
    help="Resident bytes per byte-budgeted pool (unified registry view; "
         "summed over live owners, refreshed on every /metrics render).",
    labelnames=("pool",),
)
POOL_ENTRIES = GLOBAL_METRICS.gauge(
    "horaedb_pool_entries",
    help="Entries per byte-budgeted pool.",
    labelnames=("pool",),
)
POOL_CAPACITY = GLOBAL_METRICS.gauge(
    "horaedb_pool_capacity_bytes",
    help="Configured byte budget per pool (0 = disabled).",
    labelnames=("pool",),
)
POOL_EVICTIONS = GLOBAL_METRICS.counter(
    "horaedb_pool_evictions_total",
    help="Budget-pressure evictions per pool (invalidation-driven "
         "removals are not evictions and do not count here).",
    labelnames=("pool",),
)


class PoolRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        # pool -> list of (weakref(owner), accessor(owner) -> (bytes, n))
        self._providers: dict[str, list] = {p: [] for p in POOLS}
        self._capacity: dict[str, int] = {p: 0 for p in POOLS}
        self._evict_child = {p: POOL_EVICTIONS.labels(p) for p in POOLS}
        for p in POOLS:  # eager zero-state
            POOL_BYTES.labels(p)
            POOL_ENTRIES.labels(p)
            POOL_CAPACITY.labels(p)

    def register_provider(self, pool: str, owner, accessor) -> None:
        """Attach one owner's occupancy view to `pool`. `accessor(owner)`
        must return (resident_bytes, entries) without taking the owner's
        lock order into anything registry-side (the registry only reads
        plain ints). Dead owners fall out on the next refresh."""
        ref = weakref.ref(owner)
        with self._lock:
            lst = self._providers.setdefault(pool, [])
            lst[:] = [(r, a) for (r, a) in lst if r() is not None]
            lst.append((ref, accessor))

    def set_capacity(self, pool: str, nbytes: int) -> None:
        with self._lock:
            self._capacity[pool] = int(nbytes)
        POOL_CAPACITY.labels(pool).set(int(nbytes))

    def note_eviction(self, pool: str, n: int = 1) -> None:
        child = self._evict_child.get(pool)
        if child is None:
            with self._lock:
                child = self._evict_child.setdefault(
                    pool, POOL_EVICTIONS.labels(pool)
                )
        child.inc(n)

    def refresh(self) -> dict:
        """Sum live providers, update the gauge families, and return the
        /debug/memory occupancy map
        {pool: {bytes, entries, capacity_bytes, evictions, owners}}."""
        with self._lock:
            views = {
                p: list(lst) for p, lst in self._providers.items()
            }
            caps = dict(self._capacity)
        out: dict[str, dict] = {}
        for pool, lst in views.items():
            total_b = 0
            total_n = 0
            owners = 0
            for ref, accessor in lst:
                owner = ref()
                if owner is None:
                    continue
                try:
                    b, n = accessor(owner)
                except Exception:  # noqa: BLE001 — a torn read costs a tick
                    continue
                total_b += int(b)
                total_n += int(n)
                owners += 1
            POOL_BYTES.labels(pool).set(total_b)
            POOL_ENTRIES.labels(pool).set(total_n)
            cap = caps.get(pool, 0)
            out[pool] = {
                "bytes": total_b,
                "entries": total_n,
                "capacity_bytes": cap,
                "utilization": round(total_b / cap, 4) if cap else None,
                "evictions": int(self._evict_child[pool].value)
                if pool in self._evict_child else 0,
                "owners": owners,
            }
        return out


GLOBAL_POOLS = PoolRegistry()


def rss_bytes() -> "int | None":
    """Process resident-set bytes from /proc/self/statm (None where the
    procfs file is unavailable — macOS dev boxes)."""
    import os

    try:
        # jaxlint: disable=J018 procfs pseudo-file: a memory read, not IO — never blocks
        with open("/proc/self/statm") as f:
            fields = f.read().split()
        return int(fields[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, IndexError, ValueError):
        return None
