"""Serving tier for dashboard-scale repeat traffic.

Production dashboard traffic is ~99% repeated panels re-scanning the same
sealed SSTs every refresh interval. This package turns that repeat work
into O(1)-ish lookups with two stacked layers, each honest about its
shortcuts (EXPLAIN `serving` verdict, `horaedb_serving_*` families, and
the `HORAEDB_SERVING=off` forced-cold switch):

1. **Compaction-time rollups** (storage/rollup.py): compaction already
   rewrites every byte of a segment, so it additionally emits 1m/1h
   pre-aggregated SSTs (sum/count/min/max per series per bucket) — exact
   LWW-post-merge, tombstones and late data already reconciled. The
   planner (engine/data.py) substitutes a rollup for a raw segment scan
   only when the segment's live SST set EXACTLY equals the rollup's
   recorded source set, no newer tombstone overlaps it, and the query
   grid is resolution-aligned — so a rollup can never serve stale data;
   it simply stops being used the moment anything changes, until the
   next compaction re-emits it.

2. **Result cache** (serving/cache.py): a byte-bounded process-global
   LRU over finished query results. The key IS the invalidation
   contract: (normalized plan fingerprint, the sealed-SST id set
   covering the range, tombstone ids, retention component) — any flush,
   compaction, or delete changes the key, so a stale entry can never
   hit. Flush/compaction/delete events additionally purge the table's
   entries eagerly (the funnel: `serving_invalidate`), and concurrent
   same-key fills collapse to one computation (single-flight).

jaxlint J013 enforces the funnel discipline: result-cache/rollup READS
happen only at the planner choke point (engine/data.py) and the serving/
rollup modules themselves; cache MUTATION happens only through the
invalidation funnel (storage write/compaction commit/delete paths).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from horaedb_tpu.common.size_ext import ReadableSize
from horaedb_tpu.common.time_ext import ReadableDuration
from horaedb_tpu.server.metrics import GLOBAL_METRICS

# -- metric families (pre-registered zero states so /metrics shows them
# -- from boot, the PR2 convention) ------------------------------------------

CACHE_REQUESTS = GLOBAL_METRICS.counter(
    "horaedb_serving_cache_requests_total",
    help="Result-cache lookups at the planner choke point, by outcome: "
         "hit (served without scanning), miss (computed + stored), "
         "bypass (HORAEDB_SERVING=off or serving disabled).",
    labelnames=("result",),
)
CACHE_BYTES = GLOBAL_METRICS.gauge(
    "horaedb_serving_cache_bytes",
    help="Resident bytes in the query result cache (byte-bounded LRU).",
)
CACHE_ENTRIES = GLOBAL_METRICS.gauge(
    "horaedb_serving_cache_entries",
    help="Entries resident in the query result cache.",
)
CACHE_EVICTIONS = GLOBAL_METRICS.counter(
    "horaedb_serving_cache_evictions_total",
    help="Result-cache entries evicted by the LRU byte bound.",
)
INVALIDATIONS = GLOBAL_METRICS.counter(
    "horaedb_serving_invalidations_total",
    help="Result-cache invalidation events through the funnel, by "
         "reason: flush (new SST committed), compact (manifest "
         "rewrite), delete (tombstone created).",
    labelnames=("reason",),
)
ROLLUPS_BUILT = GLOBAL_METRICS.counter(
    "horaedb_serving_rollups_built_total",
    help="Rollup artifacts emitted at compaction time, by resolution.",
    labelnames=("resolution",),
)
ROLLUP_SUBSTITUTIONS = GLOBAL_METRICS.counter(
    "horaedb_serving_rollup_substitutions_total",
    help="Per-segment rollup substitutions the planner made (a raw "
         "segment scan replaced by a bucket-count-scale rollup read), "
         "by resolution.",
    labelnames=("resolution",),
)
ROLLUP_ROWS = GLOBAL_METRICS.counter(
    "horaedb_serving_rollup_rows_total",
    help="Pre-aggregated rollup rows read in place of raw rows.",
)

for _r in ("hit", "miss", "bypass"):
    CACHE_REQUESTS.labels(_r)
for _r in ("flush", "compact", "delete"):
    INVALIDATIONS.labels(_r)
for _r in ("1m", "1h"):
    ROLLUPS_BUILT.labels(_r)
    ROLLUP_SUBSTITUTIONS.labels(_r)


def serving_env_off() -> bool:
    """The honesty switch: HORAEDB_SERVING=off forces every query cold
    (no result cache, no rollup substitution) so serving
    answers can be asserted bit-exact against first-principles scans.
    Read per query, not at import, so tests and operators can flip it
    live."""
    return os.environ.get("HORAEDB_SERVING", "").lower() in (
        "off", "0", "false", "no",
    )


def resolution_label(ms: int) -> str:
    """Human resolution label for metrics/EXPLAIN ("1m", "1h", else ms)."""
    if ms == 60_000:
        return "1m"
    if ms == 3_600_000:
        return "1h"
    if ms % 3_600_000 == 0:
        return f"{ms // 3_600_000}h"
    if ms % 60_000 == 0:
        return f"{ms // 60_000}m"
    return f"{ms}ms"


def parse_resolution(v) -> int:
    """One rollup resolution: int ms, or a duration string ("1m", "1h")."""
    if isinstance(v, int):
        return v
    return ReadableDuration.parse(v).as_millis()


@dataclass
class ServingTierConfig:
    """Knobs of the serving tier ([metric_engine.serving] in TOML).

    Defaults are ON: the tier is invalidation-correct by construction
    (results are bit-exact vs forced-cold scans — regression-tested and
    chaos-soaked), so there is no correctness reason to opt in."""

    enabled: bool = True
    # compaction-time downsample rollups (data tables only; emitted when
    # a compaction merges a FULL segment)
    rollup_enabled: bool = True
    rollup_resolutions: list = field(
        default_factory=lambda: [60_000, 3_600_000]  # 1m, 1h
    )
    # result-cache byte budget (process-global LRU; 0 disables)
    result_cache: ReadableSize = field(
        default_factory=lambda: ReadableSize.mb(64)
    )
    # decoded rollup-artifact read cache (storage/rollup.py; 0 disables)
    rollup_cache: ReadableSize = field(
        default_factory=lambda: ReadableSize.mb(16)
    )

    @classmethod
    def from_dict(cls, d: dict | None) -> "ServingTierConfig":
        if d is None:
            return cls()
        from horaedb_tpu.common.error import HoraeError

        known = {f for f in cls.__dataclass_fields__}
        unknown = set(d) - known
        if unknown:
            raise HoraeError(
                f"unknown config keys for ServingTierConfig: {sorted(unknown)}"
            )
        kwargs = dict(d)
        if "rollup_resolutions" in kwargs:
            kwargs["rollup_resolutions"] = [
                parse_resolution(v) for v in kwargs["rollup_resolutions"]
            ]
        for k in ("result_cache", "rollup_cache"):
            if k in kwargs:
                kwargs[k] = ReadableSize.parse(kwargs[k])
        return cls(**kwargs)


class ServingTier:
    """One engine's handle on the (process-global) serving tier: the
    config plus the shared result cache, sized at engine open. Installed
    on each SampleManager as the planner's single entry into the tier."""

    def __init__(self, config: "ServingTierConfig | None" = None):
        from horaedb_tpu.serving import cache as cache_mod

        self.config = config or ServingTierConfig()
        self.cache = cache_mod.RESULT_CACHE
        if self.config.enabled:
            from horaedb_tpu.storage import rollup as rollup_mod

            cache_mod.configure(self.config.result_cache.as_bytes())
            rollup_mod.configure_cache(self.config.rollup_cache.as_bytes())

    def active(self) -> bool:
        """Serving layers may be consulted for this query (config on AND
        the HORAEDB_SERVING honesty switch not forcing cold)."""
        return self.config.enabled and not serving_env_off()

    @property
    def rollups_active(self) -> bool:
        return self.active() and self.config.rollup_enabled
