"""Server configuration (reference: src/server/src/config.rs:21-175).

Same tree: port, test-write knobs, engine threads, object-store selection
(tagged enum Local | S3-like), nested StorageConfig. TOML via tomllib,
deny_unknown_fields semantics throughout, ReadableDuration/Size strings
accepted anywhere a duration/size appears (docs/example.toml analog below).

Example:

    port = 5000

    [test]
    enable_write = true
    write_worker_num = 2
    write_interval = "500ms"
    segment_duration = "12h"

    [metric_engine.storage.object_store]
    type = "Local"
    data_dir = "/tmp/horaedb-tpu"

    [metric_engine.storage.time_merge_storage]
    update_mode = "Overwrite"
"""

from __future__ import annotations

try:
    import tomllib  # Python >= 3.11
except ImportError:  # 3.10 images ship the API-identical backport
    import tomli as tomllib
from dataclasses import dataclass, field

from horaedb_tpu.common import memtrace as _memtrace_mod
from horaedb_tpu.common import tracing as _tracing_mod
from horaedb_tpu.common.error import ensure
from horaedb_tpu.common.time_ext import ReadableDuration
from horaedb_tpu.objstore.s3 import HttpOptions, S3LikeConfig, TimeoutOptions
from horaedb_tpu.storage.config import StorageConfig, _from_dict


def _default_retry():
    # deferred: objstore.resilient registers metric families, whose
    # registry module lives under server/ — a top-level import here would
    # close the server.__init__ -> config -> resilient -> server.metrics
    # cycle while server is still partially initialized
    from horaedb_tpu.objstore.resilient import RetryPolicy

    return RetryPolicy()


def _default_breaker():
    from horaedb_tpu.objstore.resilient import BreakerPolicy

    return BreakerPolicy()


def _serving_mod():
    # deferred for the same cycle reason as the resilience defaults:
    # serving registers metric families on the server-side registry
    from horaedb_tpu import serving

    return serving


def _telemetry_mod():
    # deferred: telemetry registers the horaedb_tenant_*/_telemetry_*
    # families and wires the exemplar source
    from horaedb_tpu import telemetry

    return telemetry


def _batching_mod():
    # deferred: batching registers the horaedb_batch_* families
    from horaedb_tpu.server import batching

    return batching


def _cluster_mod():
    # deferred: cluster registers the horaedb_cluster_* families
    from horaedb_tpu import cluster

    return cluster


@dataclass
class TestConfig:
    """Self-write load generator (reference config.rs TestConfig)."""

    enable_write: bool = False
    write_worker_num: int = 1
    write_interval: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.millis(500)
    )
    segment_duration: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.hours(12)
    )

    @classmethod
    def from_dict(cls, d: dict | None) -> "TestConfig":
        return _from_dict(cls, d)


@dataclass
class ThreadConfig:
    """Background executor sizing (reference: tokio runtime thread counts;
    here: bounded concurrency for manifest/compaction work)."""

    manifest_thread_num: int = 2
    sst_thread_num: int = 2

    @classmethod
    def from_dict(cls, d: dict | None) -> "ThreadConfig":
        return _from_dict(cls, d)


@dataclass
class ResilienceConfig:
    """Fault-tolerance knobs for the object-store boundary
    (objstore/resilient.py): the server wraps whichever store it builds
    in a ResilientStore with this retry ladder and circuit breaker.
    `[metric_engine.storage.object_store.resilience.retry]` /
    `[...resilience.breaker]` in TOML. There is no off switch — set
    `retry.max_attempts = 1` and `breaker.failure_threshold = 0` to get
    single-attempt semantics with classification/metrics kept."""

    retry: object = field(default_factory=_default_retry)
    breaker: object = field(default_factory=_default_breaker)

    @classmethod
    def from_dict(cls, d: dict | None) -> "ResilienceConfig":
        return _from_dict(cls, d)


@dataclass
class ObjectStoreConfig:
    """Tagged store selection: `type = "Local"` (data_dir) or
    `type = "S3Like"` with the reference's full knob tree
    (config.rs:104-130). Divergence from the reference, documented: its
    main.rs:112 panics 'S3 not support yet' even though the config parses;
    here S3Like actually boots (objstore/s3.py)."""

    type: str = "Local"
    data_dir: str = "/tmp/horaedb-tpu"
    # S3-like knobs (objstore/s3.py::S3LikeConfig)
    region: str = ""
    endpoint: str = ""
    bucket: str = ""
    key_id: str = ""
    key_secret: str = ""
    prefix: str = ""
    max_retries: int = 3
    http: HttpOptions = field(default_factory=HttpOptions)
    timeout: TimeoutOptions = field(default_factory=TimeoutOptions)
    # retry/backoff/breaker policy applied by the server's ResilientStore
    # wrapper around EITHER store type (objstore/resilient.py)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)

    @classmethod
    def from_dict(cls, d: dict | None) -> "ObjectStoreConfig":
        return _from_dict(cls, d)

    def to_s3_config(self) -> "S3LikeConfig":
        return S3LikeConfig(
            region=self.region, key_id=self.key_id,
            key_secret=self.key_secret, endpoint=self.endpoint,
            bucket=self.bucket, prefix=self.prefix,
            max_retries=self.max_retries, http=self.http,
            timeout=self.timeout,
        )


@dataclass
class EngineStorageConfig:
    object_store: ObjectStoreConfig = field(default_factory=ObjectStoreConfig)
    time_merge_storage: StorageConfig = field(default_factory=StorageConfig)

    @classmethod
    def from_dict(cls, d: dict | None) -> "EngineStorageConfig":
        return _from_dict(cls, d)


@dataclass
class IngestConfig:
    """Overlapped ingest->flush pipeline knobs (engine/flush_executor.py).

    `flush_workers` background write-out workers drain a queue of at most
    `flush_queue_max` sealed memtables; when the queue is full, appends
    block (backpressure, horaedb_ingest_stall_seconds) and fail with a
    retryable error past `stall_deadline`. Bounded ingest memory is
    roughly (flush_queue_max + flush_workers + 1) x ingest_buffer_rows."""

    flush_workers: int = 2
    flush_queue_max: int = 4
    stall_deadline: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(30)
    )

    @classmethod
    def from_dict(cls, d: dict | None) -> "IngestConfig":
        return _from_dict(cls, d)


@dataclass
class QueryConfig:
    """Query-path admission control knobs (`[metric_engine.query]`,
    server/admission.py): a bounded scheduler in front of the engine so
    a dashboard burst degrades to 503s + Retry-After instead of
    unbounded concurrent scans, and every query carries an end-to-end
    deadline (504 past it). See docs/operations.md "Query admission &
    deadlines"."""

    # Global in-flight query cap (scans running concurrently).
    max_concurrent: int = 8
    # Per-tenant in-flight cap; 0 = same as max_concurrent.
    max_per_tenant: int = 0
    # Bounded admission queue; a full queue sheds 503 immediately. 0
    # disables queuing entirely (at-capacity queries shed at once).
    queue_max: int = 64
    # A query queued longer than this sheds 503 (the stall deadline).
    queue_deadline: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(5)
    )
    # Default end-to-end query deadline; per-request override via
    # Prometheus-style `timeout=` (clamped to max_timeout).
    default_timeout: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(30)
    )
    max_timeout: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(300)
    )
    # Hard cost gate: shed (503) queries whose ESTIMATED device cost
    # (server/admission.py CostModel, seeded from the xprof kernel
    # catalog) exceeds this many seconds. 0 disables the gate — the
    # estimate still rides EXPLAIN's admission verdict.
    max_cost_s: float = 0.0
    # Header naming the tenant for fairness accounting.
    tenant_header: str = "X-Horaedb-Tenant"
    # Weighted-fair shares per tenant (default weight 1.0):
    # [metric_engine.query.tenant_weights] dashboards = 2.0
    tenant_weights: dict = field(default_factory=dict)
    # Query batcher ([metric_engine.query.batching], server/batching.py):
    # compatible cache-MISS grid queries arriving within max_delay
    # coalesce into ONE stacked kernel launch; HORAEDB_BATCH=off is the
    # runtime honesty switch. See docs/operations.md "Query batching".
    batching: object = field(
        default_factory=lambda: _batching_mod().BatchingConfig()
    )

    @classmethod
    def from_dict(cls, d: dict | None) -> "QueryConfig":
        return _from_dict(cls, d)


@dataclass
class RetentionConfig:
    """Per-table retention horizon (`[metric_engine.retention]`): samples
    older than now - period stop existing. Row-exact at scan time via the
    shared visibility mask (storage/visibility.py), whole SSTs expire
    physically through the compaction scheduler's TTL (including
    expired-only delete tasks on quiet tables). Applies to the data +
    exemplars tables of every region; registration tables never expire.
    period = "0s" / absent keeps samples forever."""

    period: ReadableDuration | None = None

    @classmethod
    def from_dict(cls, d: dict | None) -> "RetentionConfig":
        if d is None:
            return cls()
        unknown = set(d) - {"period"}
        ensure(not unknown,
               f"unknown config keys for RetentionConfig: {sorted(unknown)}")
        p = d.get("period")
        if p in (None, "", 0, "0s"):
            return cls()
        return cls(period=ReadableDuration.parse(p))

    def period_ms(self) -> int | None:
        if self.period is None:
            return None
        ms = self.period.as_millis()
        return ms if ms > 0 else None


@dataclass
class LimitsConfig:
    """Dirty-traffic limits (`[metric_engine.limits]`).

    `max_series`: per-engine series-cardinality cap enforced by the
    ingest-path HLL sketch (ingest/cardinality.py): at the limit, NEW
    series are rejected with a 503/Retry-After partial-accept while
    existing-series samples keep landing. On regioned deployments the
    limit applies PER REGION (series hash-partition evenly, so the
    effective global cap is ~num_regions x max_series). 0 = unlimited
    (the sketch still runs and exports horaedb_series_cardinality)."""

    max_series: int = 0

    @classmethod
    def from_dict(cls, d: dict | None) -> "LimitsConfig":
        return _from_dict(cls, d)


@dataclass
class RulesConfig:
    """Streaming rule engine knobs (`[metric_engine.rules]`,
    horaedb_tpu/rules): recording rules materialized incrementally at
    flush time + alert rules with exactly-once transitions. See
    docs/operations.md "Rules"."""

    enabled: bool = True
    # evaluator tick spacing (the server's background loop; rules are
    # dirty-set driven, so a quiet tick costs ~nothing)
    eval_interval: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(30)
    )
    # admission-fairness identity for rule evaluations, and its
    # weighted-fair share (merged into query.tenant_weights; low by
    # default so a rule storm queues behind dashboards, not ahead)
    tenant: str = "rules"
    tenant_weight: float = 0.25
    # rules declared in TOML ([[metric_engine.rules.recording]] /
    # [[metric_engine.rules.alerting]] arrays of tables); validated and
    # durably registered at boot (by name — a restart re-asserts them)
    recording: list = field(default_factory=list)
    alerting: list = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: dict | None) -> "RulesConfig":
        # kind-tagging of the recording/alerting arrays lives in the
        # generic loader (_from_dict), which is ALSO what runs when this
        # config nests under MetricEngineConfig — one path, no drift
        return _from_dict(cls, d)


@dataclass
class MetricEngineConfig:
    threads: ThreadConfig = field(default_factory=ThreadConfig)
    ingest: IngestConfig = field(default_factory=IngestConfig)
    query: QueryConfig = field(default_factory=QueryConfig)
    retention: RetentionConfig = field(default_factory=RetentionConfig)
    limits: LimitsConfig = field(default_factory=LimitsConfig)
    # Streaming rule engine ([metric_engine.rules], horaedb_tpu/rules):
    # recording rules evaluated incrementally off the invalidation
    # funnel's dirty sets, alert rules with fenced exactly-once
    # transitions, both admission-controlled as a low-weight tenant.
    rules: RulesConfig = field(default_factory=RulesConfig)
    # Serving tier for repeated dashboard traffic ([metric_engine.serving],
    # horaedb_tpu/serving): compaction-time rollups and the invalidation-
    # correct result cache. ON by default — answers are bit-exact vs
    # forced-cold scans (HORAEDB_SERVING=off).
    serving: "ServingTierConfig" = field(
        default_factory=lambda: _serving_mod().ServingTierConfig()
    )
    # Self-telemetry ([metric_engine.telemetry], horaedb_tpu/telemetry):
    # the self-scrape loop writing the registry's families back through
    # the normal ingest path as first-class series, per-tenant usage
    # metering, and the HORAEDB_TELEMETRY=off kill switch.
    telemetry: "TelemetryConfig" = field(
        default_factory=lambda: _telemetry_mod().TelemetryConfig()
    )
    # SLO burn-rate templates ([[metric_engine.slo]] array of tables,
    # telemetry/slo.py): each expands into recording + alert rules over
    # the self-scraped series at boot (requires rules.enabled).
    slo: list = field(default_factory=list)
    # Cluster layer ([metric_engine.cluster], horaedb_tpu/cluster):
    # stateless read replicas over the shared object store, the
    # region-assignment map, and the rendezvous query router. Disabled =
    # the single-process behavior, byte-identical.
    cluster: "ClusterConfig" = field(
        default_factory=lambda: _cluster_mod().ClusterConfig()
    )
    storage: EngineStorageConfig = field(default_factory=EngineStorageConfig)
    # Data-plane memory observatory ([metric_engine.memory],
    # common/memtrace.py): per-query buffer-lineage tracing mode.
    memory: "MemoryConfig" = field(default_factory=lambda: MemoryConfig())
    # Ingest buffering (engine/data.py SampleManager): 0 = every write is
    # immediately durable (reference write==SST semantics); > 0 buffers up
    # to that many rows (flushed at the threshold, on the flush interval,
    # before every query, and on shutdown). Higher throughput, bounded
    # data-loss window on crash.
    ingest_buffer_rows: int = 0
    ingest_flush_interval: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(1)
    )
    # Region partitioning (RFC :28-76): > 1 runs N independent region
    # engines over the shared store, series routed by seahash range
    # (engine/region.py). 1 = a single unpartitioned engine.
    num_regions: int = 1
    # "series" = hash(metric + sorted tags) range partition (the RFC
    # design; one metric spans regions, reads fan out + merge, regions can
    # split). "metric" = coarse metric-granularity routing.
    region_granularity: str = "series"
    # Non-empty = claim exclusive write ownership of each region root via
    # epoch fencing (storage/fence.py): required when several server
    # processes share one object store; a later claimant deposes this one
    # and its writes fail with FencedError instead of corrupting manifests.
    node_id: str = ""

    @classmethod
    def from_dict(cls, d: dict | None) -> "MetricEngineConfig":
        return _from_dict(cls, d)


@dataclass
class MemoryConfig:
    """Data-plane memory observatory knobs ([metric_engine.memory],
    common/memtrace.py). The default comes from HORAEDB_MEMTRACE (via
    memtrace.env_default), so build_app applying this config never
    clobbers an env override set without a config section; an explicit
    config value wins over both."""

    # "" (default: cheap per-query lineage ledger), "deep" (adds
    # tracemalloc peak-delta + top allocation sites per query — debug
    # only), "off" (no-op collectors; the funnels still perform their
    # array ops, so the data path is byte-identical).
    memtrace: str = field(default_factory=lambda: _memtrace_mod.env_default())

    @classmethod
    def from_dict(cls, d: dict | None) -> "MemoryConfig":
        return _from_dict(cls, d)


@dataclass
class TracingConfig:
    """Request tracing knobs (common/tracing.py). Field defaults come from
    the HORAEDB_TRACE_* env vars (via tracing.env_defaults), so build_app
    applying this config never clobbers an env override the operator set
    without a [tracing] section; an explicit config value wins over both."""

    # Sample rate in [0, 1]: 1 traces every request, 0 disables tracing
    # entirely (span() collapses to one contextvar get — the overhead
    # budget the bench acceptance bar holds).
    sample: float = field(
        default_factory=lambda: _tracing_mod.env_defaults()[0]
    )
    # Traces slower than this log a WARNING with the trace id.
    slow_threshold: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.millis(
            int(_tracing_mod.env_defaults()[1] * 1000)
        )
    )
    # Bounded in-memory ring of recent traces served at /debug/traces.
    ring_capacity: int = field(
        default_factory=lambda: _tracing_mod.env_defaults()[2]
    )

    @classmethod
    def from_dict(cls, d: dict | None) -> "TracingConfig":
        return _from_dict(cls, d)


@dataclass
class SlowlogConfig:
    """Slow-query flight recorder knobs (server/slowlog.py): the
    `capacity` slowest query requests spool — full trace tree + EXPLAIN —
    to `<object_store.data_dir>/slowlog/`, served at GET /debug/slowlog."""

    # How many entries to keep (the N in "N slowest"); 0 disables the
    # recorder entirely (no directory is created, no writes happen).
    capacity: int = 32
    # Requests faster than this never spool, even below capacity — keeps
    # a cold server from burning disk writes on its first N fast queries.
    min_duration: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.millis(0)
    )

    @classmethod
    def from_dict(cls, d: dict | None) -> "SlowlogConfig":
        return _from_dict(cls, d)


@dataclass
class Config:
    port: int = 5000
    test: TestConfig = field(default_factory=TestConfig)
    metric_engine: MetricEngineConfig = field(default_factory=MetricEngineConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    slowlog: SlowlogConfig = field(default_factory=SlowlogConfig)

    @classmethod
    def from_dict(cls, d: dict | None) -> "Config":
        return _from_dict(cls, d)

    @classmethod
    def from_toml(cls, text: str) -> "Config":
        return cls.from_dict(tomllib.loads(text))

    @classmethod
    def from_file(cls, path: str) -> "Config":
        with open(path, "rb") as f:
            return cls.from_dict(tomllib.load(f))

    def validate(self) -> None:
        ensure(
            0.0 <= self.tracing.sample <= 1.0,
            f"tracing.sample must be in [0, 1], got {self.tracing.sample}",
        )
        ensure(
            self.tracing.ring_capacity > 0,
            "tracing.ring_capacity must be positive",
        )
        ensure(
            self.slowlog.capacity >= 0,
            "slowlog.capacity must be >= 0 (0 disables the recorder)",
        )
        ing = self.metric_engine.ingest
        ensure(ing.flush_workers >= 1, "ingest.flush_workers must be >= 1")
        ensure(ing.flush_queue_max >= 1, "ingest.flush_queue_max must be >= 1")
        q = self.metric_engine.query
        ensure(q.max_concurrent >= 1, "query.max_concurrent must be >= 1")
        ensure(q.max_per_tenant >= 0,
               "query.max_per_tenant must be >= 0 (0 = the global cap)")
        ensure(q.queue_max >= 0, "query.queue_max must be >= 0")
        ensure(q.queue_deadline.seconds > 0,
               "query.queue_deadline must be positive")
        ensure(q.default_timeout.seconds > 0,
               "query.default_timeout must be positive")
        ensure(q.max_timeout.seconds >= q.default_timeout.seconds,
               "query.max_timeout must be >= query.default_timeout")
        ensure(q.max_cost_s >= 0, "query.max_cost_s must be >= 0")
        ensure(
            all(isinstance(v, (int, float)) and v > 0
                for v in q.tenant_weights.values()),
            "query.tenant_weights values must be positive numbers",
        )
        b = q.batching
        ensure(b.max_delay.seconds > 0,
               "query.batching.max_delay must be positive")
        ensure(b.max_group >= 2,
               "query.batching.max_group must be >= 2 (a group of one "
               "is the solo path; disable with batching.enabled=false)")
        ensure(b.max_stacked_cells >= 1,
               "query.batching.max_stacked_cells must be >= 1")
        ensure(b.max_rows >= 1, "query.batching.max_rows must be >= 1")
        ensure(
            self.metric_engine.limits.max_series >= 0,
            "limits.max_series must be >= 0 (0 disables the limit)",
        )
        rules = self.metric_engine.rules
        ensure(rules.eval_interval.seconds > 0,
               "rules.eval_interval must be positive")
        ensure(rules.tenant_weight > 0,
               "rules.tenant_weight must be positive")
        ensure(bool(rules.tenant), "rules.tenant must be non-empty")
        ensure(
            self.metric_engine.memory.memtrace in _memtrace_mod.MODES,
            f"memory.memtrace must be one of {sorted(_memtrace_mod.MODES)}, "
            f"got {self.metric_engine.memory.memtrace!r}",
        )
        tel = self.metric_engine.telemetry
        ensure(tel.scrape_interval.seconds > 0,
               "telemetry.scrape_interval must be positive")
        ensure(tel.max_series >= 0,
               "telemetry.max_series must be >= 0 (0 = unbudgeted)")
        ensure(bool(tel.tenant), "telemetry.tenant must be non-empty")
        ensure(tel.tenant_weight > 0,
               "telemetry.tenant_weight must be positive")
        fed = tel.federation
        ensure(fed.scrape_interval.seconds > 0,
               "telemetry.federation.scrape_interval must be positive")
        ensure(fed.timeout.seconds > 0,
               "telemetry.federation.timeout must be positive")
        ensure(fed.max_series >= 0,
               "telemetry.federation.max_series must be >= 0 "
               "(0 = unbudgeted)")
        if fed.enabled:
            ensure(self.metric_engine.cluster.enabled,
                   "telemetry.federation requires metric_engine.cluster "
                   "(peer scrapes pull from the cluster peer table)")
        if self.metric_engine.slo:
            ensure(rules.enabled,
                   "[[metric_engine.slo]] requires metric_engine.rules "
                   "enabled (the templates expand into rules)")
            # validate every block NOW: a typo'd SLO must fail boot, not
            # the first evaluator tick
            _telemetry_mod().expand_slos(self.metric_engine.slo)
        cl = self.metric_engine.cluster
        ensure(cl.role in ("writer", "replica"),
               f"cluster.role must be writer|replica, got {cl.role!r}")
        ensure(cl.watch_interval.seconds > 0,
               "cluster.watch_interval must be positive")
        ensure(cl.probe_interval.seconds > 0,
               "cluster.probe_interval must be positive")
        ensure(cl.watch_backoff_cap.seconds >= cl.watch_interval.seconds,
               "cluster.watch_backoff_cap must be >= watch_interval")
        if cl.enabled:
            ensure(bool(self.metric_engine.node_id),
                   "cluster.enabled requires metric_engine.node_id (the "
                   "node's identity in the assignment map and peer table)")
            if cl.role == "replica":
                ensure(
                    not self.test.enable_write,
                    "a replica cannot run the self-write load generator",
                )
        store = self.metric_engine.storage.object_store
        kind = store.type.lower()
        ensure(
            kind in ("local", "s3like"),
            f"unknown object_store type: {store.type!r} (Local | S3Like)",
        )
        if kind == "s3like":
            ensure(
                bool(store.endpoint and store.bucket),
                "S3Like object_store requires endpoint and bucket",
            )
        res = store.resilience
        ensure(
            res.retry.max_attempts >= 1,
            "object_store.resilience.retry.max_attempts must be >= 1",
        )
        ensure(
            res.breaker.failure_threshold >= 0,
            "object_store.resilience.breaker.failure_threshold must be "
            ">= 0 (0 disables the breaker)",
        )
