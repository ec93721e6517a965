"""MetricEngine facade: Prometheus-shaped writes and queries end-to-end.

Ties the three managers over four ColumnarStorage tables (one sub-root each:
{root}/{metrics,series,index,data}). The write path is the RFC pipeline:
populate metric ids -> populate series ids (registering new series + inverted
index entries) -> persist samples; the read path is index probe -> storage
scan with device predicate -> device aggregation.
"""

from __future__ import annotations

import copy
import hashlib
import logging
from dataclasses import dataclass, field

import numpy as np

from horaedb_tpu.common import tracing
from horaedb_tpu.common.error import ensure
from horaedb_tpu.common.time_ext import ReadableDuration, now_ms
from horaedb_tpu.engine import tables
from horaedb_tpu.engine.data import SampleManager
from horaedb_tpu.engine.index import IndexManager
from horaedb_tpu.engine.metric import MetricManager
from horaedb_tpu.ingest.cardinality import CardinalityLimited, SeriesSketch
from horaedb_tpu.ingest.types import ParsedWriteRequest
from horaedb_tpu.objstore import ObjectStore
from horaedb_tpu.server.metrics import GLOBAL_METRICS
from horaedb_tpu.storage.config import ColumnOptions, StorageConfig
from horaedb_tpu.storage.storage import ObjectBasedStorage
from horaedb_tpu.storage.types import TimeRange

logger = logging.getLogger(__name__)

NAME_LABEL = b"__name__"

DEFAULT_SEGMENT_MS = 2 * 3600_000  # 2h data segments

SERIES_CARDINALITY = GLOBAL_METRICS.gauge(
    "horaedb_series_cardinality",
    help="HLL-sketch estimate of distinct (metric, tsid) series this "
         "table has ever ingested (ingest/cardinality.py; seeded from "
         "the index at open). The cardinality-explosion early-warning "
         "signal, and the value the max_series limit compares against.",
    labelnames=("table",),
)
CARD_REJECTED_SAMPLES = GLOBAL_METRICS.counter(
    "horaedb_cardinality_rejected_samples_total",
    help="Samples dropped because their series was NEW while the table "
         "sat at its series-cardinality limit (partial-accept 503s; "
         "existing-series samples in the same request were accepted).",
    labelnames=("table",),
)
CARD_REJECTED_SERIES = GLOBAL_METRICS.counter(
    "horaedb_cardinality_rejected_series_total",
    help="Distinct new-series registrations rejected at the "
         "series-cardinality limit (per request; a series retried across "
         "requests counts each time).",
    labelnames=("table",),
)
CARD_LIMITED_REQUESTS = GLOBAL_METRICS.counter(
    "horaedb_cardinality_limited_requests_total",
    help="Write requests answered with the 503/Retry-After "
         "partial-accept because the series-cardinality limit rejected "
         "at least one new series.",
    labelnames=("table",),
)
TOMBSTONES_CREATED = GLOBAL_METRICS.counter(
    "horaedb_tombstones_created_total",
    help="Tombstone delete records created via the delete API, by table "
         "root (applied at scan time immediately, physically at "
         "compaction; horaedb_tombstones_applied_total tracks rows).",
    labelnames=("table",),
)


def sample_table_config(config: StorageConfig | None) -> StorageConfig:
    """Data/exemplars-table write config with measured encoding defaults.

    The RFC floats a custom compressed sample payload (delta-of-delta
    timestamps + XOR values packed into opaque bytes, RFC :218-232).
    Measured on realistic scrape-shaped data (benchmarks/
    compression_bench.py): parquet's own DELTA_BINARY_PACKED (int lanes)
    + BYTE_STREAM_SPLIT/zstd (values) beats that design — smaller than
    the byte-aligned gorilla variant AND decode stays columnar/vectorized,
    so scans get faster, not slower. These are therefore the sample-table
    defaults; explicit user column_options always win.

    Each default carries enable_dict=False: parquet rejects an explicit
    column_encoding for a dictionary-encoded column, so the tuned columns
    opt out of dictionary mode individually — a user's global
    enable_dict=true still applies to every other column."""
    cfg = copy.deepcopy(config) if config is not None else StorageConfig()
    opts = dict(cfg.write.column_options or {})
    defaults = {
        "metric_id": "DELTA_BINARY_PACKED",
        "tsid": "DELTA_BINARY_PACKED",
        "field_id": "DELTA_BINARY_PACKED",
        "ts": "DELTA_BINARY_PACKED",
        "value": "BYTE_STREAM_SPLIT",
    }
    for name, enc in defaults.items():
        opts.setdefault(name, ColumnOptions(
            enable_dict=False, encoding=enc,
            compression="zstd" if name == "value" else None,
        ))
    cfg.write.column_options = opts
    return cfg


@dataclass
class QueryRequest:
    metric: bytes
    start_ms: int
    end_ms: int
    filters: list[tuple[bytes, bytes]] = field(default_factory=list)
    # Prometheus-style extended matchers: (key, op, pattern) with op in
    # "ne" (!=), "re" (=~ full match), "nre" (!~)
    matchers: list[tuple[bytes, str, bytes]] = field(default_factory=list)
    bucket_ms: int | None = None  # None -> raw rows
    # Raw-row limit PUSHED INTO the scan: segments stop being read once
    # `limit` merged rows have accumulated (segments scan old->new), so a
    # 100M-row table queried with limit=100k pays ~100k rows of work, not
    # full materialization. None = unbounded. Ignored for bucketed queries.
    limit: int | None = None
    # Region restriction for the distributed scatter-gather read path:
    # None = all regions (the single-node behavior); a list restricts
    # `query_partial_grids` to exactly these region shards — each
    # computing node receives its assigned subset here. Ignored by the
    # plain `query` surface (whole queries always see every region).
    regions: "list[int] | None" = None


class MetricEngine:
    def __init__(self) -> None:
        raise RuntimeError("use MetricEngine.open")

    @classmethod
    async def open(
        cls,
        root: str,
        store: ObjectStore,
        segment_duration_ms: int = DEFAULT_SEGMENT_MS,
        config: StorageConfig | None = None,
        enable_compaction: bool = True,
        ingest_buffer_rows: int = 0,
        flush_workers: int = 2,
        flush_queue_max: int = 4,
        flush_stall_deadline_s: float = 30.0,
        sst_executor=None,
        manifest_executor=None,
        parser_pool=None,
        fence_node_id: str | None = None,
        fence_validate_interval_s: float = 5.0,
        retention_period_ms: int | None = None,
        max_series: int = 0,
        serving=None,
        read_only: bool = False,
    ) -> "MetricEngine":
        """`ingest_buffer_rows` > 0 buffers data-table rows across writes
        and flushes as one SST per segment when the threshold is reached
        (see SampleManager.__init__ for the durability trade-off);
        `flush_workers`/`flush_queue_max`/`flush_stall_deadline_s` size the
        background flush executor (engine/flush_executor.py) that decouples
        the append hot path from drain/encode/upload work.
        `sst_executor`/`manifest_executor` size CPU-heavy storage work
        (ThreadConfig, see ObjectBasedStorage.try_new). `parser_pool` shares
        the caller's ParserPool (so e.g. the server's pool telemetry covers
        engine ingest); None = engine creates its own on first use.
        `fence_node_id` claims exclusive write ownership of this engine
        root: ONE epoch fence covers all six tables (the region is the
        ownership unit, RFC :28-76); a later claimant deposes this process
        and its writes fail with FencedError (storage/fence.py).

        `retention_period_ms`: samples older than now - period stop
        existing — row-exact at scan time (storage/visibility.py), and the
        compaction scheduler's TTL expires whole SSTs physically. Applies
        to the data + exemplars tables only (the registration tables hold
        definitions, not samples). None = keep forever.

        `max_series`: per-engine series-cardinality limit enforced by an
        HLL sketch on the ingest path (ingest/cardinality.py): once the
        estimate reaches the limit, NEW series are rejected with a
        503/Retry-After partial-accept while existing-series samples keep
        landing. 0 = unlimited (the sketch still runs and exports
        horaedb_series_cardinality).

        `serving`: ServingTierConfig for the dashboard serving tier
        (horaedb_tpu/serving — compaction-time rollups and the result
        cache). None = defaults (ON: the tier is bit-exact vs
        forced-cold scans by construction).

        `read_only`: cluster replica mode (horaedb_tpu/cluster): open a
        read-only VIEW over a root a writer process owns on the shared
        store — no fence, no compaction, no flush pipeline, no sidecar
        dumps; every mutation raises ReplicaReadOnlyError. Queries work
        unchanged with bounded staleness (the replica's watch loop swaps
        in fresh views)."""
        from horaedb_tpu.serving import ServingTier

        self = object.__new__(cls)
        self._read_only = read_only
        if read_only:
            fence_node_id = None
            enable_compaction = False
            ingest_buffer_rows = 0
        self._store = store
        self._segment_duration = segment_duration_ms
        self._pool = parser_pool
        self._table_label = root.strip("/")
        self._max_series = int(max_series)
        self._sketch = SeriesSketch()
        self._card_events = 0
        for fam in (CARD_REJECTED_SAMPLES, CARD_REJECTED_SERIES,
                    CARD_LIMITED_REQUESTS, TOMBSTONES_CREATED):
            fam.labels(self._table_label)
        SERIES_CARDINALITY.labels(self._table_label).set(0)

        fence = None
        if fence_node_id is not None:
            from horaedb_tpu.storage.fence import EpochFence

            fence = await EpochFence.acquire(
                store, root.strip("/"), fence_node_id,
                validate_interval_s=fence_validate_interval_s,
            )
        self._fence = fence

        self.serving = ServingTier(serving)
        sample_cfg = sample_table_config(config)
        # serving tier layer a: compaction-time rollups on the sample
        # tables (emission only ever runs where a compaction scheduler
        # exists — the data table). User storage-config overrides win.
        if not sample_cfg.rollup.enabled:
            sample_cfg.rollup.enabled = (
                self.serving.config.enabled
                and self.serving.config.rollup_enabled
            )
            sample_cfg.rollup.resolutions = list(
                self.serving.config.rollup_resolutions
            )
        if retention_period_ms is not None and retention_period_ms > 0:
            # single source of truth: the compaction scheduler's TTL drives
            # BOTH physical expiry (picker expireds + the expired-only task)
            # and scan-time retention masking (storage.retention_floor_ms).
            # Sample-bearing tables only — retention must never expire
            # metric/series/index/tags registrations.
            sample_cfg.scheduler.ttl = ReadableDuration.millis(
                int(retention_period_ms)
            )

        async def open_table(name, schema, num_pks, compaction):
            sample_table = name in ("data", "exemplars")
            return await ObjectBasedStorage.try_new(
                root=f"{root}/{name}",
                store=store,
                arrow_schema=schema,
                num_primary_keys=num_pks,
                segment_duration_ms=segment_duration_ms,
                # sample-bearing tables get the measured encoding defaults
                config=sample_cfg if sample_table else config,
                enable_compaction_scheduler=compaction,
                sst_executor=sst_executor,
                manifest_executor=manifest_executor,
                fence=fence,
                # row-exact retention + time-range tombstone deletes
                # (storage/visibility.py) need the schema's time column
                time_column="ts" if sample_table else None,
                read_only=read_only,
            )

        self.metrics_table = await open_table(
            "metrics", tables.METRICS_SCHEMA, tables.METRICS_NUM_PKS, False
        )
        self.series_table = await open_table(
            "series", tables.SERIES_SCHEMA, tables.SERIES_NUM_PKS, False
        )
        self.index_table = await open_table(
            "index", tables.INDEX_SCHEMA, tables.INDEX_NUM_PKS, False
        )
        self.tags_table = await open_table(
            "tags", tables.TAGS_SCHEMA, tables.TAGS_NUM_PKS, False
        )
        self.data_table = await open_table(
            "data", tables.DATA_SCHEMA, tables.DATA_NUM_PKS, enable_compaction
        )
        self.exemplars_table = await open_table(
            "exemplars", tables.EXEMPLARS_SCHEMA, tables.EXEMPLARS_NUM_PKS, False
        )

        self.metric_mgr = MetricManager(self.metrics_table, segment_duration_ms)
        self.index_mgr = IndexManager(
            self.series_table, self.index_table, segment_duration_ms,
            # base sidecar lives beside the two tables it caches, in a
            # namespace neither table's manifest/data layout touches
            sidecar_store=store,
            sidecar_path=f"{root}/index_sidecar/base.arrow",
            tags_storage=self.tags_table,
            read_only=read_only,
        )
        # Payload-shape fingerprint cache: scrapers resend the same series
        # set every interval, so the (metric_id, tsid) lane BYTES repeat
        # exactly payload-over-payload. A hit proves this exact lane-set was
        # fully registered (entries are added only after durable
        # registration), collapsing steady-state id resolution to one set
        # probe. Keys are 16-byte blake2b digests of the lane bytes — fixed
        # memory (64 KB at the 4096-entry cap) even for 10k-series payloads
        # whose shapes churn, at cryptographic collision resistance.
        self._lanes_fp: set[bytes] = set()
        self.sample_mgr = SampleManager(
            self.data_table, segment_duration_ms,
            buffer_rows=ingest_buffer_rows,
            flush_workers=flush_workers,
            flush_queue_max=flush_queue_max,
            flush_stall_deadline_s=flush_stall_deadline_s,
            serving=self.serving,
        )
        self.exemplar_mgr = SampleManager(
            self.exemplars_table, segment_duration_ms, serving=self.serving,
        )
        await self.metric_mgr.open()
        await self.index_mgr.open()
        # seed the cardinality sketch from the index the open just loaded:
        # the estimate (and the limit) survive restarts without any extra
        # durable state
        mids, tsids = self.index_mgr.series_lanes()
        self._sketch.add_pairs(mids, tsids)
        SERIES_CARDINALITY.labels(self._table_label).set(
            round(self._sketch.estimate())
        )
        return self

    def sub_engines(self) -> "dict[str, MetricEngine]":
        """Uniform enumeration for observability surfaces — one unpartitioned
        engine; RegionedEngine returns one entry per region."""
        return {"": self}

    @property
    def read_only(self) -> bool:
        """True in cluster replica mode (see `open`'s read_only)."""
        return self._read_only

    def manifest_epoch(self) -> int:
        """Monotonic catch-up token over ALL six tables' manifests: the
        replica's view matches the writer's exactly when the epochs are
        equal (cluster/replica.py floors it so the surfaced token never
        moves backwards across GC)."""
        return max(
            t.manifest_epoch()
            for t in (self.metrics_table, self.series_table,
                      self.index_table, self.tags_table,
                      self.data_table, self.exemplars_table)
        )

    def _ensure_writable(self, what: str) -> None:
        if self._read_only:
            from horaedb_tpu.common.error import ReplicaReadOnlyError

            raise ReplicaReadOnlyError(
                f"engine {self._table_label} is a read-only replica view; "
                f"refusing {what} (route the mutation to the owning writer)"
            )

    async def flush(self) -> None:
        """Flush any buffered ingest rows to durable SSTs (waits out any
        in-flight background flush first)."""
        await self.sample_mgr.drain()

    async def close(self) -> None:
        await self.flush()
        # quiesced now: fold the index into its sidecar so the next open
        # replays nothing (best-effort — open rebuilds from the tables if
        # this never lands)
        try:
            await self.index_mgr.dump_sidecar()
        except Exception:  # noqa: BLE001
            logger.warning("index sidecar dump failed; next open will rebuild",
                           exc_info=True)
        for t in (
            self.metrics_table,
            self.series_table,
            self.index_table,
            self.tags_table,
            self.data_table,
            self.exemplars_table,
        ):
            await t.close()

    # -- write path -----------------------------------------------------------
    def metadata(self) -> dict[bytes, str]:
        """Metric-family metadata (family name -> prom type string)."""
        return dict(self.metric_mgr.metadata)

    def _record_metadata(self, req: ParsedWriteRequest) -> None:
        """Fold remote-write METADATA records (family name -> prom type)
        into the advisory metadata cache (served at /api/v1/metadata)."""
        for i in range(len(req.meta_type)):
            self.metric_mgr.record_metadata(
                req.meta_name(i), int(req.meta_type[i])
            )

    async def write_parsed(self, req: ParsedWriteRequest) -> int:
        """Ingest one decoded remote-write request; returns sample count.

        When the native parser supplied metric-id/tsid hash lanes
        (ingest/types.py), id resolution is pure numpy + set probes — no
        per-series label slicing or Python seahash (the reference hash
        contract lives in C++, src/metric_engine/src/types.rs:18-41)."""
        self._ensure_writable("write_parsed")
        if len(req.meta_type):
            self._record_metadata(req)
        if req.n_series == 0:
            return 0
        if req.series_tsid is not None:
            return await self._write_parsed_fast(req)
        ts_now = now_ms()
        # 1. metric names from __name__ labels
        names: list[bytes] = []
        label_sets: list[list[tuple[bytes, bytes]]] = []
        for s in range(req.n_series):
            labels = req.series_labels(s)
            name = b""
            rest = []
            for k, v in labels:
                if k == NAME_LABEL:
                    name = v
                else:
                    rest.append((k, v))
            ensure(bool(name), f"series {s} missing __name__ label")
            names.append(name)
            label_sets.append(rest)
        ids = await self.metric_mgr.populate_metric_ids(names, ts_now)
        metric_per_series = [ids[n] for n in names]
        # 2. cardinality gate (the pure-Python path derives the tsids it
        # needs for the known-series probe — only once the estimate has
        # already crossed the limit, so the hot case pays nothing)
        rejected = None
        if self._max_series and self._sketch.estimate() >= self._max_series:
            from horaedb_tpu.engine.types import series_id_of, series_key_of

            pred_tsids = np.fromiter(
                (series_id_of(series_key_of(ls)) for ls in label_sets),
                dtype=np.uint64, count=len(label_sets),
            )
            marr = np.asarray(metric_per_series, dtype=np.uint64)
            known = self.index_mgr.known_pairs_mask(marr, pred_tsids)
            if not bool(known.all()):
                rejected = ~known
        # series registration + tsids (accepted series only under the gate)
        if rejected is None:
            tsids = np.asarray(await self.index_mgr.populate_series_ids(
                metric_per_series, label_sets, ts_now
            ), dtype=np.uint64)
        else:
            acc = np.flatnonzero(~rejected)
            acc_list = acc.tolist()
            acc_tsids = await self.index_mgr.populate_series_ids(
                [metric_per_series[i] for i in acc_list],
                [label_sets[i] for i in acc_list], ts_now,
            )
            tsids = np.zeros(req.n_series, dtype=np.uint64)
            tsids[acc] = np.asarray(acc_tsids, dtype=np.uint64)
        # 3. samples -> data rows
        n = req.n_samples
        metric_arr = np.asarray(metric_per_series, dtype=np.uint64)
        tsid_arr = tsids
        self._feed_sketch(
            metric_arr if rejected is None else metric_arr[~rejected],
            tsid_arr if rejected is None else tsid_arr[~rejected],
        )
        card_accept = card_reject = 0
        if n:
            series_idx = req.sample_series
            if rejected is not None:
                keep = ~rejected[series_idx]
                card_accept = int(np.count_nonzero(keep))
                card_reject = n - card_accept
                sel = np.flatnonzero(keep)
                series_idx = series_idx[sel]
                if card_accept:
                    await self.sample_mgr.persist(
                        metric_arr[series_idx], tsid_arr[series_idx],
                        req.sample_ts[sel], req.sample_value[sel],
                    )
            else:
                await self.sample_mgr.persist(
                    metric_arr[series_idx], tsid_arr[series_idx],
                    req.sample_ts, req.sample_value,
                )
        # 4. exemplars -> exemplars table (with their labels: trace ids are
        # the entire point of exemplars)
        if len(req.exemplar_value):
            await self._persist_exemplars(
                req, metric_arr, tsid_arr,
                keep_series=None if rejected is None else ~rejected,
            )
        if rejected is not None:
            self._raise_cardinality(
                int(np.count_nonzero(rejected)), card_reject, card_accept
            )
        return n

    def _cardinality_gate(self, metric_arr, tsid_arr) -> "np.ndarray | None":
        """Per-series rejection mask when the table sits at its series
        limit, else None. Cheap until the limit is actually reached (one
        cached-estimate compare); only then does it pay the per-pair
        known-series probes to tell existing traffic from the explosion."""
        if not self._max_series:
            return None
        if self._sketch.estimate() < self._max_series:
            return None
        known = self.index_mgr.known_pairs_mask(metric_arr, tsid_arr)
        if known.all():
            return None
        return ~known

    def _feed_sketch(self, metric_arr, tsid_arr) -> None:
        if self._sketch.add_pairs(metric_arr, tsid_arr):
            SERIES_CARDINALITY.labels(self._table_label).set(
                round(self._sketch.estimate())
            )

    def _raise_cardinality(
        self, rejected_series: int, rejected_samples: int,
        accepted_samples: int,
    ) -> None:
        """Count + sampled-log one partial-accept, then raise the typed
        overload signal (503/Retry-After at the HTTP layer). Raised AFTER
        the accepted samples were persisted/buffered — the ack contract
        for in-budget traffic is unchanged."""
        t = self._table_label
        CARD_REJECTED_SERIES.labels(t).inc(rejected_series)
        CARD_REJECTED_SAMPLES.labels(t).inc(rejected_samples)
        CARD_LIMITED_REQUESTS.labels(t).inc()
        self._card_events += 1
        if self._card_events == 1 or self._card_events % 100 == 0:
            logger.warning(
                "series cardinality limit on %s: rejected %d new series "
                "(%d samples), accepted %d samples (event %d, est ~%.0f, "
                "limit %d)",
                t, rejected_series, rejected_samples, accepted_samples,
                self._card_events, self._sketch.estimate(), self._max_series,
            )
        raise CardinalityLimited(
            table=t, limit=self._max_series,
            estimate=self._sketch.estimate(),
            accepted_samples=accepted_samples,
            rejected_samples=rejected_samples,
            rejected_series=rejected_series,
        )

    async def _resolve_ids_fast(self, req: ParsedWriteRequest):
        """Hash-lane id resolution: validate names, register unseen metrics
        and series. Returns (metric_arr, tsid_arr, rejected) — u64 lanes
        per series plus the cardinality-limit rejection mask (None in the
        overwhelmingly common in-budget case; True entries are NEW series
        that were NOT registered and whose samples the caller must drop
        and account via _raise_cardinality)."""
        ts_now = now_ms()
        name_len = req.series_name_len
        if np.any(name_len < 0):
            s = int(np.argmax(name_len < 0))
            ensure(False, f"series {s} missing __name__ label")
        metric_arr = req.series_metric_id
        tsid_arr = req.series_tsid
        # steady-state fast path: the exact lane bytes were seen (and their
        # series durably registered) before — one set probe, no per-series
        # Python work (registered series are by definition in-budget)
        h = hashlib.blake2b(metric_arr.tobytes(), digest_size=16)
        h.update(tsid_arr.tobytes())
        fp = h.digest()
        if fp in self._lanes_fp:
            return metric_arr, tsid_arr, None
        # 0. cardinality gate BEFORE any registration: at the limit, new
        # series must not bloat the metrics/series/index tables either
        rejected = self._cardinality_gate(metric_arr, tsid_arr)
        acc = None if rejected is None else np.flatnonzero(~rejected)
        m_acc = metric_arr if acc is None else metric_arr[acc]
        t_acc = tsid_arr if acc is None else tsid_arr[acc]
        # 1. register unseen metrics (rare after warmup), accepted series only
        new_ids = self.metric_mgr.unknown_ids(m_acc)
        if len(new_ids):
            new_set = set(new_ids.tolist())
            seen: dict[int, bytes] = {}
            series_iter = range(req.n_series) if acc is None else acc.tolist()
            for s in series_iter:
                m = int(metric_arr[s])
                if m in new_set and m not in seen:
                    seen[m] = req.series_name(s)
            ensure(all(seen.values()), "series missing __name__ label")
            await self.metric_mgr.register_named(
                list(seen.values()), list(seen.keys()), ts_now
            )
        # 2. register unseen series (accepted only; index accessors take
        # positions into the subset, so remap through `acc`)
        if acc is None:
            await self.index_mgr.ensure_series_fast(
                metric_arr, tsid_arr, req.series_key, ts_now,
                tag_rows_of=req.series_tag_rows,
            )
        else:
            idx = acc.tolist()
            await self.index_mgr.ensure_series_fast(
                m_acc, t_acc,
                (lambda i: req.series_key(idx[i])), ts_now,
                tag_rows_of=(lambda i: req.series_tag_rows(idx[i])),
            )
        self._feed_sketch(m_acc, t_acc)
        if rejected is not None:
            # a partially-accepted shape is NOT fully registered: never
            # fingerprint it, or a later in-budget retry would skip
            # registration of the still-missing series
            return metric_arr, tsid_arr, rejected
        # everything in these lanes is now durably registered — remember
        # the shape (bounded: scrape fleets send a few distinct shapes)
        if len(self._lanes_fp) >= 4096:
            self._lanes_fp.clear()
        self._lanes_fp.add(fp)
        return metric_arr, tsid_arr, None

    async def write_payload(self, payload: bytes) -> int:
        """Parse + ingest one wire payload end-to-end. With native buffering
        active (ingest_buffer_rows > 0 and the C++ library available),
        samples move straight from the parser arena into the C++
        accumulator — no Python-side sample materialization at all.

        Borrow discipline: the pool slot is held only for the arena-touching
        steps (parse, id resolution, accum add). Steady-state resolution has
        no awaits; only new-series registration persists while borrowed
        (series keys/names must come from the arena, and they are
        materialized to owned bytes before the await). Exemplar persistence
        and threshold flushes use owned copies and run after release."""
        import asyncio

        self._ensure_writable("write_payload")

        from horaedb_tpu.ingest import ParserPool

        from horaedb_tpu.ingest.pooled_parser import STAGES

        if self._pool is None:
            self._pool = ParserPool()
        if not self.sample_mgr.native_accum_active:
            parsed = await self._pool.decode(payload)
            with tracing.span("append", samples=parsed.n_samples):
                return await self.write_parsed(parsed)
        from horaedb_tpu.ingest.native import NativeParser

        total = 0
        async with self._pool.borrow() as parser:
            if not isinstance(parser, NativeParser):
                with tracing.span("parse", bytes=len(payload)), \
                        STAGES.stage("parse"):
                    parsed = await asyncio.to_thread(
                        STAGES.on_worker, "parse", parser.parse, payload)
                with tracing.span("append", samples=parsed.n_samples):
                    return await self.write_parsed(parsed)
            # small payloads parse inline: the native parse runs ~1 GB/s, so
            # a sub-256KB payload blocks the loop far less than a thread
            # handoff costs (~100us)
            with tracing.span("parse", bytes=len(payload)), \
                    STAGES.stage("parse"):
                if len(payload) <= 256 * 1024:
                    req = parser.parse_light(payload)
                else:
                    req = await asyncio.to_thread(
                        STAGES.on_worker, "parse", parser.parse_light, payload)
            if len(req.meta_type):
                self._record_metadata(req)
            if req.n_series == 0:
                return 0
            rejected = None
            card_accept = card_reject = 0
            with tracing.span("append", samples=req.n_samples):
                metric_arr, tsid_arr, rejected = \
                    await self._resolve_ids_fast(req)
                if len(req.exemplar_value) or rejected is not None:
                    # the id lanes may be views into the borrowed parser's
                    # decode arena (pooled_parser.DecodeArena) — exemplar
                    # persistence (and the rejection raise below) runs
                    # after release, so own them first
                    metric_arr = np.array(metric_arr)
                    tsid_arr = np.array(tsid_arr)
                if req.n_samples and rejected is None:
                    total = self.sample_mgr.buffer_native_add(parser)
                elif req.n_samples:
                    # cardinality-limit degradation: the all-or-nothing C++
                    # accumulator can't take a subset, so this (rare,
                    # already-throttled) payload materializes its sample
                    # lanes and buffers only existing-series samples —
                    # in-budget traffic is never lost
                    vals, ts, series = parser.sample_lanes()
                    keep = ~rejected[series]
                    card_accept = int(np.count_nonzero(keep))
                    card_reject = len(series) - card_accept
                    if card_accept:
                        sel = np.flatnonzero(keep)
                        s_idx = series[sel]
                        # persist() runs its own threshold seal, so the
                        # post-borrow should_flush below stays untriggered
                        # (total stays 0) — a near-empty active memtable
                        # must not seal into a tiny SST just because the
                        # flush executor already holds pending rows
                        await self.sample_mgr.persist(
                            metric_arr[s_idx], tsid_arr[s_idx],
                            ts[sel], vals[sel],
                        )
        if len(req.exemplar_value):
            await self._persist_exemplars(
                req, metric_arr, tsid_arr,
                keep_series=None if rejected is None else ~rejected,
            )
        if rejected is not None:
            self._raise_cardinality(
                int(np.count_nonzero(rejected)), card_reject, card_accept
            )
        if total and self.sample_mgr.should_flush(total):
            # hand the sealed memtable to the background flush executor:
            # drain/encode/upload overlap continued ingest, and a FULL
            # flush queue blocks here with a stall deadline (backpressure
            # -> 5xx -> sender retries) instead of acking rows into an
            # unbounded buffer
            await self.sample_mgr.seal_and_submit()
        if self.sample_mgr.flush_in_flight:
            # cooperative yield: the steady write path never suspends, so a
            # driver hammering write_payload back-to-back would starve the
            # flush workers; one loop turn per payload lets their
            # thread-offload completions schedule (a real server yields at
            # socket reads)
            await asyncio.sleep(0)
        return req.n_samples

    async def _write_parsed_fast(self, req: ParsedWriteRequest) -> int:
        """Hash-lane write path: per-series ids come from the C++ parser."""
        metric_arr, tsid_arr, rejected = await self._resolve_ids_fast(req)
        # 3. samples
        n = req.n_samples
        card_accept = card_reject = 0
        if n:
            if rejected is not None:
                # partial accept at the cardinality limit: only
                # existing-series samples are buffered/persisted
                series_idx = req.sample_series
                keep = ~rejected[series_idx]
                card_accept = int(np.count_nonzero(keep))
                card_reject = n - card_accept
                if card_accept:
                    sel = np.flatnonzero(keep)
                    s_idx = series_idx[sel]
                    await self.sample_mgr.persist(
                        metric_arr[s_idx], tsid_arr[s_idx],
                        req.sample_ts[sel], req.sample_value[sel],
                    )
            elif self.sample_mgr.buffering:
                await self.sample_mgr.buffer_request(metric_arr, tsid_arr, req)
            else:
                series_idx = req.sample_series
                await self.sample_mgr.persist(
                    metric_arr[series_idx], tsid_arr[series_idx],
                    req.sample_ts, req.sample_value,
                )
        if len(req.exemplar_value):
            await self._persist_exemplars(
                req, metric_arr, tsid_arr,
                keep_series=None if rejected is None else ~rejected,
            )
        if rejected is not None:
            self._raise_cardinality(
                int(np.count_nonzero(rejected)), card_reject, card_accept
            )
        return n

    async def _persist_exemplars(
        self, req: ParsedWriteRequest, metric_arr, tsid_arr,
        keep_series: "np.ndarray | None" = None,
    ) -> None:
        import pyarrow as pa

        from horaedb_tpu.engine.types import series_key_of
        from horaedb_tpu.storage.read import WriteRequest as StorageWrite

        ex_idx = req.exemplar_series
        ts = req.exemplar_ts
        vals = req.exemplar_value
        ex_pos = np.arange(len(vals))
        if keep_series is not None:
            # cardinality partial-accept: exemplars of rejected series drop
            # with their samples
            sel = np.flatnonzero(keep_series[ex_idx])
            if not len(sel):
                return
            ex_idx = ex_idx[sel]
            ts = ts[sel]
            vals = vals[sel]
            ex_pos = sel
        m = metric_arr[ex_idx]
        t = tsid_arr[ex_idx]
        labels = [
            series_key_of(req.exemplar_labels(int(i))) for i in ex_pos
        ]
        seg = ts - (ts % self._segment_duration)
        for seg_start in np.unique(seg):
            msk = seg == seg_start
            idxs = np.nonzero(msk)[0]
            batch = pa.RecordBatch.from_pydict(
                {
                    "metric_id": m[msk].astype(np.uint64),
                    "tsid": t[msk].astype(np.uint64),
                    "ts": ts[msk],
                    "value": vals[msk],
                    "labels": [labels[i] for i in idxs],
                },
                schema=tables.EXEMPLARS_SCHEMA,
            )
            lo, hi = int(ts[msk].min()), int(ts[msk].max()) + 1
            await self.exemplars_table.write(StorageWrite(batch, TimeRange(lo, hi)))

    # -- query path -------------------------------------------------------------
    def _resolve_query(
        self, metric: bytes, filters, matchers=None
    ) -> tuple[int, list | None] | None:
        """Shared lookup prologue: metric id + TSID candidates, or None when
        the metric is unknown / no series matches the filters."""
        hit = self.metric_mgr.get(metric)
        if hit is None:
            return None
        tsids = self.index_mgr.find_tsids(hit[0], filters, matchers)
        if tsids == []:
            return None
        return hit[0], tsids

    async def _resolve_query_async(self, req: QueryRequest):
        """Regex matchers evaluate in a worker thread: Python re has no
        linear-time guarantee and must not stall the event loop."""
        import asyncio

        if req.matchers:
            return await asyncio.to_thread(
                self._resolve_query, req.metric, req.filters, req.matchers
            )
        return self._resolve_query(req.metric, req.filters, req.matchers)

    async def query(self, req: QueryRequest):
        """Raw rows (bucket_ms None) or downsample grids per series."""
        from horaedb_tpu.common import deadline as deadline_ctx

        # cooperative end-to-end deadline (common/deadline.py): a query
        # whose budget is already spent must not pay resolution + scan
        deadline_ctx.check("query_resolve")
        resolved = await self._resolve_query_async(req)
        if resolved is None:
            return None
        metric_id, tsids = resolved
        rng = TimeRange(req.start_ms, req.end_ms)
        if req.bucket_ms is None:
            return await self.sample_mgr.query_raw(
                metric_id, tsids, rng, limit=req.limit
            )
        filtered = tsids is not None
        if tsids is None:  # no tag filter: all series of the metric
            tsids = self.index_mgr.series_of(metric_id)
        return await self.sample_mgr.query_downsample(
            metric_id, tsids, rng, req.bucket_ms, filtered=filtered
        )

    async def query_partial_grids(self, req: QueryRequest):
        """Distributed scatter-gather leaf: per-region partial grids as
        [(region_id, tsids, grids)]. A plain (un-regioned) engine is one
        region — id 0 — and answers only when the restriction includes
        it. Runs the NORMAL downsample query path (serving cache,
        rollups, encoding, admission on the serving node all apply); the
        coordinator folds fragments with cluster/partial.merge_partials
        in canonical region order so the distributed result is
        bit-exact vs single-node."""
        from horaedb_tpu.common.error import ensure

        ensure(req.bucket_ms is not None,
               "query_partial_grids requires a bucketed (grid) query")
        if req.regions is not None and 0 not in [int(r) for r in req.regions]:
            return []
        out = await self.query(req)
        if out is None:
            return []
        tsids, grids = out
        return [(0, tsids, grids)]

    async def query_exemplars(self, req: QueryRequest):
        """Raw exemplar rows (incl. their labels) for a metric."""
        resolved = await self._resolve_query_async(req)
        if resolved is None:
            return None
        metric_id, tsids = resolved
        return await self.exemplar_mgr.query_raw(
            metric_id, tsids, TimeRange(req.start_ms, req.end_ms), limit=req.limit
        )

    def label_values(self, metric: bytes, key: bytes) -> list[bytes]:
        hit = self.metric_mgr.get(metric)
        if hit is None:
            return []
        return self.index_mgr.label_values(hit[0], key)

    async def label_values_storage(self, metric: bytes, key: bytes) -> list[bytes]:
        """LabelValues from the durable tags table (RFC :118-130) — agrees
        with `label_values` (tested); see IndexManager.label_values_storage
        for when to prefer which."""
        hit = self.metric_mgr.get(metric)
        if hit is None:
            return []
        return await self.index_mgr.label_values_storage(hit[0], key)

    def metric_names(self) -> list[bytes]:
        """All registered metric names (the /api/v1/metrics surface)."""
        return self.metric_mgr.names()

    def series_count(self, metric: bytes) -> int:
        """Registered series of a metric (in-memory index lookup, no IO).
        The admission scheduler's cost model sizes grid queries with
        this (server/admission.py); 0 for unknown metrics."""
        hit = self.metric_mgr.get(metric)
        if hit is None:
            return 0
        return len(self.index_mgr.series_of(hit[0]))

    def label_names(self) -> list[bytes]:
        """All label KEYS across every registered series (the
        /api/v1/labels no-match[] surface; `__name__` is the endpoint's
        concern). Public like `metric_names` so regioned deployments can
        answer via fan-out instead of reaching into the managers."""
        names: set[bytes] = set()
        for metric in self.metric_mgr.names():
            hit = self.metric_mgr.get(metric)
            if hit is None:
                continue
            for labs in self.index_mgr.series_labels(hit[0]).values():
                names.update(labs)
        return sorted(names)

    def series(self, metric: bytes) -> list[dict[str, str]]:
        """Label sets of every series of a metric (the /api/v1/series
        surface), including tagless series."""
        hit = self.metric_mgr.get(metric)
        if hit is None:
            return []
        per_tsid = self.index_mgr.series_labels(hit[0])
        return [
            {k.decode(errors="replace"): v.decode(errors="replace")
             for k, v in labels.items()} | {"__tsid__": str(t)}
            for t, labels in sorted(per_tsid.items())
        ]

    def series_labels_map(
        self, metric: bytes, tsids: "list[int] | None" = None
    ) -> dict[int, dict[bytes, bytes]]:
        """tsid -> raw label map for a metric, optionally restricted to
        `tsids` (so a selective query never decodes the whole metric's
        series). PromQL/discovery surface — implemented by RegionedEngine
        too (fan-out union)."""
        hit = self.metric_mgr.get(metric)
        if hit is None:
            return {}
        per_tsid = self.index_mgr.series_labels(hit[0])
        if tsids is None:
            return per_tsid
        return {t: per_tsid[t] for t in tsids if t in per_tsid}

    async def match_series(
        self, metric: bytes, filters, matchers
    ) -> dict[int, dict[bytes, bytes]]:
        """Matched tsid -> label map (Prometheus match[] resolution). Regex
        matchers evaluate off the event loop — same safeguard as queries
        (_resolve_query_async): Python `re` has no linear-time guarantee."""
        resolved = await self._resolve_query_async(
            QueryRequest(metric=metric, start_ms=0, end_ms=1,
                         filters=filters, matchers=matchers)
        )
        if resolved is None:
            return {}
        metric_id, tsids = resolved
        per_tsid = self.index_mgr.series_labels(metric_id)
        if tsids is None:
            return per_tsid
        return {t: per_tsid[t] for t in tsids if t in per_tsid}

    async def compact(self, time_range=None) -> None:
        """Manual compaction trigger on the data table (the /compact hook).
        `time_range` scopes the pick (and its follow-on picks) to SSTs
        overlapping that window; None compacts globally."""
        from horaedb_tpu.storage.read import CompactRequest

        self._ensure_writable("compact")
        await self.data_table.compact(CompactRequest(time_range=time_range))

    # -- deletes ---------------------------------------------------------------
    async def delete_series(
        self,
        metric: bytes,
        filters=None,
        matchers=None,
        start_ms: int = 0,
        end_ms: "int | None" = None,
    ) -> dict:
        """Tombstone delete: series of `metric` matching `filters`/
        `matchers`, samples in [start_ms, end_ms). The delete is visible
        to scans IMMEDIATELY (storage/visibility.py masks at read time)
        and physically applied when compaction rewrites the SSTs; rows
        written AFTER this call survive (re-ingest works). Exemplars of
        the matched series in the range are deleted too.

        `end_ms=None` (the "all time" form) caps at NOW rather than
        infinity: rows written after this call survive by sequence
        anyway, so an unbounded range would only buy coverage of
        already-written future-dated samples — while making the
        tombstone un-GC-able forever (it would overlap every live SST
        for the rest of the table's life). Pass an explicit end_ms to
        delete pre-written future-dated data.

        Flushes first, so every previously-ACKED sample carries a write
        sequence below the tombstone's and is therefore covered — the
        delete-then-crash-then-replay case cannot resurrect data."""
        from horaedb_tpu.storage.visibility import build_series_matchers

        self._ensure_writable("delete_series")

        if end_ms is None:
            end_ms = now_ms() + 1
        resolved = await self._resolve_query_async(QueryRequest(
            metric=metric, start_ms=start_ms, end_ms=end_ms,
            filters=list(filters or []), matchers=list(matchers or []),
        ))
        if resolved is None:
            return {"matched_series": 0, "tombstones": 0}
        metric_id, tsids = resolved
        # acked-but-buffered rows must be sealed (seq pinned) before the
        # tombstone's seq is allocated
        await self.flush()
        rng = TimeRange(start_ms, end_ms)
        mats = build_series_matchers(metric_id, tsids)
        tombs = [await self.data_table.delete_rows(rng, mats)]
        tombs.append(await self.exemplars_table.delete_rows(rng, mats))
        TOMBSTONES_CREATED.labels(self._table_label).inc(len(tombs))
        matched = (
            len(tsids) if tsids is not None
            else len(self.index_mgr.series_of(metric_id))
        )
        return {
            "matched_series": matched,
            "tombstones": len(tombs),
            "tombstone_ids": [t.id for t in tombs],
            "start_ms": start_ms,
            "end_ms": end_ms,
        }
