"""The columnar storage engine.

Reference: src/columnar_storage/src/storage.rs. The trait boundary is
preserved (`ColumnarStorage { schema; write; scan; compact }`,
storage.rs:58-89) and the object layout is identical:

    {root}/manifest/snapshot          binary snapshot (manifest/encoding.py)
    {root}/manifest/delta/{id}        protobuf deltas
    {root}/data/{id}.sst              sorted parquet SSTs

Execution is TPU-shaped instead of DataFusion-shaped:
- write: per-batch primary-key sort runs on device as single-key passes
  (ops/sort.py; replacing MemoryExec->SortExec, storage.rs:244-256), then
  parquet encode on host with sorting-columns metadata;
- scan: per-segment fused device pipeline (storage/read.py), segments
  unioned old->new (storage.rs:343-369);
- every write is one new sorted SST — no WAL, no memtable; the SST write is
  the durability event, then the manifest delta commits it (SURVEY §3.2).
"""

from __future__ import annotations

import asyncio
import io
import logging
import time
from abc import ABC, abstractmethod
from typing import AsyncIterator

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from horaedb_tpu.common import memtrace, tracing
from horaedb_tpu.common.error import HoraeError, context, ensure
from horaedb_tpu.objstore import ObjectStore
from horaedb_tpu.ops import sort as sort_ops
from horaedb_tpu.ops.blocks import arrow_column_to_numpy
from horaedb_tpu.server.metrics import BYTES_BUCKETS, GLOBAL_METRICS
from horaedb_tpu.storage import scanstats
from horaedb_tpu.storage.config import StorageConfig
from horaedb_tpu.storage.manifest import Manifest
from horaedb_tpu.storage.read import (
    CompactRequest,
    ParquetReader,
    ScanRequest,
    WriteRequest,
)
from horaedb_tpu.storage.sst import FileMeta, SstFile, SstPathGenerator, allocate_id
from horaedb_tpu.storage.types import StorageSchema, Timestamp, WriteResult

logger = logging.getLogger(__name__)

WRITE_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_storage_write_seconds",
    help="One storage write (sort + parquet encode + upload + manifest "
         "commit), by table root.",
    labelnames=("table",),
)
WRITE_ROWS = GLOBAL_METRICS.counter(
    "horaedb_storage_write_rows_total",
    help="Rows written to durable SSTs, by table root.",
    labelnames=("table",),
)
SST_BYTES = GLOBAL_METRICS.histogram(
    "horaedb_sst_bytes",
    help="Encoded size of SST objects written (flush shards, compaction "
         "outputs, direct writes).",
    buckets=BYTES_BUCKETS,
)
SCAN_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_storage_scan_seconds",
    help="One storage scan, first SST lookup to last batch yielded (early "
         "consumer breaks count as completed scans), by table root.",
    labelnames=("table",),
)
ORPHAN_SSTS_GC = GLOBAL_METRICS.counter(
    "horaedb_orphan_ssts_gc_total",
    help="Orphan SST objects (uploaded but never manifest-committed — a "
         "crash between upload and commit) reclaimed at storage open.",
    labelnames=("table",),
)


def jax_backend_is_cpu() -> bool:
    import jax

    return jax.default_backend() == "cpu"


def _is_pk_sorted(keys: list[np.ndarray]) -> bool:
    """O(n) vectorized check that rows are lexicographically nondecreasing
    over `keys` (most-significant first)."""
    n = len(keys[0])
    if n <= 1:
        return True
    decided_lt = np.zeros(n - 1, dtype=bool)
    for k in keys:
        a, b = k[:-1], k[1:]
        gt = (a > b) & ~decided_lt
        if gt.any():
            return False
        decided_lt |= a < b
    return True


class ColumnarStorage(ABC):
    """The storage-engine interface (storage.rs:77-87). The output stream of
    `scan` is sorted by primary keys, old segments before new ones."""

    @property
    @abstractmethod
    def schema(self) -> StorageSchema: ...

    @abstractmethod
    async def write(self, req: WriteRequest) -> None: ...

    @abstractmethod
    def scan(self, req: ScanRequest) -> AsyncIterator[pa.RecordBatch]: ...

    @abstractmethod
    async def compact(self, req: CompactRequest) -> None: ...


class ObjectBasedStorage(ColumnarStorage):
    """Object-store-backed engine (storage.rs ObjectBasedStorage)."""

    def __init__(self) -> None:
        raise HoraeError("use ObjectBasedStorage.try_new")

    @classmethod
    async def try_new(
        cls,
        root: str,
        store: ObjectStore,
        arrow_schema: pa.Schema,
        num_primary_keys: int,
        segment_duration_ms: int,
        config: StorageConfig | None = None,
        enable_compaction_scheduler: bool = True,
        start_background_merger: bool = True,
        sst_executor=None,
        manifest_executor=None,
        fence_node_id: str | None = None,
        fence_validate_interval_s: float = 5.0,
        fence=None,
        gc_orphans: bool = True,
        time_column: str | None = None,
        read_only: bool = False,
    ) -> "ObjectBasedStorage":
        """`sst_executor` / `manifest_executor`: optional
        concurrent.futures.Executors for CPU-heavy SST work (sort, parquet
        encode, bloom build) and manifest snapshot folds. Sized from the
        server's ThreadConfig (the analog of the reference's dedicated
        runtimes, main.rs:102-119); None = default pool / inline.

        `fence_node_id`: when set, acquire an EpochFence on `root` before
        opening — this process claims exclusive write ownership of the
        region (storage/fence.py); a later claimant deposes it and its
        writes fail with FencedError. The reference gets single-writer by
        construction (types.rs:135); a shared store needs it enforced.
        `fence`: share an already-acquired EpochFence instead (one claim
        covering several tables under one ownership root — the metric
        engine's six tables fence as one region).

        `time_column`: the schema column holding the row's timestamp
        (epoch ms), enabling ROW-exact retention masking and time-range
        tombstone deletes (storage/visibility.py). None = retention only
        prunes/expires whole SSTs (manifest time ranges) and
        `delete_rows` is unavailable.

        `read_only`: cluster replica mode (horaedb_tpu/cluster) — a VIEW
        over a root another writer process owns on the shared store. No
        fence, no compaction scheduler, no orphan GC, no background
        merger; the manifest loads via the in-memory delta fold and every
        write/delete raises. Scans work unchanged."""
        self = object.__new__(cls)
        if read_only:
            # a replica must never mutate the owner's root: every write
            # path below is gated, and the store-touching open-time
            # maintenance (GC, snapshot folds, compaction) is disabled
            enable_compaction_scheduler = False
            start_background_merger = False
            gc_orphans = False
            fence_node_id = None
            fence = None
        config = config or StorageConfig()
        self._root = root.strip("/")
        # this table's flush stages in the one stage funnel (scanstats.py)
        self._flush = scanstats.flush_family(self._root)
        self._store = store
        self._config = config
        self._read_only = read_only
        self._time_column = time_column
        if time_column is not None:
            ensure(
                time_column in arrow_schema.names,
                f"time_column {time_column!r} not in schema",
            )
            # pre-register the tombstone family children so /metrics shows
            # the zero state from boot (the PR2 convention)
            from horaedb_tpu.storage.visibility import TOMBSTONES_APPLIED

            for ctx in ("scan", "compact"):
                TOMBSTONES_APPLIED.labels(self._root, ctx)
        self._sst_executor = sst_executor
        self._segment_duration = segment_duration_ms
        # file_id -> (format_version, encodings) of a just-written SST,
        # consumed by the FileMeta construction site (write / compaction)
        self._pending_enc: dict[int, tuple] = {}
        self._schema = StorageSchema.try_new(
            arrow_schema, num_primary_keys, config.update_mode
        )
        self._fence = fence
        if fence is None and fence_node_id is not None:
            from horaedb_tpu.storage.fence import EpochFence

            self._fence = await EpochFence.acquire(
                store, self._root, fence_node_id,
                validate_interval_s=fence_validate_interval_s,
            )
        self._manifest = await Manifest.try_new(
            self._root,
            store,
            config.manifest,
            start_background_merger=start_background_merger,
            executor=manifest_executor,
            fence=self._fence,
            read_only=read_only,
        )
        # Startup id-collision guard: never allocate at or below an id the
        # manifest already holds (clock moved backwards across restarts, or
        # ids minted by another process against this store root).
        existing = self._manifest.all_ssts()
        if existing:
            from horaedb_tpu.storage.sst import ensure_id_above

            ensure_id_above(max(s.id for s in existing))
        self._path_gen = SstPathGenerator(self._root)
        if gc_orphans:
            # crash recovery: a writer that died between SST upload and
            # manifest commit left data objects nothing references — safe
            # to reclaim here because the manifest bootstrap above already
            # folded every committed delta, and single-writer ownership
            # (by construction or epoch fence) means no concurrent
            # uploader exists at open
            await self._gc_orphan_ssts()
            # rollup artifacts live under their own prefix with their own
            # registry (manifest/rollup records) — reclaim objects a crash
            # stranded between the artifact PUT and the record PUT
            await self._gc_orphan_rollups()
        self._reader = ParquetReader(
            store, self._path_gen, self._schema,
            scan_block_rows=config.scan_block_rows,
            scan_cache_bytes=config.scan_cache.as_bytes(),
            enc_cache_bytes=config.encoding.sidecar_cache.as_bytes(),
        )
        # EVERY SST read (materializing scan, chunked scan, downsample
        # pushdown, compaction) funnels through the shared visibility mask
        # (storage/visibility.py) via this provider — the single place
        # tombstone/retention filtering happens (jaxlint J010)
        self._reader.visibility_provider = self.visibility
        self._scheduler = None
        if enable_compaction_scheduler:
            # imported lazily: compaction depends on this module's writer
            from horaedb_tpu.storage.compaction.scheduler import CompactionScheduler

            self._scheduler = CompactionScheduler(
                storage=self,
                manifest=self._manifest,
                config=config.scheduler,
                segment_duration_ms=segment_duration_ms,
            )
            self._scheduler.start()
        return self

    async def close(self) -> None:
        if self._scheduler is not None:
            await self._scheduler.close()
        await self._manifest.close()

    async def _gc_orphan_ssts(self) -> None:
        """Reclaim data objects the manifest does not reference (crash
        between upload and commit, or a bloom-failure cleanup that itself
        failed). Best-effort: a faulty store at open degrades to a log
        line, never a failed boot — the orphans cost capacity, not
        correctness, and the next open retries. Orphan ids also raise the
        id-allocation floor so a fresh write can never mint an id whose
        `.sst` path is already occupied by a dead object."""
        from horaedb_tpu.storage.sst import ensure_id_above

        try:
            metas = await self._store.list(f"{self._root}/data")
        except Exception as e:  # noqa: BLE001 — GC is best-effort at open
            logger.warning("orphan sst gc skipped (list failed): %s", e)
            return
        live = {s.id for s in self._manifest.all_ssts()}
        by_id: dict[int, list[str]] = {}
        for m in metas:
            name = m.path.rsplit("/", 1)[-1]
            stem, _, ext = name.partition(".")
            if ext not in ("sst", "bloom", "enc") or not stem.isdigit():
                continue
            fid = int(stem)
            if fid in live:
                continue
            by_id.setdefault(fid, []).append(m.path)
        if not by_id:
            return
        ensure_id_above(max(by_id))
        paths = [p for ps in by_id.values() for p in ps]
        results = await asyncio.gather(
            *(self._store.delete(p) for p in paths), return_exceptions=True
        )
        failed = [
            p for p, r in zip(paths, results) if isinstance(r, BaseException)
        ]
        for p in failed:
            logger.warning("orphan sst gc: failed to delete %s", p)
        # count only FULLY reclaimed orphans: a failed delete stays behind
        # for the next open to retry, and counting it now would double-count
        # it then (and lie to the runbook watching this family)
        failed_ids = {
            int(p.rsplit("/", 1)[-1].partition(".")[0]) for p in failed
        }
        ORPHAN_SSTS_GC.labels(self._root).inc(len(by_id) - len(
            failed_ids & set(by_id)
        ))
        logger.info(
            "orphan sst gc: root=%s orphans=%d objects=%d (failed=%d)",
            self._root, len(by_id), len(paths), len(failed),
        )

    async def _gc_orphan_rollups(self) -> None:
        """Reclaim rollup objects no record references (crash between the
        artifact PUT and its record PUT, or a failed supersede-delete).
        Best-effort like the data orphan GC; ids raise the allocation
        floor for the same reason."""
        from horaedb_tpu.objstore import NotFound
        from horaedb_tpu.storage.sst import ensure_id_above

        try:
            metas = await self._store.list(f"{self._root}/rollup")
        except NotFound:
            return
        except Exception as e:  # noqa: BLE001 — GC is best-effort at open
            logger.warning("rollup orphan gc skipped (list failed): %s", e)
            return
        live = self._manifest.referenced_rollup_sst_ids()
        orphans = []
        for m in metas:
            name = m.path.rsplit("/", 1)[-1]
            stem, _, ext = name.partition(".")
            if ext != "sst" or not stem.isdigit():
                continue
            if int(stem) not in live:
                orphans.append((int(stem), m.path))
        if not orphans:
            return
        ensure_id_above(max(i for i, _ in orphans))
        results = await asyncio.gather(
            *(self._store.delete(p) for _i, p in orphans),
            return_exceptions=True,
        )
        failed = sum(1 for r in results if isinstance(r, BaseException))
        logger.info(
            "rollup orphan gc: root=%s orphans=%d (failed=%d)",
            self._root, len(orphans), failed,
        )

    def _ensure_writable(self, what: str) -> None:
        if self._read_only:
            from horaedb_tpu.common.error import ReplicaReadOnlyError

            raise ReplicaReadOnlyError(
                f"storage {self._root} is a read-only replica view; "
                f"refusing {what} (route the mutation to the owning writer)"
            )

    # -- accessors ----------------------------------------------------------
    @property
    def read_only(self) -> bool:
        return self._read_only

    def manifest_epoch(self) -> int:
        """The manifest's monotonic epoch (Manifest.epoch) — the number
        the cluster staleness token and /api/v1/cluster/status compare
        between writer and replicas."""
        return self._manifest.epoch()

    @property
    def schema(self) -> StorageSchema:
        return self._schema

    @property
    def manifest(self) -> Manifest:
        return self._manifest

    @property
    def parquet_reader(self) -> ParquetReader:
        return self._reader

    @property
    def segment_duration_ms(self) -> int:
        return self._segment_duration

    @property
    def time_column(self) -> str | None:
        return self._time_column

    @property
    def store(self) -> ObjectStore:
        return self._store

    @property
    def sst_path_gen(self) -> SstPathGenerator:
        return self._path_gen

    @property
    def rollup_config(self):
        """Rollup emission/substitution knobs (storage/rollup.py)."""
        return self._config.rollup

    # -- visibility: retention + tombstone deletes (storage/visibility.py) --
    def retention_floor(self) -> int | None:
        """Rows/SSTs older than this are out of retention. Single source of
        truth is the compaction scheduler's TTL, so scan-time masking and
        compaction-time expiry can never disagree."""
        ttl = self._config.scheduler.ttl
        if ttl is None:
            return None
        from horaedb_tpu.common.time_ext import now_ms

        return now_ms() - ttl.as_millis()

    def visibility(self):
        """Current Visibility for this table's reads, or None (the common
        fast path: nothing subtractive is configured)."""
        tombs = self._manifest.all_tombstones()
        floor = self.retention_floor() if self._time_column else None
        if not tombs and floor is None:
            return None
        from horaedb_tpu.storage.visibility import Visibility

        return Visibility(
            table=self._root,
            time_column=self._time_column,
            tombstones=tuple(tombs),
            retention_floor_ms=floor,
        )

    def select_ssts(self, time_range: TimeRange) -> list[SstFile]:
        """Manifest overlap selection + retention pruning: SSTs wholly
        older than the retention floor never cost IO even before the
        compaction picker expires them. EXPLAIN provenance:
        `ssts_retention_pruned` counts what the horizon removed here."""
        ssts = self._manifest.find_ssts(time_range)
        floor = self.retention_floor()
        if floor is not None:
            kept = [s for s in ssts if s.meta.time_range.end >= floor]
            pruned = len(ssts) - len(kept)
            if pruned:
                scanstats.note("ssts_retention_pruned", pruned)
            ssts = kept
        return ssts

    async def delete_rows(
        self,
        time_range: TimeRange,
        matchers: "tuple[tuple[str, tuple[int, ...] | None], ...]",
    ):
        """Create + persist one tombstone delete record: rows matching
        every matcher inside `time_range` whose `__seq__` predates this
        call become invisible to scans NOW and are physically removed when
        compaction rewrites their SSTs. Returns the Tombstone.

        The sequence is allocated HERE, from the same monotonic allocator
        as write sequences — every row acked (sealed/written) before this
        call has a smaller seq and is therefore covered; rows written
        after it survive (re-ingest into a deleted range works)."""
        self._ensure_writable("delete_rows")
        ensure(
            self._time_column is not None,
            "delete_rows requires a table with a time_column",
        )
        from horaedb_tpu.storage.visibility import Tombstone

        for col, _vals in matchers:
            ensure(
                col in self._schema.arrow_schema.names,
                f"tombstone matcher column {col!r} not in schema",
            )
        rid = allocate_id()
        tomb = Tombstone(
            id=rid, seq=rid, time_range=time_range, matchers=tuple(matchers)
        )
        await self._manifest.add_tombstone(tomb)
        # serving-tier invalidation funnel (jaxlint J013): the new
        # tombstone id changes the visibility epoch in every cache key
        # covering this range; purge the table's entries eagerly too
        from horaedb_tpu.serving.cache import RESULT_CACHE

        RESULT_CACHE.serving_invalidate(self._root, "delete", time_range)
        logger.info(
            "tombstone created: root=%s id=%d range=[%d,%d) matchers=%s",
            self._root, rid, time_range.start, time_range.end, matchers,
        )
        return tomb

    # -- write path (storage.rs:189-333) ------------------------------------
    async def write(self, req: WriteRequest) -> None:
        self._ensure_writable("write")
        if self._fence is not None:
            # reject BEFORE the encode+upload: the manifest update would
            # fence anyway, but by then a deposed writer has already PUT a
            # full SST object nobody will ever reference (no orphan GC)
            await self._fence.ensure_valid()
        if req.enable_check:
            start_seg = Timestamp(req.time_range.start).truncate_by(self._segment_duration)
            end_seg = Timestamp(req.time_range.end - 1).truncate_by(self._segment_duration)
            ensure(
                start_seg == end_seg,
                f"time range of one write must fall in one segment, "
                f"range: [{req.time_range.start}, {req.time_range.end})",
            )
        with tracing.span("storage_write", table=self._root,
                          rows=req.batch.num_rows), \
                WRITE_SECONDS.labels(self._root).time():
            result = await self.write_batch(
                req.batch, presorted=req.presorted, seq=req.seq,
                fast_encode=req.fast_encode,
            )
            fmt, encodings = self.pop_enc_meta(result.id)
            meta = FileMeta(
                max_sequence=result.seq,
                num_rows=req.batch.num_rows,
                size=result.size,
                time_range=req.time_range,
                format_version=fmt,
                encodings=encodings,
            )
            with self._flush.stage("manifest"):
                await self._manifest.add_file(result.id, meta)
        # serving-tier invalidation funnel (jaxlint J013): a committed SST
        # changes the table's sealed set — cached results for it are dead
        from horaedb_tpu.serving.cache import RESULT_CACHE

        RESULT_CACHE.serving_invalidate(self._root, "flush", req.time_range)
        WRITE_ROWS.labels(self._root).inc(req.batch.num_rows)

    async def _run_sst(self, fn, *args):
        """Run CPU-heavy SST work on the configured executor (ThreadConfig
        sizing) or the default thread pool."""
        if self._sst_executor is None:
            return await asyncio.to_thread(fn, *args)
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._sst_executor, lambda: fn(*args)
        )

    async def write_batch(
        self,
        batch: pa.RecordBatch,
        presorted: bool = False,
        seq: int | None = None,
        fast_encode: bool = False,
    ) -> WriteResult:
        file_id = allocate_id()
        if presorted:
            sorted_batch = batch
        else:
            with self._flush.stage("sort"):
                sorted_batch = await self._run_sst(
                    self._flush.on_worker, "sort", self._sort_batch, batch)
        # file ids are increasing, so the id doubles as the sequence unless
        # the caller pinned one at snapshot time (same allocator, so the
        # combined seq stream stays monotonic with unbuffered writes)
        if seq is None:
            seq = file_id
        with_builtin = self._schema.fill_builtin_columns(sorted_batch, seq)
        table = pa.Table.from_batches([with_builtin])
        size = await self.write_sst(file_id, table, fast_encode=fast_encode)
        return WriteResult(id=file_id, seq=seq, size=size)

    def _sort_batch(self, batch: pa.RecordBatch) -> pa.RecordBatch:
        """Primary-key sort on device (replaces SortExec, storage.rs:244-256).

        The permutation is computed over the numeric pk lanes on device
        (ops/sort.py); the gather applies to all columns via pyarrow take so binary
        payloads never touch the device. Schemas with binary primary keys
        sort on host via arrow compute (the device path needs numeric lanes).
        """
        if batch.num_rows <= 1:
            return batch
        pk_names = self._schema.primary_key_names
        pk_types = [batch.schema.field(n).type for n in pk_names]
        if any(
            pa.types.is_binary(t) or pa.types.is_large_binary(t) or pa.types.is_string(t)
            for t in pk_types
        ):
            import pyarrow.compute as pc

            perm = pc.sort_indices(
                pa.Table.from_batches([batch]),
                sort_keys=[(n, "ascending") for n in pk_names],
            )
            return batch.take(perm)
        keys = [
            np.asarray(arrow_column_to_numpy(batch.column(batch.schema.names.index(name))))
            for name in pk_names
        ]
        if _is_pk_sorted(keys):
            # presorted batches (e.g. the metric engine's series-ordered
            # ingest flush) skip the sort entirely; the O(n) check costs a
            # few vector compares
            return batch
        if jax_backend_is_cpu():
            # np.lexsort beats XLA's CPU sort ~2x; the device path only pays
            # off on real accelerators
            perm = np.lexsort(tuple(reversed(keys)))
        else:
            perm = sort_ops.sort_permutation(keys)
        return batch.take(pa.array(perm))

    def _writer_kwargs(self, fast: bool = False) -> dict:
        """ParquetWriter options from WriteConfig, per-column overrides
        applied (the analog of build_write_props, storage.rs:258-298).

        `fast=True` = the ingest-flush L0 profile: snappy + plain encodings
        (measured ~2x the encode rate of zstd+BYTE_STREAM_SPLIT at ~1.7x
        output bytes). Statistics and sorting columns are preserved — the
        read path's row-group pruning and presorted fast path see no
        difference; compaction re-encodes outputs with the tuned profile."""
        cfg = self._config.write
        if fast and cfg.flush_fast_encode:
            sorting = [
                pq.SortingColumn(i) for i in range(self._schema.num_primary_keys)
            ] + [pq.SortingColumn(self._schema.seq_idx)]
            return dict(
                compression="SNAPPY",
                use_dictionary=False,
                write_statistics=True,
                write_batch_size=cfg.write_batch_size,
                sorting_columns=sorting if cfg.enable_sorting_columns else None,
            )
        names = self._schema.arrow_schema.names
        col_opts = cfg.column_options or {}

        def opt(n: str, attr: str):
            per = col_opts.get(n)
            return getattr(per, attr, None) if per is not None else None

        # dictionary: global bool, upgraded to a column list when any
        # per-column override exists
        if any(opt(n, "enable_dict") is not None for n in names):
            use_dictionary: bool | list = [
                n for n in names
                if (opt(n, "enable_dict")
                    if opt(n, "enable_dict") is not None else cfg.enable_dict)
            ]
        else:
            use_dictionary = cfg.enable_dict
        global_comp = cfg.compression.value if cfg.compression.value != "none" else "NONE"
        if any(opt(n, "compression") for n in names):
            compression: str | dict = {
                n: (opt(n, "compression") or global_comp) for n in names
            }
        else:
            compression = global_comp
        column_encoding = {
            n: opt(n, "encoding") for n in names if opt(n, "encoding")
        }
        # type-driven defaults for columns with NO explicit override and no
        # dictionary page: DELTA_BINARY_PACKED on integer/timestamp lanes,
        # BYTE_STREAM_SPLIT on float lanes (measured 8.1 B/row vs 13.1
        # plain on the bench write shape — the ingest copy-tax pin in
        # tools/mem_smoke.py gates the ratio). Skipped entirely when
        # dictionary encoding is globally ON (parquet forbids mixing
        # column_encoding with a dictionary-encoded column).
        if use_dictionary is not True:
            dict_cols = set(use_dictionary) if isinstance(
                use_dictionary, list) else set()
            for n in names:
                if n in column_encoding or n in dict_cols:
                    continue
                t = self._schema.arrow_schema.field(n).type
                if pa.types.is_integer(t) or pa.types.is_timestamp(t):
                    column_encoding[n] = "DELTA_BINARY_PACKED"
                elif pa.types.is_floating(t):
                    column_encoding[n] = "BYTE_STREAM_SPLIT"
        column_encoding = column_encoding or None
        sorting = [
            pq.SortingColumn(i) for i in range(self._schema.num_primary_keys)
        ] + [pq.SortingColumn(self._schema.seq_idx)]
        return dict(
            compression=compression,
            use_dictionary=use_dictionary,
            write_statistics=True,
            write_batch_size=cfg.write_batch_size,
            column_encoding=column_encoding,
            sorting_columns=sorting if cfg.enable_sorting_columns else None,
        )

    def _bloom_columns(self) -> list[str]:
        """Columns with bloom filters enabled (global flag or per-column).
        Builtin columns never get blooms — equality probes on them make no
        sense and `__reserved__` is null-filled."""
        from horaedb_tpu.storage.types import RESERVED_COLUMN_NAME, SEQ_COLUMN_NAME

        cfg = self._config.write
        col_opts = cfg.column_options or {}
        out = []
        for n in self._schema.arrow_schema.names:
            if n in (SEQ_COLUMN_NAME, RESERVED_COLUMN_NAME):
                continue
            per = getattr(col_opts.get(n), "enable_bloom_filter", None) if n in col_opts else None
            if per is True or (per is None and cfg.enable_bloom_filter):
                out.append(n)
        return out

    async def write_sst(
        self, file_id: int, table: pa.Table, fast_encode: bool = False,
        stages: "scanstats.Family | None" = None,
    ) -> int:
        """Encode a (sorted, builtin-filled) table as one parquet SST,
        STREAMED to the object store at chunk granularity — host memory
        stays O(row group + chunk), not O(table), matching the reference's
        AsyncArrowWriter streaming (storage.rs:192-224). Returns object size.

        `stages`: the family the write's stages (encode, upload, sidecar)
        are observed in: this table's flush family unless the caller is a
        compaction, which passes its own.

        When bloom filters are enabled, a sidecar `{id}.bloom` lands after
        the SST but before the file is registrable in the manifest, so
        readers never observe a registered SST without its sidecar."""
        import queue as _queue
        import threading as _threading

        stages = stages or self._flush
        path = self._path_gen.generate(file_id)
        cfg = self._config.write
        # The manifest wire format carries num_rows as u32 (sst.proto,
        # encoding.py); reject before paying any upload.
        ensure(table.num_rows < 2**32, f"sst row count too large: {table.num_rows}")

        CHUNK = 4 << 20
        kwargs = self._writer_kwargs(fast=fast_encode)

        # Small tables (registration batches, flush shards) skip the
        # producer-thread/queue streaming machinery: one worker-thread
        # encode into memory + one put. The streaming path exists to bound
        # host memory for LARGE tables; the threshold admits a whole flush
        # shard (~5-10 MB input -> ~1-3 MB object), whose streaming
        # loop<->thread ping-pong measured ~18 ms per shard — more than the
        # encode itself. Peak extra memory = one encoded object (< input).
        if table.nbytes <= 4 * CHUNK:
            def _encode_small() -> bytes:
                sink = io.BytesIO()
                writer = pq.ParquetWriter(sink, table.schema, **kwargs)
                writer.write_table(table, row_group_size=cfg.max_row_group_size)
                writer.close()
                return sink.getvalue()

            # encode (thread pool; pyarrow cannot thread one file's
            # columns, so flush parallelism is shard-level across the
            # pool) vs the upload PUT below
            with stages.stage("encode"):
                blob = await self._run_sst(
                    stages.on_worker, "encode", _encode_small)
            # lineage: the encoded object is a fresh buffer distinct from
            # the table's lanes (the copy-tax of the flush encode)
            memtrace.track_bytes(len(blob), "flush_encode", "alloc")
            ensure(len(blob) < 2**32, f"sst too large for manifest format: {len(blob)}")
            with stages.stage("upload"), context(f"write sst {path}"):
                await self._store.put(path, blob)
            await self._write_sidecars(file_id, path, table, stages)
            SST_BYTES.observe(len(blob))
            return len(blob)

        q: _queue.Queue = _queue.Queue(maxsize=4)
        cancel = _threading.Event()
        done = _threading.Event()

        class _Sink(io.RawIOBase):
            def __init__(self):
                self.parts: list[bytes] = []
                self.pending = 0

            def writable(self):
                return True

            def write(self, b):
                if cancel.is_set():
                    raise IOError("sst stream cancelled")
                # accumulate whole chunks in a list (O(1) append) instead of
                # a bytearray whose head-slicing memmoves the tail each emit
                self.parts.append(bytes(b))
                self.pending += len(b)
                while self.pending >= CHUNK:
                    blob = b"".join(self.parts)
                    q.put(blob[:CHUNK])
                    rest = blob[CHUNK:]
                    self.parts = [rest] if rest else []
                    self.pending = len(rest)
                return len(b)

            def flush_tail(self):
                if self.pending:
                    q.put(b"".join(self.parts))
                    self.parts = []
                    self.pending = 0

        def _produce() -> None:
            try:
                with stages.mark("encode"):
                    sink = _Sink()
                    writer = pq.ParquetWriter(sink, table.schema, **kwargs)
                    # one call: pyarrow splits into max_row_group_size row
                    # groups in C++ (same file layout as a Python slice
                    # loop, without per-group Python/GIL overhead)
                    writer.write_table(table, row_group_size=cfg.max_row_group_size)
                    writer.close()
                    sink.flush_tail()
                q.put(None)  # EOF
            except BaseException as e:  # noqa: BLE001 — relayed to consumer
                q.put(e)
            finally:
                done.set()

        # The CPU-heavy encode runs on the sized SST executor when one is
        # configured (ThreadConfig) — ad-hoc threads would bypass exactly
        # the contention bound the executor exists for.
        if self._sst_executor is not None:
            self._sst_executor.submit(_produce)
        else:
            _threading.Thread(target=_produce, daemon=True).start()

        async def chunks():
            total = 0
            while True:
                item = await asyncio.to_thread(q.get)
                if item is None:
                    return
                if isinstance(item, BaseException):
                    raise item
                total += len(item)
                memtrace.track_bytes(len(item), "flush_encode", "alloc")
                # size is u32 in the manifest format: abort mid-stream
                # (put_stream discards the partial object)
                ensure(total < 2**32, f"sst too large for manifest format: {total}")
                yield item

        try:
            # streaming path overlaps encode with the PUT; the combined
            # wall time attributes to upload (encode rides the stream, and
            # is on the timeline as the producer thread's own event)
            with stages.stage("upload"), context(f"write sst {path}"):
                size = await self._store.put_stream(path, chunks())
        finally:
            cancel.set()
            while not done.is_set():
                try:  # unblock a producer stuck on a full queue
                    q.get_nowait()
                except _queue.Empty:
                    pass
                done.wait(timeout=0.05)

        await self._write_sidecars(file_id, path, table, stages)
        SST_BYTES.observe(size)
        return size

    async def _write_sidecars(self, file_id: int, path: str, table,
                              stages: "scanstats.Family") -> None:
        """Bloom first, enc LAST: _write_enc_sidecar registers the pending
        (format, encodings) entry only once nothing after it can fail, so
        a failed write never strands it."""
        with stages.stage("sidecar"):
            await self._write_bloom_sidecar(file_id, path, table, stages)
            await self._write_enc_sidecar(file_id, path, table, stages)

    def pop_enc_meta(self, file_id: int) -> tuple[int, tuple]:
        """(format_version, encodings) of a just-written SST — consumed
        exactly once by the FileMeta construction site."""
        return self._pending_enc.pop(file_id, (1, ()))

    async def _write_enc_sidecar(self, file_id: int, path: str, table,
                                 stages: "scanstats.Family") -> None:
        """Encoded-lane sidecar AFTER the SST object lands and BEFORE the
        manifest can reference it — a registered v2 SST always has its
        sidecar. Its cost is part of the `sidecar` stage; a failed PUT
        reclaims the SST object best-effort and raises, exactly like the
        bloom sidecar path."""
        cfg = self._config.encoding
        if not cfg.enabled or table.num_rows < cfg.min_rows:
            return
        from horaedb_tpu.storage import encoding as enc_mod

        def _encode_and_pack():
            # blob serialization rides the same offload as the encode:
            # b"".join over multi-MB lane payloads on the event loop would
            # stall admission/deadline servicing during flush bursts
            e = enc_mod.encode_table(
                table, cfg.page_rows, cfg.max_dict,
                self._time_column, cfg.lanes,
            )
            return (e, enc_mod.encode_blob(e)) if e is not None else (None, None)

        try:
            enc, blob = await self._run_sst(
                stages.on_worker, "sidecar", _encode_and_pack)
            if enc is None:
                return
            await self._store.put(self._path_gen.generate_enc(file_id), blob)
        except BaseException:
            try:
                await self._store.delete(path)
            except Exception:  # noqa: BLE001 — orphan cleanup best-effort
                logger.warning(
                    "orphaned sst object %s after enc sidecar failure", path
                )
            raise
        self._pending_enc[file_id] = (
            enc_mod.SST_FORMAT_V2, enc.descriptor(),
        )

    async def _write_bloom_sidecar(self, file_id: int, path: str, table,
                                   stages: "scanstats.Family") -> None:
        """Bloom sidecar AFTER the SST lands: readers only learn ids via the
        manifest (updated after this returns), so ordering is safe, and a
        failed stream can't orphan a sidecar. If the sidecar put itself
        fails, the SST object is reclaimed best-effort before raising."""
        bloom_cols = self._bloom_columns()
        if not bloom_cols:
            return
        from horaedb_tpu.storage import bloom as bloom_mod

        try:
            blooms = await self._run_sst(
                stages.on_worker, "sidecar", bloom_mod.build_blooms, table,
                bloom_cols,
            )
            await self._store.put(
                self._path_gen.generate_bloom(file_id),
                bloom_mod.encode_blooms(blooms),
            )
        except BaseException:
            try:
                await self._store.delete(path)
            except Exception:  # noqa: BLE001 — orphan cleanup best-effort
                logger.warning("orphaned sst object %s after bloom failure", path)
            raise

    # -- scan path (storage.rs:335-370) --------------------------------------
    async def scan(self, req: ScanRequest) -> AsyncIterator[pa.RecordBatch]:
        """Per-segment scans, old segments first. The NEXT segment's
        read+kernel overlaps with the consumer draining the current one
        (bounded one-segment prefetch — the async analog of the reference's
        UnionExec driving per-segment plans concurrently); an early consumer
        break (limit pushdown) cancels the prefetch."""
        t0 = time.perf_counter()
        ssts = self.select_ssts(req.range)
        if req.min_sst_id is not None:
            ssts = [s for s in ssts if s.id > req.min_sst_id]
        # EXPLAIN provenance: time-range SST selection (reads and bloom
        # prunes are noted per SST in read.py)
        scanstats.note("ssts_selected", len(ssts))
        if not ssts:
            return
        segments = self.group_by_segment(ssts)

        def start(seg):
            return asyncio.ensure_future(self.scan_segment_retrying(
                seg, req.range,
                lambda fresh: self._reader.scan_segment(
                    fresh,
                    predicate=req.predicate,
                    projections=req.projections,
                    keep_builtin=False,
                ),
                empty_result=[],
            ))

        from horaedb_tpu.common import deadline as deadline_ctx

        pending = start(segments[0])
        try:
            for i in range(len(segments)):
                batches = await pending
                # cooperative deadline between segments: an expired query
                # stops here instead of prefetching + decoding the rest
                deadline_ctx.check("segment_scan")
                pending = start(segments[i + 1]) if i + 1 < len(segments) else None
                for b in batches:
                    yield b
        finally:
            if pending is not None:
                pending.cancel()
                try:
                    await pending
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
            # NOT a tracing span: an async generator's frame suspends across
            # consumer turns, and a contextvar set inside it would leak into
            # the consumer's context — the per-stage spans attach from
            # scan_segment (a plain coroutine) instead
            SCAN_SECONDS.labels(self._root).observe(time.perf_counter() - t0)

    async def scan_segment_retrying(self, seg_ssts, time_range, op, empty_result=None):
        """Run a per-segment scan `op`, refreshing the segment's SST list
        from the manifest on NotFound: a compaction may physically delete
        input files between the caller's manifest snapshot and the read.
        Sound because compaction is segment-local (picker groups by
        segment), so the replacement SST lives in the same segment; an
        empty refresh means the data was TTL-expired.

        A store-unavailable failure (breaker open / retries exhausted in
        the resilience layer) is NOT retried here — the store layer
        already spent its budget. It is noted as `ssts_unavailable` scan
        provenance (EXPLAIN / the 503 body carries it) and re-raised
        typed, so the HTTP layer sheds instead of 500ing."""
        from horaedb_tpu.common.error import UnavailableError
        from horaedb_tpu.objstore import NotFound

        seg_key = Timestamp(seg_ssts[0].meta.time_range.start).truncate_by(
            self._segment_duration
        ).value
        for _attempt in range(3):
            try:
                return await op(seg_ssts)
            except UnavailableError:
                scanstats.note("ssts_unavailable", len(seg_ssts))
                raise
            except NotFound:
                fresh = [
                    s for s in self._manifest.find_ssts(time_range)
                    if Timestamp(s.meta.time_range.start).truncate_by(
                        self._segment_duration
                    ).value == seg_key
                ]
                if not fresh:
                    return empty_result
                logger.info(
                    "segment scan raced a compaction; retrying with %d fresh ssts",
                    len(fresh),
                )
                seg_ssts = fresh
        return await op(seg_ssts)  # last attempt: let NotFound propagate

    def group_by_segment(self, ssts: list[SstFile]) -> list[list[SstFile]]:
        """Bucket SSTs by segment start, ordered old->new (storage.rs:343-345)."""
        buckets: dict[int, list[SstFile]] = {}
        for s in ssts:
            seg = Timestamp(s.meta.time_range.start).truncate_by(self._segment_duration)
            buckets.setdefault(seg.value, []).append(s)
        return [buckets[k] for k in sorted(buckets)]

    # -- compaction (storage.rs:372-374) --------------------------------------
    async def compact(self, req: CompactRequest) -> None:
        ensure(self._scheduler is not None, "compaction scheduler disabled")
        self._scheduler.trigger_compaction(time_range=req.time_range)

    @property
    def compaction_scheduler(self):
        return self._scheduler
