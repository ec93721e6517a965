"""Compaction executor: k-way merge+dedup on device, then manifest commit.

Reference: src/columnar_storage/src/compaction/executor.rs. Semantics kept:
- memory gate: in-use bytes + task input size must stay under the limit or
  the task is rejected before running (executor.rs:93-114);
- each admitted task immediately pings the trigger channel so the picker
  looks for more work (executor.rs:147-151);
- the k inputs merge through the SAME pipeline as scans with
  keep_builtin=True (original __seq__ values survive into the output SST);
- the manifest update (add new, delete inputs+expireds) is the commit point:
  after it, physical deletes are best-effort and never fail the task
  ("From now on, no error should be returned", executor.rs:218-219);
- failures before the commit release memory and unmark the SSTs so the
  picker can retry them (executor.rs:123-137).
"""

from __future__ import annotations

import asyncio
import logging
import time
from contextlib import contextmanager

import pyarrow as pa

from horaedb_tpu.common import tracing
from horaedb_tpu.common.error import ensure
from horaedb_tpu.server.metrics import BYTES_BUCKETS, GLOBAL_METRICS
from horaedb_tpu.storage import scanstats
from horaedb_tpu.storage.compaction import Task
from horaedb_tpu.storage.sst import FileMeta, SstFile, allocate_id
from horaedb_tpu.storage.types import TimeRange

logger = logging.getLogger(__name__)

COMPACTION_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_compaction_seconds",
    help="One compaction task end to end (read inputs, device merge, "
         "encode shards, manifest commit, physical deletes).",
)
COMPACTION_BYTES = GLOBAL_METRICS.histogram(
    "horaedb_compaction_bytes",
    help="Input bytes per compaction task (the admitted task's SST sizes).",
    buckets=BYTES_BUCKETS,
)
COMPACTIONS = GLOBAL_METRICS.counter(
    "horaedb_compactions_total",
    help="Completed compaction tasks by result.",
    labelnames=("result",),
)
COMPACTION_ROWS = GLOBAL_METRICS.counter(
    "horaedb_compaction_rows_total",
    help="Rows compaction tasks took in (their inputs' num_rows) and "
         "wrote out (after dedup, tombstones and retention).",
    labelnames=("dir",),
)
for _dir in ("in", "out"):
    COMPACTION_ROWS.labels(_dir)
del _dir


@contextmanager
def _stage(report: dict, name: str):
    """One compaction stage through the funnel, its seconds kept for the
    task's log line."""
    with scanstats.COMPACTION.stage(name) as t:
        yield
    report[name] = t.seconds


class Executor:
    def __init__(
        self,
        storage,  # ObjectBasedStorage (duck-typed to avoid an import cycle)
        manifest,
        mem_limit: int,
        trigger: "asyncio.Queue[None]",
    ):
        self._storage = storage
        self._manifest = manifest
        self._mem_limit = mem_limit
        self._inused_memory = 0
        self._trigger = trigger
        self._inflight: set[asyncio.Task] = set()

    # -- admission (executor.rs:93-114) -------------------------------------
    def pre_check(self, task: Task) -> None:
        # expired-only tasks (retention enforcement: delete-only commit, no
        # merge) are legal; a task with neither inputs nor expireds is not
        ensure(bool(task.inputs) or bool(task.expireds),
               "compaction task must have inputs or expireds")
        ensure(
            all(f.is_compaction() for f in task.inputs + task.expireds),
            "compaction task files must be marked in_compaction",
        )
        task_size = task.input_size()
        ensure(
            self._inused_memory + task_size <= self._mem_limit,
            f"Compaction memory usage too high, inused:{self._inused_memory}, "
            f"task_size:{task_size}, limit:{self._mem_limit}",
        )
        self._inused_memory += task_size
        task.mem_reserved = True

    def _release(self, task: Task) -> None:
        if task.mem_reserved:
            self._inused_memory -= task.input_size()
            task.mem_reserved = False

    def on_success(self, task: Task) -> None:
        self._release(task)

    def on_failure(self, task: Task) -> None:
        """Release the budget (only if charged — a pre_check rejection must
        not drive the gate negative) and unmark SSTs for re-pick."""
        self._release(task)
        for sst in task.inputs + task.expireds:
            sst.unmark_compaction()

    def _trigger_more_task(self, scope=None) -> None:
        """Ping the picker for more work (executor.rs:147-151), re-picking
        under the admitted task's scope (None = global)."""
        try:
            self._trigger.put_nowait(scope)
        except asyncio.QueueFull:
            pass

    # -- submission (executor.rs:139-151, 261-272) ---------------------------
    def submit(self, task: Task) -> asyncio.Task:
        async def _run() -> None:
            try:
                with tracing.trace(
                    "compaction", inputs=len(task.inputs),
                    input_bytes=task.input_size(),
                ), COMPACTION_SECONDS.time():
                    await self.do_compaction(task)
            except Exception:  # noqa: BLE001
                logger.exception("Do compaction failed")
                COMPACTIONS.labels("error").inc()
                self.on_failure(task)
            else:
                COMPACTIONS.labels("ok").inc()
                self.on_success(task)

        t = asyncio.create_task(_run(), name="compaction-task")
        self._inflight.add(t)
        t.add_done_callback(self._inflight.discard)
        return t

    async def drain(self) -> None:
        """Wait for in-flight compactions (tests & shutdown).

        A finished task leaves `_inflight` only when the loop runs its done
        callback; awaiting finished tasks does not yield to the loop, so the
        set is emptied here as well, or a drain that starts between a
        task's last step and its callback would spin without end."""
        while self._inflight:
            tasks = list(self._inflight)
            await asyncio.gather(*tasks, return_exceptions=True)
            self._inflight.difference_update(tasks)

    # -- the compaction itself (executor.rs:155-222) --------------------------
    async def do_compaction(self, task: Task) -> None:
        """One task through the funnel's compaction stages: `scan` (read
        and merge the inputs), `encode` (write the output shards),
        `commit` (the manifest update), `rollup` (emission, full-segment
        tasks only) and `cleanup` (physical deletes and GC). The finished
        task leaves one INFO line and its numbers on the root span."""
        t0 = time.perf_counter()
        done = {"rows_in": sum(f.meta.num_rows for f in task.inputs),
                "rows_out": 0, "bytes_out": 0}
        stages: dict[str, float] = {}
        await self._compact(task, done, stages)
        tracing.add_attr(**done)
        logger.info(
            "compaction done: inputs=%d expireds=%d rows_in=%d rows_out=%d "
            "bytes_in=%d bytes_out=%d seconds=%.3f stages=%s",
            len(task.inputs), len(task.expireds), done["rows_in"],
            done["rows_out"], task.input_size(), done["bytes_out"],
            time.perf_counter() - t0,
            {k: round(v, 4) for k, v in stages.items()},
        )

    async def _commit(self, stages: dict, new_files: list[SstFile],
                      to_deletes: list[int], time_range: TimeRange) -> None:
        """The commit point: add new THEN delete inputs+expireds, atomically
        in one manifest delta (executor.rs:206-216)."""
        from horaedb_tpu.serving.cache import RESULT_CACHE

        with _stage(stages, "commit"):
            await self._manifest.update(new_files, to_deletes)
        # serving-tier invalidation funnel (jaxlint J013): the sealed-SST
        # set just changed; cached results over the old set are dead
        RESULT_CACHE.serving_invalidate(
            self._storage._root, "compact", time_range
        )

    async def _cleanup(self, stages: dict, to_deletes: list[int]) -> None:
        """After the commit: best-effort, never fails the task."""
        with _stage(stages, "cleanup"):
            await self._delete_ssts(to_deletes)
            await self._gc_tombstones()
            await self._gc_rollups()

    async def _compact(self, task: Task, done: dict, stages: dict) -> None:
        """The task's steps, and where each runs: `scan` decodes and
        merges on threads of the default pool (`asyncio_<n>`, see below),
        `encode` encodes its shards on the SST executor's threads and
        awaits their puts, `cleanup` unlinks the inputs in one call of the
        store; the event loop's thread runs what lies between (the
        slicing, the manifest update)."""
        from horaedb_tpu.storage import visibility as vis_mod

        self.pre_check(task)
        self._trigger_more_task(task.scope)
        COMPACTION_BYTES.observe(task.input_size())
        COMPACTION_ROWS.labels("in").inc(done["rows_in"])
        logger.debug("Start do compaction, input_len=%d", len(task.inputs))

        if not task.inputs:
            # expired-only task (retention enforcement): delete-only commit,
            # no merge — the horizon already proved every row out of range
            to_deletes = [f.id for f in task.expireds]
            await self._commit(
                stages, [], to_deletes,
                TimeRange.union_of([f.meta.time_range for f in task.expireds]),
            )
            await self._cleanup(stages, to_deletes)
            return

        time_range = TimeRange.union_of([f.meta.time_range for f in task.inputs])
        # Tombstones whose masking the merge below WILL include — captured
        # BEFORE the read so the rollup record can never claim a delete it
        # did not apply (a tombstone landing mid-task compares newer than
        # this set and forces raw until the next compaction re-emits).
        applied_tombs = tuple(sorted(
            t.id for t in self._manifest.all_tombstones()
        ))
        # Same merge pipeline as the scan path (`_scan_segment`), builtins
        # kept, and none of it on the event loop's thread: this coroutine
        # awaits the parquet decodes (a default-pool thread hop for each
        # batch's worth of rows: eight for thirty flushes of 2,000 rows),
        # then ONE worker call that runs host_prep, the planner's merge
        # (h2d, the kernel's dispatch, the wait on the device and d2h, or
        # the host route) and materialize, so a merge of any size leaves
        # the loop to the writers. What follows here on the loop is
        # `Table.from_batches` and the zero-copy slicing.
        # Memory bound: device memory is O(scan_block_rows) (hierarchical
        # chunked scan), the parquet ENCODE streams to the store at
        # O(row group + chunk) (write_sst), and the merged host columns are
        # O(task rows) — admitted only under the memory_limit gate
        # (pre_check, default 2 GiB), the same bound the reference's
        # streamed plan enforces via its task budget (executor.rs:93-114).
        # The reads funnel through the shared visibility mask under the
        # "compact" context (storage/visibility.py): tombstoned/expired
        # rows are PHYSICALLY absent from the rewritten output — this is
        # where a delete reclaims bytes.
        with _stage(stages, "scan"), vis_mod.mask_context("compact"):
            batches = await self._storage.parquet_reader.scan_segment(
                task.inputs,
                predicate=None,
                projections=None,
                keep_builtin=True,
                # a compaction reads every row group of soon-deleted inputs
                # exactly once — caching them would evict the hot query entries
                use_block_cache=False,
            )
        if not batches:
            # All inputs were empty SSTs (or every row was tombstoned/
            # expired): commit a delete-only update instead of erroring (an
            # error would unmark + re-pick the same files in an infinite
            # retry loop).
            to_deletes = [f.id for f in task.expireds] + [f.id for f in task.inputs]
            await self._commit(
                stages, [], to_deletes,
                TimeRange.union_of(
                    [f.meta.time_range for f in task.inputs + task.expireds]
                ),
            )
            await self._cleanup(stages, to_deletes)
            return
        table = pa.Table.from_batches(batches)

        # Output sharding (divergence from the reference's single output,
        # executor.rs:173-191, shared with the flush path's shard design):
        # a large merged output splits into pk-contiguous slices whose
        # parquet encodes run CONCURRENTLY on worker threads — the encode
        # was the pipeline's serial tail (VERDICT r02 #3). Shard count is
        # capped below the picker's input_sst_min_num so a fully-compacted
        # segment can never re-pick its own output in a churn loop; each
        # shard is a sorted, pk-disjoint run, so later scans take the
        # presorted O(n) merge path instead of re-sorting.
        cfg = self._storage._config.scheduler
        max_shards = max(1, cfg.input_sst_min_num - 1)
        shard_rows = max(1, cfg.output_shard_rows)
        n_shards = min(max_shards, -(-table.num_rows // shard_rows))
        per = -(-table.num_rows // n_shards)
        slices = [table.slice(i * per, per) for i in range(n_shards)]
        slices = [s for s in slices if s.num_rows > 0]
        ids = [allocate_id() for _ in slices]
        with _stage(stages, "encode"):
            # all-settle semantics: a failed shard encode must not leave its
            # siblings running detached (they would race close/teardown);
            # gather with return_exceptions, then re-raise the first failure
            results = await asyncio.gather(
                *(self._storage.write_sst(fid, s, stages=scanstats.COMPACTION_SST)
                  for fid, s in zip(ids, slices)),
                return_exceptions=True,
            )
            # compaction outputs carry the encoding descriptor of their
            # fresh sidecar (pop_enc_meta): rewriting v1 inputs under an
            # encoding-enabled config naturally upgrades the tree to
            # format v2. Popped BEFORE the failure re-raise so successful
            # siblings of a failed shard never strand their entries (the
            # orphan objects themselves are GC'd at next open).
            enc_metas = [self._storage.pop_enc_meta(fid) for fid in ids]
            for r in results:
                if isinstance(r, BaseException):
                    raise r
            sizes = results
        done["rows_out"] = table.num_rows
        done["bytes_out"] = sum(sizes)
        COMPACTION_ROWS.labels("out").inc(table.num_rows)
        new_files = [
            SstFile(
                id=fid,
                meta=FileMeta(
                    max_sequence=fid,
                    num_rows=s.num_rows,
                    size=size,
                    time_range=time_range,
                    format_version=fmt,
                    encodings=encodings,
                ),
            )
            for fid, s, size, (fmt, encodings) in zip(ids, slices, sizes, enc_metas)
        ]
        logger.debug(
            "Compact output %d sst shard(s): ids=%s rows=%d",
            len(new_files), ids, table.num_rows,
        )

        to_deletes = [f.id for f in task.expireds] + [f.id for f in task.inputs]
        await self._commit(stages, new_files, to_deletes, time_range)
        # From now on, no error should be returned (executor.rs:218-219).
        try:
            # rollup emission rides the bytes compaction already rewrote:
            # the merged table IS the segment's exact LWW-resolved,
            # tombstone-applied content. Post-commit and best-effort — a
            # failed artifact costs speed on the next dashboard refresh,
            # never correctness (the planner scans raw without it).
            with _stage(stages, "rollup"):
                await self._emit_rollups(task, table, new_files, time_range,
                                         applied_tombs)
        except Exception:  # noqa: BLE001 — perf artifact only
            logger.warning("rollup emission failed (raw scans still exact)",
                           exc_info=True)
        await self._cleanup(stages, to_deletes)

    async def _emit_rollups(
        self, task: Task, table: pa.Table, new_files: list[SstFile],
        time_range: TimeRange, applied_tombs: tuple,
    ) -> None:
        """Emit one pre-aggregated SST + registry record per configured
        resolution for a FULL-segment compaction (storage/rollup.py holds
        the freshness contract the records carry).

        Emission is skipped — never wrong — when the contract cannot be
        exact: a partial-segment task (un-merged siblings would carry
        un-deduped duplicates), a racing flush that landed mid-task (the
        output set is no longer the segment's whole live set), a
        non-OVERWRITE schema, or a table without a trailing time-column
        primary key."""
        from horaedb_tpu.serving import ROLLUPS_BUILT, resolution_label
        from horaedb_tpu.storage import rollup as rollup_mod
        from horaedb_tpu.storage.config import UpdateMode
        from horaedb_tpu.storage.types import Timestamp

        storage = self._storage
        cfg = storage.rollup_config
        if not cfg.enabled or storage.time_column is None:
            return
        if storage.schema.update_mode != UpdateMode.OVERWRITE:
            return
        pks = storage.schema.primary_key_names
        names = storage.schema.arrow_schema.names
        if not pks or pks[-1] != storage.time_column:
            return
        if cfg.value_column not in names:
            return
        if table.num_rows < max(1, cfg.min_rows):
            return
        seg_ms = storage.segment_duration_ms
        segs = {
            Timestamp(f.meta.time_range.start).truncate_by(seg_ms).value
            for f in task.inputs
        }
        if len(segs) != 1:
            return
        seg_start = segs.pop()
        seg_range = TimeRange(seg_start, seg_start + seg_ms)
        live = {
            s.id for s in self._manifest.find_ssts(seg_range)
            if Timestamp(s.meta.time_range.start).truncate_by(seg_ms).value
            == seg_start
        }
        out_ids = {f.id for f in new_files}
        if live != out_ids:
            return  # partial-segment task or a flush raced the merge
        group_cols = list(pks[:-1])
        sources = tuple(sorted(out_ids))
        for res in cfg.resolutions:
            if res <= 0 or seg_ms % res != 0:
                continue
            rtab = await storage._run_sst(
                rollup_mod.compute_rollup, table, group_cols,
                storage.time_column, cfg.value_column, res,
            )
            blob = await storage._run_sst(rollup_mod.encode_rollup, rtab)
            rid = allocate_id()
            # artifact BEFORE record: a crash between the two leaves an
            # unreferenced object the rollup orphan GC reclaims at open
            await storage.store.put(
                storage.sst_path_gen.generate_rollup(rid), blob
            )
            old = self._manifest.rollup_records().get((seg_start, res))
            record = rollup_mod.RollupRecord(
                id=allocate_id(),
                resolution_ms=res,
                segment_start=seg_start,
                sst_id=rid,
                num_rows=rtab.num_rows,
                size=len(blob),
                time_range=time_range,
                source_sst_ids=sources,
                tombstone_ids=applied_tombs,
            )
            await self._manifest.add_rollup(record)
            if old is not None:
                await self._manifest.remove_rollups([old])
            ROLLUPS_BUILT.labels(resolution_label(res)).inc()
            logger.debug(
                "rollup emitted: seg=%d res=%d rows=%d size=%d sources=%s",
                seg_start, res, rtab.num_rows, len(blob), sources,
            )

    async def _gc_rollups(self) -> None:
        """Post-commit rollup-record GC, best-effort like tombstone GC:
        records whose sources are no longer live can never pass the
        freshness contract again."""
        try:
            await self._manifest.gc_rollups()
        except Exception as e:  # noqa: BLE001 — next compaction retries
            logger.warning("rollup gc failed: %s", e)

    async def _gc_tombstones(self) -> None:
        """Post-commit tombstone GC, best-effort like physical deletes:
        records whose time range no live SST overlaps are dead weight."""
        try:
            await self._manifest.gc_tombstones()
        except Exception as e:  # noqa: BLE001 — next compaction retries
            logger.warning("tombstone gc failed: %s", e)

    async def _delete_ssts(self, ids: list[int]) -> None:
        """Best-effort parallel physical deletes (executor.rs:224-253),
        including bloom sidecars (missing ones are expected: sidecars only
        exist when bloom filters were enabled at write time)."""
        path_gen = self._storage.parquet_reader._path_gen
        for i in ids:
            self._storage.parquet_reader.evict_cached(i)
        paths = [path_gen.generate(i) for i in ids]
        bloom_paths = [path_gen.generate_bloom(i) for i in ids]
        enc_paths = [path_gen.generate_enc(i) for i in ids]
        every = paths + bloom_paths + enc_paths
        results = await self._storage._store.delete_many(every)
        from horaedb_tpu.objstore import NotFound

        for p, r in zip(every, results):
            if isinstance(r, NotFound):
                continue
            if isinstance(r, BaseException):
                logger.error("Failed to delete sst object %s: %s", p, r)
