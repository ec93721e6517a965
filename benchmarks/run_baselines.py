"""The five BASELINE.json configs, measured (SURVEY §6: 'the baseline for the
new framework is measured, not quoted').

  1. TSBS single-groupby-1: sum, 1 metric, 1 host(series), 1h window, 5m
     buckets — end-to-end through ObjectBasedStorage (parquet SSTs + device
     scan pipeline).
  2. Tag-equality predicate + range scan, 10M points / 100 series —
     end-to-end storage scan with a TSID membership predicate.
  3. Group-by-tag avg/min/max, 100M points / 1K series — device kernel path
     (sharded_grouped_stats with min/max).
  4. Time-bucket downsample (5m mean) over 1B points / 10K series — chunked
     device passes accumulating partial grids (the streaming shape the
     engine uses for segments larger than one block; chunk data is reused
     across iterations with shifted windows — throughput is content-
     independent).
  5. SST compaction: 100-way merge+dedup of overlapping sorted runs on
     device (the compaction executor's kernel).

Usage:  python benchmarks/run_baselines.py [--quick]
Prints one JSON line per config. --quick (default on CPU) shrinks sizes ~50x.
"""

from __future__ import annotations

import asyncio
import json
import sys
import tempfile
import time

sys.path.insert(0, ".")

import numpy as np  # noqa: E402


def _emit(cfg: int, name: str, n_rows: int, elapsed: float, extra: dict | None = None) -> None:
    out = {
        "config": cfg,
        "bench": name,
        "rows": n_rows,
        "seconds": round(elapsed, 4),
        "rows_per_sec": round(n_rows / elapsed),
    }
    out.update(extra or {})
    print(json.dumps(out))


# -- configs 1 & 2: end-to-end through the storage engine --------------------

async def config_1_and_2(quick: bool) -> None:
    import pyarrow as pa

    from horaedb_tpu.objstore import LocalStore
    from horaedb_tpu.ops import filter as F
    from horaedb_tpu.storage import (
        ObjectBasedStorage, ScanRequest, WriteRequest, TimeRange,
    )

    n_rows = 1_000_000 if quick else 10_000_000
    n_series = 100
    hour_ms = 3_600_000
    schema = pa.schema(
        [("series", pa.int64()), ("ts", pa.int64()), ("value", pa.float64())]
    )
    store = LocalStore(tempfile.mkdtemp(prefix="bl12_"))
    eng = await ObjectBasedStorage.try_new(
        "bl", store, schema, num_primary_keys=2, segment_duration_ms=12 * hour_ms,
        enable_compaction_scheduler=False, start_background_merger=False,
    )
    rng = np.random.default_rng(0)
    per_sst = n_rows // 8
    for i in range(8):
        batch = pa.RecordBatch.from_pydict(
            {
                "series": rng.integers(0, n_series, per_sst),
                "ts": rng.integers(0, hour_ms, per_sst),
                "value": rng.normal(size=per_sst),
            },
            schema=schema,
        )
        await eng.write(WriteRequest(batch, TimeRange(0, hour_ms)))

    async def scan_rows(pred) -> int:
        total = 0
        async for b in eng.scan(ScanRequest(range=TimeRange(0, hour_ms), predicate=pred)):
            total += b.num_rows
        return total

    from horaedb_tpu.storage.scanstats import scan_stats

    # config 1: single series, 1h, sum over 5m buckets
    pred1 = F.Compare("series", "eq", 7)
    await scan_rows(pred1)  # warm/compile
    with scan_stats() as st:
        start = time.perf_counter()
        got = 0
        async for b in eng.scan(ScanRequest(range=TimeRange(0, hour_ms), predicate=pred1)):
            ts = b.column("ts").to_numpy()
            v = b.column("value").to_numpy()
            buckets = ts // 300_000
            _ = np.bincount(buckets, weights=v, minlength=12)  # final 12-bucket sum
            got += b.num_rows
        elapsed = time.perf_counter() - start
    _emit(1, "tsbs_single_groupby_1", n_rows, elapsed,
          {"matched_rows": got, "stages": st.as_dict(),
           "note": "rows/sec = engine rows scanned over wall time"})

    # config 2: tag-equality (series membership) + range scan
    tsids = tuple(range(0, n_series, 10))
    pred2 = F.InSet("series", tsids)
    await scan_rows(pred2)  # warm
    with scan_stats() as st:
        start = time.perf_counter()
        got = await scan_rows(pred2)
        elapsed = time.perf_counter() - start
    _emit(2, "tag_predicate_range_scan", n_rows, elapsed,
          {"matched_rows": got, "series_selected": len(tsids),
           "stages": st.as_dict()})
    await eng.close()


# -- config 3: group-by-tag avg/min/max --------------------------------------

def config_3(quick: bool) -> None:
    import jax

    from horaedb_tpu.parallel import make_mesh, sharded_grouped_stats
    from horaedb_tpu.parallel.scan import shard_rows

    n = 4_000_000 if quick else 100_000_000
    groups = 1000
    rng = np.random.default_rng(1)
    gid = rng.integers(0, groups, n).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    mesh = make_mesh(1)
    (d_g, d_v), d_valid = shard_rows(mesh, (gid, vals))
    out = sharded_grouped_stats(mesh, d_g, d_v, d_valid, groups)  # warm
    probe = jax.jit(lambda o: o["sum"].sum() + o["min"].sum() + o["max"].sum())
    float(np.asarray(probe(out)))
    start = time.perf_counter()
    out = sharded_grouped_stats(mesh, d_g, d_v, d_valid, groups)
    float(np.asarray(probe(out)))
    _emit(3, "group_by_tag_avg_min_max", n, time.perf_counter() - start,
          {"groups": groups})


# -- config 4: 1B-point downsample, chunked ----------------------------------

def config_4(quick: bool) -> None:
    import jax
    import jax.numpy as jnp

    from horaedb_tpu.parallel import make_mesh
    from horaedb_tpu.parallel.scan import build_sharded_downsample

    total = 40_000_000 if quick else 1_000_000_000
    chunk = 8_000_000 if quick else 50_000_000
    num_series, bucket_ms = 10_000, 300_000
    span = 24 * 3_600_000
    num_buckets = span // bucket_ms
    rng = np.random.default_rng(2)
    ts = rng.integers(0, span, chunk, dtype=np.int64).astype(np.int32)
    sid = rng.integers(0, num_series, chunk, dtype=np.int64).astype(np.int32)
    vals = rng.normal(size=chunk).astype(np.float32)
    mesh = make_mesh(1)
    d_valid = jax.device_put(np.ones(chunk, dtype=bool))
    t0 = jnp.asarray(0, jnp.int32)
    bkt = jnp.asarray(bucket_ms, jnp.int32)
    probe = jax.jit(lambda a, b: a["sum"].sum() + b["sum"].sum())
    iters = total // chunk

    def run(order_sorted: bool) -> float:
        """Chunked accumulation. sorted=True presents each chunk in
        (series, ts) order — the engine's actual scan-output order (SSTs
        are pk-sorted; the hierarchical merge preserves it), where the
        sorted block compaction applies; sorted=False is the raw
        unsorted-points shape (auto: device sort + compaction)."""
        if order_sorted:
            order = np.lexsort((ts, sid))
            args = map(jax.device_put, (ts[order], sid[order], vals[order]))
        else:
            args = map(jax.device_put, (ts, sid, vals))
        d_ts, d_sid, d_vals = args
        fn = build_sharded_downsample(
            mesh, num_series, num_buckets, None, with_minmax=False,
            sorted_input=order_sorted,
        )
        out = fn(d_ts, d_sid, d_vals, d_valid, (), t0, bkt)  # warm
        acc = out
        float(np.asarray(probe(acc, out)))
        start = time.perf_counter()
        for _ in range(iters):
            out = fn(d_ts, d_sid, d_vals, d_valid, (), t0, bkt)
            acc = {k: acc[k] + out[k] for k in ("sum", "count")}
        float(np.asarray(probe(acc, out)))
        return time.perf_counter() - start

    unsorted_s = run(False)
    sorted_s = run(True)
    _emit(4, "downsample_5m_1b_points", iters * chunk, sorted_s,
          {"num_series": num_series, "chunks": iters, "chunk_rows": chunk,
           "note": "chunks in engine scan order (pk-sorted)",
           "unsorted_rows_per_sec": round(iters * chunk / unsorted_s)})


# -- config 5: 100-way SST merge + dedup on device ---------------------------

def config_5(quick: bool) -> None:
    import jax

    from horaedb_tpu.ops import dedup as dedup_ops
    from horaedb_tpu.ops import merge as merge_ops
    from horaedb_tpu.ops.blocks import Block

    ways = 100
    rows_per_sst = 50_000 if quick else 500_000
    key_space = ways * rows_per_sst // 4  # ~4x overlap -> real dedup work
    rng = np.random.default_rng(3)
    blocks = []
    for i in range(ways):
        pk = np.sort(rng.integers(0, key_space, rows_per_sst)).astype(np.int64)
        seq = np.full(rows_per_sst, i, dtype=np.uint64)
        val = rng.normal(size=rows_per_sst)
        blocks.append(
            Block.from_numpy(
                {"pk": pk, "__seq__": seq, "value": val},
                pad_multiple=rows_per_sst,
                pad_keys=("pk", "__seq__"),
            )
        )
    total = ways * rows_per_sst

    @jax.jit
    def merge_dedup(cols_list):
        merged = merge_ops.merge_sorted(cols_list, ["pk", "__seq__"])
        keep = dedup_ops.dedup_last_value(merged, ["pk"], total)
        return merged["value"], keep

    cols = [b.columns for b in blocks]
    v, keep = merge_dedup(cols)  # warm
    probe = jax.jit(lambda v, k: v.sum() + k.sum())
    float(np.asarray(probe(v, keep)))
    start = time.perf_counter()
    v, keep = merge_dedup(cols)
    float(np.asarray(probe(v, keep)))
    lanes_s = time.perf_counter() - start
    bytes_total = total * 24  # pk + seq + value lanes

    # packed path: the executor's production kernel — (pk, seq-rank) pack
    # into one u64 on host, the device sorts TWO lanes (key + iota) and
    # returns compacted surviving indices; values gather through the
    # permutation. Stage-attributed: pack (host) / h2d / device kernel.
    from horaedb_tpu.storage.read import _build_packed_index_kernel, _pack_sort_keys

    host_cols = {
        "pk": np.concatenate([np.asarray(b.columns["pk"][: rows_per_sst]) for b in blocks]),
        "__seq__": np.concatenate(
            [np.asarray(b.columns["__seq__"][: rows_per_sst]) for b in blocks]
        ),
    }
    t0 = time.perf_counter()
    packed, seq_width = _pack_sort_keys(host_cols.__getitem__, ("pk", "__seq__"), total)
    pack_s = time.perf_counter() - t0
    host_values = np.concatenate(
        [np.asarray(b.columns["value"][: rows_per_sst]) for b in blocks]
    )
    # H2D covers BOTH inbound lanes — the packed keys and the value lane
    # the gather permutes; leaving values untimed would hide half the
    # transfer on a slow link
    t0 = time.perf_counter()
    packed_d = jax.device_put(packed)
    values_d = jax.device_put(host_values)
    jax.block_until_ready((packed_d, values_d))
    h2d_s = time.perf_counter() - t0

    import jax.numpy as jnp

    kernel = _build_packed_index_kernel(seq_width, True)

    @jax.jit
    def packed_merge(p, vals):
        out_idx, kcnt = kernel(p, total)
        return jnp.take(vals, out_idx, axis=0), kcnt

    merged_v, kcnt = packed_merge(packed_d, values_d)  # warm
    float(np.asarray(probe(merged_v, kcnt)))
    t0 = time.perf_counter()
    merged_v, kcnt = packed_merge(packed_d, values_d)
    float(np.asarray(probe(merged_v, kcnt)))
    dev_s = time.perf_counter() - t0
    # survivors must come back to the host for the parquet encode — the
    # D2H leg is part of the job, not an externality (warm once so the
    # slice compile isn't billed as transfer)
    k = int(np.asarray(kcnt))
    np.asarray(merged_v[:k])
    t0 = time.perf_counter()
    np.asarray(merged_v[:k])
    d2h_s = time.perf_counter() - t0
    # headline = WALL CLOCK of the whole merge (pack + H2D + kernel + D2H);
    # the kernel-only number flattered the packed path on slow links
    # (VERDICT r03 weak #4) — it now lives in `stages` where it belongs
    wall_s = pack_s + h2d_s + dev_s + d2h_s
    extra = {"ways": ways, "impl": "packed", "survivors": k,
             "mb_per_sec": round(bytes_total / wall_s / 1e6, 1),
             "lanes_seconds": round(lanes_s, 4),
             "lanes_mb_per_sec": round(bytes_total / lanes_s / 1e6, 1),
             "stages": {"pack_s": round(pack_s, 4), "h2d_s": round(h2d_s, 4),
                        "device_s": round(dev_s, 4),
                        "d2h_s": round(d2h_s, 4)}}

    # sharded lane: the cross-chip sample-sort (parallel/merge.py) over
    # every local device — the multi-chip form of this merge, wall-clocked
    # end to end (host splitters/capacity + device_put + all_to_all merge +
    # collect). Skipped on a 1-device environment (it IS the packed path
    # then); on the virtual CPU mesh it validates the path, on a real
    # slice it is the config-5 scaling lane.
    n_dev = len(jax.devices())
    if n_dev > 1:
        from jax.sharding import Mesh

        from horaedb_tpu.parallel.merge import sharded_packed_merge

        # virtual CPU meshes serialize all "devices" onto the host cores:
        # cap the lane there so it validates the path instead of dominating
        # the suite's wall clock; real multi-chip runs the full size
        on_cpu = jax.devices()[0].platform == "cpu"
        sub = min(total, 1_000_000) if on_cpu else total
        sub_packed = packed[:sub]
        sub_kernel = _build_packed_index_kernel(seq_width, True)
        _, sub_kcnt = sub_kernel(sub_packed, sub)
        sub_k = int(np.asarray(sub_kcnt))
        mesh = Mesh(np.array(jax.devices()), ("m",))
        idx = sharded_packed_merge(sub_packed, seq_width, True, mesh)  # warm
        assert len(idx) == sub_k, (len(idx), sub_k)
        t0 = time.perf_counter()
        idx = sharded_packed_merge(sub_packed, seq_width, True, mesh)
        shard_s = time.perf_counter() - t0
        extra["sharded"] = {
            "devices": n_dev,
            "rows": sub,
            "seconds": round(shard_s, 4),
            "mb_per_sec": round(sub * 24 / shard_s / 1e6, 1),
            "equal_survivors": bool(len(idx) == sub_k),
            "validation_only": on_cpu,
        }
    _emit(5, "compaction_100way_merge_dedup", total, wall_s, extra)


def main() -> None:
    import os

    import jax

    # same platform escape hatch as the server entrypoint
    want = os.environ.get("HORAEDB_JAX_PLATFORM") or os.environ.get("JAX_PLATFORMS")
    if want and "," not in want:
        try:
            jax.config.update("jax_platforms", want)
        except Exception:  # noqa: BLE001 - backend already initialized
            pass

    quick = "--quick" in sys.argv or jax.devices()[0].platform == "cpu"
    asyncio.run(config_1_and_2(quick))
    config_3(quick)
    config_4(quick)
    config_5(quick)


if __name__ == "__main__":
    main()
