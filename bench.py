"""Headline benchmark: TSBS-style range-aggregate (BASELINE config 4).

Time-bucket downsample (5m mean/min/max/count) with a predicate filter over
synthetic metric rows (10K series), the north-star pipeline of
BASELINE.json: scan -> filter -> aggregate on device vs the single-thread
CPU (numpy) baseline of the same computation.

Every registered aggregation impl (ops/agg_registry.py) is A/B'd on both
the sorted and unsorted lane; the HEADLINE rides the impl the calibrated
dispatcher picks AUTOMATICALLY (no env pinning) — the bench measures what
production would actually run, and the `sorted_ab`/`unsorted_ab` dicts
plus the `agg_dispatcher` block explain why.

Prints ONE JSON line:
  {"metric": "downsample_rows_per_sec", "value": N, "unit": "rows/s",
   "vs_baseline": ratio, ...extras}

Measures on the TPU only: run without `--smoke` on any other platform it
exits non-zero before measuring, and every result it prints carries
`platform`, `device_kind` and `device_count`. `--smoke` shrinks to a
seconds-scale shape for the `make bench-smoke` gate, which drives the
dispatch plumbing on the CPU and measures nothing.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SMOKE = "--smoke" in sys.argv


def numpy_baseline(ts, sid, vals, bucket_ms, num_series, num_buckets, lo):
    """Single-node CPU oracle: the same filter+downsample with numpy."""
    mask = vals > lo
    t = ts[mask]
    s = sid[mask]
    v = vals[mask]
    flat = s.astype(np.int64) * num_buckets + (t // bucket_ms)
    sums = np.bincount(flat, weights=v, minlength=num_series * num_buckets)
    counts = np.bincount(flat, minlength=num_series * num_buckets)
    return sums, counts


def ingest_lane(smoke: bool) -> dict:
    """Engine ingest lane (ROOFLINE §7): remote-write payloads through
    write_payload end-to-end, measured two ways — PURE append (no flush
    inside the timed window: the parse + id-resolve + accumulate ceiling)
    vs WITH background flushes (threshold crossings seal memtables to the
    flush executor; the final drain is inside the timing so durability
    counts). Host-side only; runs identically with or without an
    accelerator. The with-flush/pure ratio is the measured overlap of the
    ingest->flush pipeline on this box."""
    import asyncio
    import shutil
    import tempfile

    from horaedb_tpu.engine import MetricEngine
    from horaedb_tpu.objstore import LocalStore
    from horaedb_tpu.pb import remote_write_pb2

    n_payloads = 16 if smoke else 150
    n_series, n_samples = 200, 10

    def payload(seq: int, late_pct: int = 0) -> bytes:
        """`late_pct`% of samples arrive 4 hours behind (two default 2h
        segments older than the watermark) — the out-of-order/backfill
        knob: deterministic striping, so the dirty fraction is exact."""
        base = 1_700_000_000_000 + seq * 10_000
        req = remote_write_pb2.WriteRequest()
        for s in range(n_series):
            series = req.timeseries.add()
            for k, v in ((b"__name__", f"ingest_{s % 20}".encode()),
                         (b"host", f"host-{s:04d}".encode())):
                lab = series.labels.add()
                lab.name = k
                lab.value = v
            for i in range(n_samples):
                smp = series.samples.add()
                smp.timestamp = base + i * 1000
                if late_pct and (s * n_samples + i) % 100 < late_pct:
                    smp.timestamp -= 4 * 3_600_000
                smp.value = float(s + i)
        return req.SerializeToString()

    payloads = [payload(i) for i in range(n_payloads)]
    total_rows = n_payloads * n_series * n_samples

    async def run(pls: list, buffer_rows: int, drain: bool) -> float:
        root = tempfile.mkdtemp(prefix="horaedb-bench-ingest-")
        store = LocalStore(root)
        eng = await MetricEngine.open(
            "db", store, enable_compaction=False,
            ingest_buffer_rows=buffer_rows,
        )
        try:
            await eng.write_payload(pls[0])  # warm: series registration
            await eng.flush()
            t0 = time.perf_counter()
            n = 0
            for p in pls:
                n += await eng.write_payload(p)
            if drain:
                await eng.flush()
            elapsed = time.perf_counter() - t0
        finally:
            await eng.close()
            shutil.rmtree(root, ignore_errors=True)
        return n / elapsed

    # best-of-N: the with-flush number rides the box's fsync latency,
    # which swings wildly on shared containers — the best round is the
    # pipeline's capability, the others are disk-contention noise
    rounds = 1 if smoke else 3
    # pure lane: a threshold the run can never reach (NOT a giant
    # sentinel — buffer_rows sizes real allocations on the fallback path)
    pure = max(
        asyncio.run(run(payloads, 2 * total_rows, drain=False))
        for _ in range(rounds)
    )
    # a buffer ~1/8 of the run forces several background flushes inside
    # the timed window
    flush_buffer = max(total_rows // 8, 1024)
    with_flush = max(
        asyncio.run(run(payloads, flush_buffer, drain=True))
        for _ in range(rounds)
    )
    # out-of-order-ratio lanes (dirty-traffic hardening): the SAME
    # with-flush shape at 0/5/25% late samples — the 0 lane is the
    # in-order reference so the reported overhead is same-round,
    # same-box (with_flush above is best-of-N and would understate it)
    ooo: dict[str, int] = {}
    for pct in (0, 5, 25):
        pls = payloads if pct == 0 else [
            payload(i, late_pct=pct) for i in range(n_payloads)
        ]
        ooo[str(pct)] = round(asyncio.run(run(pls, flush_buffer, drain=True)))
    overhead_pct = round((ooo["0"] / max(ooo["25"], 1) - 1) * 100, 1)

    # cardinality-sketch overhead (ingest/cardinality.py): steady-state
    # add_pairs over payload-shaped series lanes — the per-series cost the
    # limiter adds to the ingest path (budget-checked by bench-smoke)
    from horaedb_tpu.ingest.cardinality import SeriesSketch

    rng = np.random.default_rng(1)
    lanes = [
        (
            rng.integers(0, 2**63, n_series, dtype=np.int64).astype(np.uint64),
            rng.integers(0, 2**63, n_series, dtype=np.int64).astype(np.uint64),
        )
        for _ in range(32)
    ]
    sk = SeriesSketch()
    for m, t in lanes:
        sk.add_pairs(m, t)  # warm: registers settled, adds become no-ops
    reps = 20 if smoke else 100
    t0 = time.perf_counter()
    for _ in range(reps):
        for m, t in lanes:
            sk.add_pairs(m, t)
    sketch_ns = (time.perf_counter() - t0) / (reps * len(lanes) * n_series) * 1e9

    return {
        "ingest_pure_samples_per_sec": round(pure),
        "ingest_with_flush_samples_per_sec": round(with_flush),
        "ingest_rows": total_rows,
        "ingest_ooo_samples_per_sec": ooo,
        "ingest_ooo_overhead_pct": overhead_pct,
        "cardinality_sketch_ns_per_series": round(sketch_ns, 1),
    }


def query_qps_lane(smoke: bool) -> dict:
    """Closed-loop multi-client query lane through the admission
    scheduler (server/admission.py) + engine: per concurrency level
    (1/8/64 clients), QPS, p50/p99 latency, and the shed rate. The
    scheduler is sized small (cap 4, queue 16) so the 64-client level
    actually exercises shedding — the lane measures the DEGRADATION
    contract (bounded latency + 503-class sheds), not just raw speed.

    Grows the query-batching A/B (server/batching.py): the same closed
    loop over DISTINCT same-shape panels (per-client rotating host
    filters — the dashboard-of-N-panels traffic batching exists for),
    run with coalescing on vs HORAEDB_BATCH=off, forced cold
    (HORAEDB_SERVING=off) so every query real-scans and the window sees
    exactly the expensive distinct shapes. Reports per level/arm p50/p99
    + QPS, the batched_with mix, and measured pad waste."""
    import asyncio
    import os
    import shutil
    import tempfile

    from horaedb_tpu.common.error import UnavailableError
    from horaedb_tpu.engine import MetricEngine, QueryRequest
    from horaedb_tpu.objstore import LocalStore
    from horaedb_tpu.pb import remote_write_pb2
    from horaedb_tpu.server.admission import AdmissionController, run_query
    from horaedb_tpu.storage import scanstats

    n_series, n_samples = 100, 20

    def payload() -> bytes:
        req = remote_write_pb2.WriteRequest()
        base = 1_700_000_000_000
        for s in range(n_series):
            series = req.timeseries.add()
            for k, v in ((b"__name__", b"qps_cpu"),
                         (b"host", f"host-{s:04d}".encode())):
                lab = series.labels.add()
                lab.name = k
                lab.value = v
            for i in range(n_samples):
                smp = series.samples.add()
                smp.timestamp = base + i * 1000
                smp.value = float(s + i)
        return req.SerializeToString()

    wall_s = 0.4 if smoke else 2.0
    levels = (1, 8, 64)

    async def run() -> dict:
        root = tempfile.mkdtemp(prefix="horaedb-bench-qps-")
        store = LocalStore(root)
        eng = await MetricEngine.open("db", store, enable_compaction=False)
        out: dict[str, dict] = {}
        try:
            await eng.write_payload(payload())
            await eng.flush()
            base = 1_700_000_000_000
            req = QueryRequest(
                metric=b"qps_cpu", start_ms=base,
                end_ms=base + n_samples * 1000, bucket_ms=5000,
            )
            cells = 4 * n_series
            for clients in levels:
                ctl = AdmissionController(
                    max_concurrent=4, queue_max=16, queue_deadline_s=0.25,
                )
                lat: list[float] = []
                sheds = 0

                async def one_client():
                    nonlocal sheds
                    t_end = time.perf_counter() + wall_s
                    while time.perf_counter() < t_end:
                        t0 = time.perf_counter()
                        try:
                            await run_query(ctl, eng, req, cells=cells)
                        except UnavailableError:
                            sheds += 1
                            await asyncio.sleep(0.002)  # client backoff
                            continue
                        lat.append(time.perf_counter() - t0)

                t0 = time.perf_counter()
                await asyncio.gather(*(one_client() for _ in range(clients)))
                elapsed = time.perf_counter() - t0
                lat.sort()
                total = len(lat) + sheds
                out[str(clients)] = {
                    "qps": round(len(lat) / elapsed, 1),
                    "p50_ms": round(lat[len(lat) // 2] * 1000, 2) if lat else None,
                    "p99_ms": round(
                        lat[max(0, int(len(lat) * 0.99) - 1)] * 1000, 2
                    ) if lat else None,
                    "shed_pct": round(100.0 * sheds / total, 1) if total else 0.0,
                }
            out["batching"] = await batching_ab(eng, base)
        finally:
            await eng.close()
            shutil.rmtree(root, ignore_errors=True)
        return out

    async def batching_ab(eng, base: int) -> dict:
        """The coalescing A/B: distinct same-shape panels, serving forced
        cold, batching on vs HORAEDB_BATCH=off at each level."""
        def panel(k: int) -> QueryRequest:
            return QueryRequest(
                metric=b"qps_cpu", start_ms=base,
                end_ms=base + n_samples * 1000, bucket_ms=5000,
                filters=[(b"host", f"host-{k % n_series:04d}".encode())],
            )

        saved = {k: os.environ.get(k)
                 for k in ("HORAEDB_SERVING", "HORAEDB_BATCH")}
        os.environ["HORAEDB_SERVING"] = "off"
        out: dict[str, dict] = {}
        wall = 0.35 if smoke else 1.5
        try:
            # warmup: compile the stacked shapes (and the solo pushdown's)
            # outside the timed loops so the A/B measures steady state
            os.environ["HORAEDB_BATCH"] = ""
            for _ in range(3):
                await asyncio.gather(
                    *(eng.query(panel(k)) for k in range(8))
                )
            os.environ["HORAEDB_BATCH"] = "off"
            await asyncio.gather(*(eng.query(panel(k)) for k in range(8)))
            for clients in (1, 8, 64):
                row: dict[str, dict] = {}
                for arm in ("on", "off"):
                    os.environ["HORAEDB_BATCH"] = "" if arm == "on" else "off"
                    ctl = AdmissionController(
                        max_concurrent=8, queue_max=max(16, clients),
                        queue_deadline_s=2.0,
                    )
                    lat: list[float] = []
                    sheds = 0
                    mix: dict[str, int] = {}
                    waste: list[int] = []
                    t_end = time.perf_counter() + wall

                    async def one_client(seed: int):
                        nonlocal sheds
                        i = 0
                        while time.perf_counter() < t_end:
                            req = panel(seed * 37 + i)
                            i += 1
                            t0 = time.perf_counter()
                            try:
                                with scanstats.scan_stats() as st:
                                    await run_query(ctl, eng, req,
                                                    cells=4)
                            except UnavailableError:
                                sheds += 1
                                await asyncio.sleep(0.002)
                                continue
                            lat.append(time.perf_counter() - t0)
                            bw = st.counts.get("batched_with")
                            if bw:
                                mix[str(bw)] = mix.get(str(bw), 0) + 1
                            if "batch_pad_waste_pct" in st.counts:
                                waste.append(
                                    st.counts["batch_pad_waste_pct"]
                                )
                            await asyncio.sleep(0)

                    t0 = time.perf_counter()
                    await asyncio.gather(
                        *(one_client(c) for c in range(clients))
                    )
                    elapsed = time.perf_counter() - t0
                    lat.sort()
                    row[arm] = {
                        "qps": round(len(lat) / elapsed, 1),
                        "p50_ms": round(lat[len(lat) // 2] * 1000, 3)
                        if lat else None,
                        "p99_ms": round(
                            lat[max(0, int(len(lat) * 0.99) - 1)] * 1000, 3
                        ) if lat else None,
                        "shed_pct": round(
                            100.0 * sheds / (len(lat) + sheds), 1
                        ) if (lat or sheds) else 0.0,
                        "batched_with_mix": dict(sorted(mix.items())),
                    }
                    if waste:
                        row[arm]["pad_waste_pct_avg"] = round(
                            sum(waste) / len(waste), 1
                        )
                out[str(clients)] = row
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return out

    return {"query_qps": asyncio.run(run())}


def cluster_scaleout_lane(smoke: bool) -> dict:
    """Cluster lane (horaedb_tpu/cluster): closed-loop read QPS at
    1/8/64 clients against ONE writer vs the SAME writer + 2 stateless
    read replicas on one bucket, with live ingest churning underneath
    (the replicas tail manifests via the conditional-GET watch loop).

    Reported: per-level QPS/p50/p99/shed for both arms, the scale-out
    factor (replica-arm QPS / writer-only QPS at the top level), replica
    lag p99 under churn, and `replica_exact` — replicas answered
    bit-identically to the writer after catch-up (bench_smoke asserts
    it). Honesty caveat carried in the JSON: all three "nodes" share one
    process/event loop here, so the lane measures the ROUTING + per-node
    admission-cap contract (each node gets its own scheduler), not
    cross-host CPU scaling; serving is forced cold so every query really
    scans."""
    import asyncio
    import os
    import shutil
    import tempfile

    from horaedb_tpu.cluster import rendezvous_pick
    from horaedb_tpu.common.error import UnavailableError
    from horaedb_tpu.engine import MetricEngine, QueryRequest
    from horaedb_tpu.objstore import LocalStore
    from horaedb_tpu.pb import remote_write_pb2
    from horaedb_tpu.server.admission import (
        AdmissionController,
        run_query,
        run_query_partials,
    )

    n_series, n_samples = 100, 20
    base = 1_700_000_000_000

    def payload(seq: int = 0, rows: int = n_samples) -> bytes:
        req = remote_write_pb2.WriteRequest()
        for s in range(n_series if seq == 0 else 4):
            series = req.timeseries.add()
            for k, v in ((b"__name__", b"cluster_cpu"),
                         (b"host", f"host-{s:04d}".encode())):
                lab = series.labels.add()
                lab.name = k
                lab.value = v
            for i in range(rows):
                smp = series.samples.add()
                smp.timestamp = base + seq * 60_000 + i * 1000
                smp.value = float(s + i)
        return req.SerializeToString()

    wall_s = 0.3 if smoke else 1.5
    levels = (1, 8, 64)

    async def forwarded_write_ab(smoke: bool) -> dict:
        """Trace-shipping overhead on the FORWARDED write path: the same
        replica->writer HTTP forward, A/B'd with tracing off (no spans,
        no headers, no shipping) vs full sampling (remote adopt + subtree
        export + graft), over real aiohttp servers so the measured hop
        includes the router's traced client funnel end to end. The
        acceptance bar is <5% added to the forwarded-request p50."""
        import socket

        from aiohttp import ClientSession, ClientTimeout, web

        from horaedb_tpu.common import tracing
        from horaedb_tpu.server.config import Config
        from horaedb_tpu.server.main import build_app

        socks, ports = [], []
        for _ in range(2):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
        for s in socks:
            s.close()
        wport, rport = ports

        def cfg(port: int, node: str, role: str, peers: list) -> Config:
            return Config.from_dict({
                "port": port,
                "metric_engine": {
                    "node_id": node,
                    "rules": {"enabled": False},
                    "telemetry": {"enabled": False},
                    "storage": {"object_store": {"type": "Local",
                                                 "data_dir": http_root}},
                    "cluster": {
                        "enabled": True,
                        "role": role,
                        "watch_interval": "30s",
                        "probe_interval": "30s",
                        "self_url": f"http://127.0.0.1:{port}",
                        "peers": peers,
                    },
                },
            })

        async def boot(config: Config):
            app = await build_app(config)
            runner = web.AppRunner(app, handler_cancellation=True,
                                   shutdown_timeout=1.0)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", config.port)
            await site.start()
            return runner

        def fwd_payload(seq: int) -> bytes:
            req = remote_write_pb2.WriteRequest()
            for s in range(4):
                series = req.timeseries.add()
                for k, v in ((b"__name__", b"fwd_cpu"),
                             (b"host", f"fwd-{s:02d}".encode())):
                    lab = series.labels.add()
                    lab.name = k
                    lab.value = v
                smp = series.samples.add()
                smp.timestamp = base + seq * 1000
                smp.value = float(seq)
            return req.SerializeToString()

        warmup, iters = (10, 50) if smoke else (25, 200)
        prev_sample = tracing._sample_rate
        http_root = tempfile.mkdtemp(prefix="horaedb-bench-fwd-")
        runners = []
        out: dict = {}
        try:
            runners.append(await boot(cfg(
                wport, "bw1", "writer",
                [{"node": "br1", "url": f"http://127.0.0.1:{rport}",
                  "role": "replica"}])))
            runners.append(await boot(cfg(
                rport, "br1", "replica",
                [{"node": "bw1", "url": f"http://127.0.0.1:{wport}",
                  "role": "writer"}])))
            rbase = f"http://127.0.0.1:{rport}"
            async with ClientSession(
                timeout=ClientTimeout(total=10)
            ) as sess:
                # deterministic peer health before timing anything
                await sess.post(f"{rbase}/api/v1/cluster/refresh")

                async def one(sample: float, seq: int) -> float:
                    tracing.configure(sample=sample)
                    body = fwd_payload(seq)
                    t0 = time.perf_counter()
                    async with sess.post(
                        f"{rbase}/api/v1/write", data=body,
                        headers={"Content-Type":
                                 "application/x-protobuf"},
                    ) as r:
                        assert r.status == 200, await r.text()
                    return time.perf_counter() - t0

                # interleaved arms: alternating traced/untraced requests
                # share any warmup/GC/flush drift instead of one arm
                # eating all of it (sequential arms bias the later one)
                off_lat: list[float] = []
                on_lat: list[float] = []
                for i in range(warmup + iters):
                    a = await one(0.0, 2 * i)
                    b = await one(1.0, 2 * i + 1)
                    if i >= warmup:
                        off_lat.append(a)
                        on_lat.append(b)
                off_lat.sort()
                on_lat.sort()
                p50_off = off_lat[len(off_lat) // 2] * 1000
                p50_on = on_lat[len(on_lat) // 2] * 1000
            out = {
                "p50_ms_untraced": round(p50_off, 3),
                "p50_ms_traced": round(p50_on, 3),
                "trace_ship_overhead_pct": round(
                    100.0 * (p50_on - p50_off) / max(p50_off, 1e-9), 1
                ),
                "iters_per_arm": iters,
            }
        finally:
            tracing.configure(sample=prev_sample)
            for r in runners:
                try:
                    await r.cleanup()
                except Exception:  # noqa: BLE001 — bench teardown
                    pass
            shutil.rmtree(http_root, ignore_errors=True)
        return out

    async def scatter_ab(smoke: bool) -> dict:
        """Scatter-gather A/B (the distributed read path): the SAME
        range-aggregate query answered by the two read topologies over a
        regioned writer + 2 regioned computing replicas on one bucket:

        - whole_forward: the pre-split topology — the writer only
          RELAYS grid reads (route_reads offload), and the router's
          cache-affinity rendezvous keys on the QUERY identity, so a
          repeated dashboard panel lands whole on ONE pinned replica:
          full all-regions scan + the full JSON grid body that peer
          ships back (the relay is zero-parse, nothing else charged).
        - split_compute: the scatter plan — every node (the writer's
          coordinator-steal shard included) scans only its region
          fragment under its OWN admission slot, ships binary partial
          grids (cluster/partial encode/decode), and the coordinator
          folds them in canonical region order and builds the final
          JSON body.

        Two kinds of numbers, because the three "nodes" share one
        process and one core:

        1. Per-level closed-loop wall QPS at 1/8/64 clients under the
           sibling arms' per-node admission caps — real wall clock, but
           a single core serializes all three nodes, so topology-level
           parallelism CANNOT show up here (`speedup_wall`).
        2. `capacity_speedup` — the near-linear-scaling headline, from
           sequentially CALIBRATED per-node service times: the
           bottleneck node's busy time per query in each arm
           (whole_forward: the pinned replica does everything;
           split_compute: max over the coordinator's fragment + decode
           + fold + final body vs a replica's fragment + encode). On
           nodes with their own CPUs, sustained fleet QPS is
           1/bottleneck-busy — this ratio is what 3 computing nodes buy
           over a pinned whole-query replica, measured not assumed. The
           acceptance bar (>=1.6x on the 8/64-client lanes) reads this.

        Response production is charged exactly once per query in both
        arms (on the computing peer / on the coordinator). `split_exact`
        is the u64-view bit-equality of the merged split answer vs the
        single-node scan — the property the wire format + fixed fold
        order exist to keep."""
        import json as json_mod
        from dataclasses import replace as dc_replace

        import numpy as np

        from horaedb_tpu.cluster.partial import (
            decode_partials,
            encode_partials,
            merge_partials,
        )
        from horaedb_tpu.cluster.replica import ReplicaEngine
        from horaedb_tpu.engine.region import RegionedEngine

        # dashboard-shaped grid: 120 series x 24 buckets over 120
        # samples/series. Small enough that queue dynamics (the capacity
        # contract above), not raw event-loop CPU, are the binding
        # resource at 8/64 clients — the same regime the sibling arms
        # measure.
        n_sg = 120
        sg_samples = 120
        sg_bucket_ms = 5000
        sg_wall = 1.0 if smoke else wall_s

        def sg_payload() -> bytes:
            req = remote_write_pb2.WriteRequest()
            for s in range(n_sg):
                series = req.timeseries.add()
                for k, v in ((b"__name__", b"sg_cpu"),
                             (b"host", f"sg-{s:04d}".encode())):
                    lab = series.labels.add()
                    lab.name = k
                    lab.value = v
                for i in range(sg_samples):
                    smp = series.samples.add()
                    smp.timestamp = base + i * 1000
                    smp.value = float(s + i)
            return req.SerializeToString()

        root = tempfile.mkdtemp(prefix="horaedb-bench-scatter-")
        store = LocalStore(root)
        writer = await RegionedEngine.open("db", store, num_regions=3,
                                           enable_compaction=False)
        reps = []
        out: dict = {}
        try:
            await writer.write_payload(sg_payload())
            await writer.flush()
            for _ in range(2):
                reps.append(await ReplicaEngine.open(
                    "db", store, num_regions=3,
                ))
            nodes = [writer] + reps
            order = [int(r) for r in writer.engines]
            # one region shard per node — plan_scatter's cap fill for
            # R=3, N=3
            plan = {i: [order[i]] for i in range(3)}
            req = QueryRequest(
                metric=b"sg_cpu", start_ms=base,
                end_ms=base + sg_samples * 1000, bucket_ms=sg_bucket_ms,
            )
            n_buckets = (sg_samples * 1000 + sg_bucket_ms - 1) // sg_bucket_ms
            cells = n_sg * n_buckets

            # correctness first: merged split answer vs single-node scan
            tsids, grids = await writer.query(req)
            parts = []
            for i, node in enumerate(nodes):
                frag = await node.query_partial_grids(
                    dc_replace(req, regions=plan[i]))
                buf = encode_partials(f"n{i}", frag)
                parts.extend(decode_partials(buf)[1])
            merged = merge_partials(parts, order=order)
            exact = merged is not None and merged[0] == tsids and all(
                np.array_equal(
                    np.asarray(merged[1][k]).view(np.uint64),
                    np.asarray(grids[k]).view(np.uint64),
                )
                for k in ("sum", "count", "min", "max", "mean")
            )
            out["split_exact"] = bool(exact)
            body = json_mod.dumps({
                "tsids": [int(t) for t in tsids],
                "mean": grids["mean"].tolist(),
                "count": grids["count"].tolist(),
            })
            split_wire = 0
            for i in range(3):
                split_wire += len(encode_partials(
                    f"n{i}",
                    await nodes[i].query_partial_grids(
                        dc_replace(req, regions=plan[i])),
                ))
            out["wire_bytes_per_query"] = {
                "whole_forward_json": len(body),
                "split_partials": split_wire,
            }

            # --- capacity calibration: sequential (single in-flight
            # query, nothing interleaving), so each timing is one
            # node's busy time, uninflated by other tasks ---
            cal_reps = 10 if smoke else 30

            def _final_body(t, g) -> None:
                json_mod.dumps({
                    "tsids": [int(x) for x in t],
                    "mean": g["mean"].tolist(),
                    "count": g["count"].tolist(),
                })

            async def _time(coro_fn) -> float:
                await coro_fn()  # warm
                t0 = time.perf_counter()
                for _ in range(cal_reps):
                    await coro_fn()
                return (time.perf_counter() - t0) / cal_reps

            async def _whole_service() -> None:
                # the pinned replica does everything: full scan + body
                t, g = await reps[0].query(req)
                _final_body(t, g)

            frag_bufs: dict[int, bytes] = {}

            def _frag_service(i: int):
                async def go() -> None:
                    res = await nodes[i].query_partial_grids(
                        dc_replace(req, regions=plan[i]))
                    frag_bufs[i] = encode_partials(f"n{i}", res)
                return go

            async def _coord_extra() -> None:
                # decode + canonical fold + final body, on the writer
                gathered: list = []
                for buf in frag_bufs.values():
                    gathered.extend(decode_partials(buf)[1])
                mt, mg = merge_partials(gathered, order=order)
                _final_body(mt, mg)

            whole_busy = await _time(_whole_service)
            frag_busy = [await _time(_frag_service(i)) for i in range(3)]
            coord_busy = frag_busy[0] + await _time(_coord_extra)
            split_bottleneck = max(coord_busy, *frag_busy[1:])
            out["node_busy_ms_per_query"] = {
                "whole_forward_pinned_replica": round(whole_busy * 1e3, 2),
                "split_coordinator": round(coord_busy * 1e3, 2),
                "split_replica_fragment": round(
                    max(frag_busy[1:]) * 1e3, 2),
            }
            out["capacity_speedup"] = round(
                whole_busy / max(split_bottleneck, 1e-9), 2)

            node_names = [f"n{i}" for i in range(3)]
            # the whole-forward pin: same query => same rendezvous key
            # => same replica, every client
            pin = node_names.index(rendezvous_pick(
                b"/api/v1/query?sg_cpu", node_names[1:]))
            for clients in levels:
                row: dict = {}
                for arm in ("whole_forward", "split_compute"):
                    # the sibling arms' per-node caps — same contract
                    ctls = [
                        AdmissionController(
                            max_concurrent=2, queue_max=16,
                            queue_deadline_s=0.25,
                        )
                        for _ in nodes
                    ]
                    lat: list[float] = []
                    sheds = 0

                    async def one_whole(idx: int) -> None:
                        t, g = (await run_query(
                            ctls[idx], nodes[idx], req, cells=cells))[0]
                        # the computing peer builds the full JSON grid
                        # body it ships back; the writer relay is
                        # zero-parse, so nothing else is charged
                        json_mod.dumps({
                            "tsids": [int(x) for x in t],
                            "mean": g["mean"].tolist(),
                            "count": g["count"].tolist(),
                        })

                    async def one_split() -> None:
                        async def frag(i: int) -> bytes:
                            frag_req = dc_replace(req, regions=plan[i])
                            res = (await run_query_partials(
                                ctls[i], nodes[i], frag_req,
                                cells=cells // 3,
                            ))[0]
                            return encode_partials(f"n{i}", res)
                        bufs = await asyncio.gather(
                            *(frag(i) for i in range(3)))
                        gathered: list = []
                        for buf in bufs:
                            gathered.extend(decode_partials(buf)[1])
                        mt, mg = merge_partials(gathered, order=order)
                        # the coordinator produces the final body here
                        json_mod.dumps({
                            "tsids": [int(x) for x in mt],
                            "mean": mg["mean"].tolist(),
                            "count": mg["count"].tolist(),
                        })

                    async def one_client(cid: int) -> None:
                        nonlocal sheds
                        t_end = time.perf_counter() + sg_wall
                        while time.perf_counter() < t_end:
                            t0 = time.perf_counter()
                            try:
                                if arm == "whole_forward":
                                    await one_whole(pin)
                                else:
                                    await one_split()
                            except UnavailableError:
                                sheds += 1
                                await asyncio.sleep(0.002)
                                continue
                            lat.append(time.perf_counter() - t0)

                    t0 = time.perf_counter()
                    await asyncio.gather(
                        *(one_client(c) for c in range(clients)))
                    elapsed = time.perf_counter() - t0
                    lat.sort()
                    total = len(lat) + sheds
                    row[arm] = {
                        "qps": round(len(lat) / elapsed, 1),
                        "p50_ms": round(lat[len(lat) // 2] * 1000, 2)
                        if lat else None,
                        "p99_ms": round(
                            lat[max(0, int(len(lat) * 0.99) - 1)] * 1000,
                            2,
                        ) if lat else None,
                        "shed_pct": round(100.0 * sheds / total, 1)
                        if total else 0.0,
                    }
                w_qps = row["whole_forward"]["qps"]
                s_qps = row["split_compute"]["qps"]
                row["speedup_wall"] = round(s_qps / max(w_qps, 1e-9), 2)
                out[str(clients)] = row
            out["scale_out_split"] = out["capacity_speedup"]
        finally:
            for r in reps:
                await r.close()
            await writer.close()
            shutil.rmtree(root, ignore_errors=True)
        return out

    async def run() -> dict:
        root = tempfile.mkdtemp(prefix="horaedb-bench-cluster-")
        store = LocalStore(root)
        writer = await MetricEngine.open("db", store,
                                         enable_compaction=False)
        out: dict = {}
        saved = os.environ.get("HORAEDB_SERVING")
        os.environ["HORAEDB_SERVING"] = "off"
        replicas = []
        try:
            from horaedb_tpu.cluster.replica import ReplicaEngine

            await writer.write_payload(payload())
            await writer.flush()
            for _ in range(2):
                replicas.append(await ReplicaEngine.open(
                    "db", store, engine_kwargs={},
                ))
            req = QueryRequest(
                metric=b"cluster_cpu", start_ms=base,
                end_ms=base + n_samples * 1000, bucket_ms=5000,
            )
            # replica-served correctness after catch-up: bit-identical
            wt = await writer.query(req)
            exact = True
            for r in replicas:
                rt = await r.query(req)
                exact = exact and (
                    rt[1]["sum"].tolist() == wt[1]["sum"].tolist()
                    and rt[0] == wt[0]
                )
            out["replica_exact"] = bool(exact)

            # live churn: the writer commits small batches while the
            # replicas tail — lag p99 is measured under real movement
            stop = asyncio.Event()
            lag_ms: list[float] = []

            async def churn():
                seq = 1
                while not stop.is_set():
                    try:
                        await writer.write_payload(payload(seq, rows=2))
                        await writer.flush()
                    except Exception:  # noqa: BLE001 — bench keeps going
                        pass
                    seq += 1
                    await asyncio.sleep(0.05)

            async def tail(rep):
                while not stop.is_set():
                    try:
                        # sample the lag AS SEEN AT the probe (time since
                        # the view was last confirmed current) — after a
                        # successful probe it is ~0 by definition
                        lag_ms.append(rep.staleness_ms())
                        await rep.watch_once()
                    except Exception:  # noqa: BLE001
                        pass
                    await asyncio.sleep(0.02)

            bg = [asyncio.create_task(churn())] + [
                asyncio.create_task(tail(r)) for r in replicas
            ]
            cells = 4 * n_series
            arms = {
                "writer_only": [writer],
                "writer_plus_2_replicas": [writer] + replicas,
            }
            for clients in levels:
                row = {}
                for arm, nodes in arms.items():
                    # one bounded scheduler PER NODE — the per-process
                    # caps a real deployment would run
                    ctls = [
                        AdmissionController(
                            max_concurrent=2, queue_max=16,
                            queue_deadline_s=0.25,
                        )
                        for _ in nodes
                    ]
                    node_names = [f"n{i}" for i in range(len(nodes))]
                    lat: list[float] = []
                    sheds = 0

                    async def one_client(cid: int):
                        nonlocal sheds
                        # rendezvous on the client identity: one client's
                        # repeats stay on one node, like the router
                        pick = rendezvous_pick(
                            f"client-{cid}".encode(), node_names
                        )
                        idx = node_names.index(pick)
                        t_end = time.perf_counter() + wall_s
                        while time.perf_counter() < t_end:
                            t0 = time.perf_counter()
                            try:
                                await run_query(
                                    ctls[idx], nodes[idx], req, cells=cells
                                )
                            except UnavailableError:
                                sheds += 1
                                await asyncio.sleep(0.002)
                                continue
                            lat.append(time.perf_counter() - t0)

                    t0 = time.perf_counter()
                    await asyncio.gather(
                        *(one_client(c) for c in range(clients))
                    )
                    elapsed = time.perf_counter() - t0
                    lat.sort()
                    total = len(lat) + sheds
                    row[arm] = {
                        "qps": round(len(lat) / elapsed, 1),
                        "p50_ms": round(lat[len(lat) // 2] * 1000, 2)
                        if lat else None,
                        "p99_ms": round(
                            lat[max(0, int(len(lat) * 0.99) - 1)] * 1000, 2
                        ) if lat else None,
                        "shed_pct": round(100.0 * sheds / total, 1)
                        if total else 0.0,
                    }
                out[str(clients)] = row
            stop.set()
            await asyncio.gather(*bg, return_exceptions=True)
            out["forwarded_write"] = await forwarded_write_ab(smoke)
            out["scatter_gather"] = await scatter_ab(smoke)
            top = str(levels[-1])
            w_qps = out[top]["writer_only"]["qps"]
            c_qps = out[top]["writer_plus_2_replicas"]["qps"]
            out["scale_out_factor"] = round(c_qps / max(w_qps, 1e-9), 2)
            if lag_ms:
                lag_ms.sort()
                out["replica_lag_p99_ms"] = round(
                    lag_ms[max(0, int(len(lag_ms) * 0.99) - 1)], 1
                )
            out["honesty"] = (
                "single-process simulation: per-node admission caps + "
                "routing measured; cross-host CPU scaling is not"
            )
        finally:
            if saved is None:
                os.environ.pop("HORAEDB_SERVING", None)
            else:
                os.environ["HORAEDB_SERVING"] = saved
            for r in replicas:
                await r.close()
            await writer.close()
            shutil.rmtree(root, ignore_errors=True)
        return out

    return {"cluster_scaleout": asyncio.run(run())}


def query_serving_lane(smoke: bool) -> dict:
    """Serving-tier lane (horaedb_tpu/serving + storage/rollup.py): a
    zipf(1.1)-repeated dashboard workload over 64 distinct panels —
    production dashboard traffic re-runs the same few panels every
    refresh — through the admission scheduler at 1/8/64 clients.

    Reports:
    - cold p50/p99 (every panel's FIRST execution: result-cache miss,
      real scan — with rollup substitution where the grid aligns);
    - the rollup substitution rate across the panel set (fraction of
      panels whose plan folded pre-aggregated artifacts instead of raw
      segment scans);
    - per concurrency level: warm p50/p99 + QPS of the zipf-repeated
      traffic and the measured result-cache hit rate (the acceptance
      bar: warm p50 >= 3x faster than cold, hit rate > 80%)."""
    import asyncio
    import shutil
    import tempfile

    from horaedb_tpu.common.error import UnavailableError
    from horaedb_tpu.engine import MetricEngine, QueryRequest
    from horaedb_tpu.objstore import LocalStore
    from horaedb_tpu.pb import remote_write_pb2
    from horaedb_tpu.server.admission import AdmissionController, run_query
    from horaedb_tpu.serving import CACHE_REQUESTS
    from horaedb_tpu.serving.cache import RESULT_CACHE
    from horaedb_tpu.storage import scanstats
    from horaedb_tpu.storage.config import SchedulerConfig, StorageConfig

    MIN = 60_000
    HOUR = 3_600_000
    n_hosts = 16 if smoke else 64
    hours = 2 if smoke else 4
    n_panels = 64
    wall_s = 0.3 if smoke else 2.0
    levels = (1, 8, 64)

    def payload(minute_lo: int, minute_hi: int) -> bytes:
        """Per-minute integer-valued samples for every host across all
        hour-segments — two halves so each segment holds two SSTs and
        qualifies for compaction (rollup emission rides it)."""
        req = remote_write_pb2.WriteRequest()
        for h in range(n_hosts):
            series = req.timeseries.add()
            for k, v in ((b"__name__", b"panel_cpu"),
                         (b"host", f"host-{h:02d}".encode())):
                lab = series.labels.add()
                lab.name = k
                lab.value = v
            for hr in range(hours):
                for m in range(minute_lo, minute_hi):
                    smp = series.samples.add()
                    smp.timestamp = hr * HOUR + m * MIN
                    smp.value = float(h + hr * 100 + m)
        return req.SerializeToString()

    def panels() -> list:
        """64 DISTINCT dashboard panels across four shape families —
        unfiltered overview grids at aligned (window, step) combos,
        per-host per-minute drill-downs, raw recent windows, and
        host-filtered hourly overviews. Three of the four families are
        rollup-aligned (they substitute artifacts); the raw family
        always scans."""
        out = []
        wins = [(a, b) for a in range(hours) for b in range(a + 1, hours + 1)]
        steps = (HOUR, 30 * MIN, 15 * MIN, 10 * MIN, 6 * MIN, 5 * MIN)
        for a, b, s in [(a, b, s) for s in steps for (a, b) in wins][:16]:
            out.append(QueryRequest(
                metric=b"panel_cpu", start_ms=a * HOUR, end_ms=b * HOUR,
                bucket_ms=s,
            ))
        for j in range(16):  # drill-downs: distinct (hour, host) combos
            hr = j % hours
            host = f"host-{(j // hours) % n_hosts:02d}".encode()
            out.append(QueryRequest(
                metric=b"panel_cpu", start_ms=hr * HOUR,
                end_ms=(hr + 1) * HOUR, bucket_ms=MIN,
                filters=[(b"host", host)],
            ))
        for j in range(16):  # raw windows at distinct offsets
            lo = (j * 7) % (hours * 60 - 10)
            out.append(QueryRequest(
                metric=b"panel_cpu", start_ms=lo * MIN,
                end_ms=(lo + 10) * MIN,
            ))
        for j in range(16):  # host-filtered full-range overviews
            host = f"host-{j % n_hosts:02d}".encode()
            out.append(QueryRequest(
                metric=b"panel_cpu", start_ms=0, end_ms=hours * HOUR,
                bucket_ms=HOUR, filters=[(b"host", host)],
            ))
        return out

    # zipf(1.1) over panel RANKS: the classic dashboard skew (a few hot
    # panels dominate, a long warm tail still repeats)
    rng = np.random.default_rng(7)
    zipf_p = 1.0 / np.arange(1, n_panels + 1) ** 1.1
    zipf_p /= zipf_p.sum()

    async def run() -> dict:
        root = tempfile.mkdtemp(prefix="horaedb-bench-serving-")
        store = LocalStore(root)
        cfg = StorageConfig()
        cfg.scheduler = SchedulerConfig(input_sst_min_num=2)
        eng = await MetricEngine.open(
            "db", store, segment_duration_ms=HOUR, enable_compaction=True,
            config=cfg,
        )
        try:
            for lo, hi in ((0, 30), (30, 60)):
                await eng.write_payload(payload(lo, hi))
                await eng.flush()
            # compact every segment so rollup artifacts exist (the picker
            # is driven directly: the trigger channel rides a background
            # loop the bench should not race)
            sched = eng.data_table.compaction_scheduler
            for _ in range(hours * 4):
                picked = sched.pick_once()
                while sched._tasks.qsize() or sched.executor._inflight:
                    await asyncio.sleep(0.001)
                    await sched.executor.drain()
                if not picked:
                    break
            reqs = panels()
            cells = n_hosts * hours  # hourly-grid panel cost estimate

            # ---- cold pass: every panel's first execution (all misses)
            RESULT_CACHE.clear()  # bench harness resets state between passes
            cold_lat: list[float] = []
            subst = 0
            for req in reqs:
                with scanstats.scan_stats() as st:
                    t0 = time.perf_counter()
                    await eng.query(req)
                    cold_lat.append(time.perf_counter() - t0)
                if st.counts.get("rollup_segments"):
                    subst += 1
            cold_lat.sort()

            # ---- warm zipf traffic through admission per level
            out_levels: dict[str, dict] = {}
            for clients in levels:
                ctl = AdmissionController(
                    max_concurrent=4, queue_max=max(16, clients),
                    queue_deadline_s=2.0,
                )
                hit0 = CACHE_REQUESTS.labels("hit").value
                miss0 = CACHE_REQUESTS.labels("miss").value
                lat: list[float] = []
                sheds = 0
                # shared absolute deadline + an explicit per-iteration
                # yield: a cache-hit query can complete without ever
                # suspending, and a per-client relative deadline would
                # then serialize the "concurrent" clients (64 x wall_s)
                t_end = time.perf_counter() + wall_s

                async def one_client(seed: int):
                    nonlocal sheds
                    crng = np.random.default_rng(seed)
                    while time.perf_counter() < t_end:
                        req = reqs[int(crng.choice(n_panels, p=zipf_p))]
                        t0 = time.perf_counter()
                        try:
                            await run_query(ctl, eng, req, cells=cells)
                        except UnavailableError:
                            sheds += 1
                            await asyncio.sleep(0.002)
                            continue
                        lat.append(time.perf_counter() - t0)
                        await asyncio.sleep(0)

                t0 = time.perf_counter()
                await asyncio.gather(
                    *(one_client(100 + clients * 1000 + c)
                      for c in range(clients))
                )
                elapsed = time.perf_counter() - t0
                lat.sort()
                hits = CACHE_REQUESTS.labels("hit").value - hit0
                misses = CACHE_REQUESTS.labels("miss").value - miss0
                looked = hits + misses
                out_levels[str(clients)] = {
                    "qps": round(len(lat) / elapsed, 1),
                    "p50_ms": round(lat[len(lat) // 2] * 1000, 3)
                    if lat else None,
                    "p99_ms": round(
                        lat[max(0, int(len(lat) * 0.99) - 1)] * 1000, 3
                    ) if lat else None,
                    "hit_rate": round(hits / looked, 3) if looked else None,
                    "shed_pct": round(
                        100.0 * sheds / (len(lat) + sheds), 1
                    ) if (lat or sheds) else 0.0,
                }
            cold_p50 = cold_lat[len(cold_lat) // 2] * 1000
            warm_p50 = out_levels["1"]["p50_ms"]
            return {
                "panels": n_panels,
                "cold_p50_ms": round(cold_p50, 3),
                "cold_p99_ms": round(
                    cold_lat[max(0, int(len(cold_lat) * 0.99) - 1)] * 1000, 3
                ),
                "rollup_substitution_rate": round(subst / n_panels, 3),
                "warm_vs_cold_p50": round(cold_p50 / warm_p50, 1)
                if warm_p50 else None,
                "levels": out_levels,
            }
        finally:
            await eng.close()
            shutil.rmtree(root, ignore_errors=True)

    return {"query_serving": asyncio.run(run())}


def rule_storm_lane(smoke: bool) -> dict:
    """Rule-storm lane (horaedb_tpu/rules): N recording rules + M alert
    rules over one scraped metric, proving the dirty-set path.

    Reports:
    - `materialize`: the first tick (every rule evaluates its full span
      — the worst case a naive engine pays EVERY tick), rules/s;
    - `incremental`: K rounds of one-minute ingest + tick (every rule
      re-evaluates only the smeared dirty steps), per-tick p50/p99 and
      the post-tick eval lag (0 = fully caught up);
    - `quiet`: a no-mutation tick — the dirty-set skip path — which must
      evaluate ZERO rules and beat the materialize tick by >10x (the
      acceptance bar bench-smoke pins);
    - `alert_cache_hit_rate`: M alert rules sharing one selector at one
      tick instant ride the result cache — N standing queries, one scan."""
    import asyncio

    from horaedb_tpu.engine import MetricEngine
    from horaedb_tpu.objstore import MemStore
    from horaedb_tpu.pb import remote_write_pb2
    from horaedb_tpu.rules import AlertRule, RecordingRule
    from horaedb_tpu.rules.engine import RuleEngine
    from horaedb_tpu.serving import CACHE_REQUESTS

    MIN = 60_000
    BASE = 1_700_000_000_000
    n_rec = 150 if smoke else 10_000
    n_alert = 100 if smoke else 1_000
    n_hosts = 4
    warm_minutes = 10 if smoke else 30
    k_rounds = 3 if smoke else 5

    def payload(minute_lo: int, minute_hi: int) -> bytes:
        req = remote_write_pb2.WriteRequest()
        for h in range(n_hosts):
            series = req.timeseries.add()
            for k, v in ((b"__name__", b"storm_cpu"),
                         (b"host", f"h{h}".encode())):
                lab = series.labels.add()
                lab.name = k
                lab.value = v
            for m in range(minute_lo, minute_hi):
                smp = series.samples.add()
                smp.timestamp = BASE + m * MIN + 10_000
                smp.value = float(h * 100 + m)
        return req.SerializeToString()

    async def run() -> dict:
        store = MemStore()
        eng = await MetricEngine.open(
            "storm", store, enable_compaction=False,
        )
        rules = await RuleEngine.open(eng, store, root="storm/rules")
        try:
            await eng.write_payload(payload(0, warm_minutes))
            for i in range(n_rec):
                await rules.register(RecordingRule(
                    name=f"storm:r{i:05d}",
                    expr=(f'sum by (host) (sum_over_time('
                          f'storm_cpu{{host="h{i % n_hosts}"}}[1m]))'),
                    interval_ms=MIN, since_ms=BASE,
                ).validate())
            for i in range(n_alert):
                await rules.register(AlertRule(
                    name=f"StormA{i:05d}",
                    expr=f'storm_cpu{{host="h{i % n_hosts}"}}',
                    for_ms=2 * MIN,
                ).validate())
            now = BASE + warm_minutes * MIN

            # ---- materialize: every rule's full first evaluation
            hit0 = CACHE_REQUESTS.labels("hit").value
            miss0 = CACHE_REQUESTS.labels("miss").value
            t0 = time.perf_counter()
            s1 = await rules.tick(now_ms=now)
            materialize_s = time.perf_counter() - t0
            assert s1["errors"] == 0, s1
            hits = CACHE_REQUESTS.labels("hit").value - hit0
            miss = CACHE_REQUESTS.labels("miss").value - miss0
            alert_hit_rate = (
                hits / (hits + miss) if (hits + miss) else None
            )

            # ---- incremental: one minute of ingest per round
            from horaedb_tpu.rules import RULE_EVAL_LAG

            inc: list[float] = []
            for r in range(k_rounds):
                await eng.write_payload(
                    payload(warm_minutes + r, warm_minutes + r + 1)
                )
                now += MIN
                t0 = time.perf_counter()
                s = await rules.tick(now_ms=now)
                inc.append(time.perf_counter() - t0)
                assert s["errors"] == 0, s
            lag_after = RULE_EVAL_LAG.value
            inc.sort()

            # ---- quiet: drain the trailing window, then the no-mutation
            # tick the dirty-set path exists for
            now += 20 * MIN
            await rules.tick(now_ms=now)
            t0 = time.perf_counter()
            sq = await rules.tick(now_ms=now + MIN)
            quiet_s = time.perf_counter() - t0
            return {
                "rules": n_rec,
                "alert_rules": n_alert,
                "materialize_s": round(materialize_s, 3),
                "materialize_rules_per_sec": round(
                    (n_rec + n_alert) / materialize_s, 1
                ),
                "incremental_tick_p50_ms": round(
                    inc[len(inc) // 2] * 1000, 3
                ),
                "incremental_tick_p99_ms": round(
                    inc[max(0, int(len(inc) * 0.99) - 1)] * 1000, 3
                ),
                "eval_lag_after_tick_s": lag_after,
                "quiet_tick_s": round(quiet_s, 6),
                "quiet_evaluated": sq["evaluated"],
                "quiet_skipped": sq["skipped"],
                "quiet_speedup_vs_materialize": round(
                    materialize_s / max(quiet_s, 1e-9), 1
                ),
                "alert_cache_hit_rate": (
                    round(alert_hit_rate, 3)
                    if alert_hit_rate is not None else None
                ),
            }
        finally:
            await rules.close()
            await eng.close()

    return {"rule_storm": asyncio.run(run())}


def self_telemetry_lane(smoke: bool) -> dict:
    """Self-telemetry lane (horaedb_tpu/telemetry): what the monitor
    itself costs.

    Reports:
    - `snapshot_ns_per_family`: registry snapshot cost (no write) —
      the per-tick fixed cost of reading every typed family;
    - `tick_ms`: one full scrape tick (snapshot + payload build +
      ingest write) wall time, averaged;
    - `duty_pct_at_default_interval`: tick wall over the default 15 s
      scrape interval — the steady-state overhead the <2% acceptance
      budget pins (tools/bench_smoke.py); duty cycle is the honest
      number — an interleaved A/B at artificial scrape frequency
      measures the harness, not the deployment;
    - ingest A/B (info): the same payload stream with a scrape tick
      interleaved every quarter vs without, samples/s both ways."""
    import asyncio

    from horaedb_tpu.engine import MetricEngine
    from horaedb_tpu.objstore import MemStore
    from horaedb_tpu.pb import remote_write_pb2
    from horaedb_tpu.telemetry.collector import SelfScrapeCollector

    DEFAULT_INTERVAL_S = 15.0
    n_snap = 30 if smoke else 200
    n_tick = 4 if smoke else 20
    n_payloads = 30 if smoke else 200

    def payload(seq: int) -> bytes:
        req = remote_write_pb2.WriteRequest()
        for h in range(4):
            series = req.timeseries.add()
            for k, v in ((b"__name__", b"telbench_cpu"),
                         (b"host", f"h{h}".encode())):
                lab = series.labels.add()
                lab.name = k
                lab.value = v
            for i in range(25):
                smp = series.samples.add()
                smp.timestamp = 1_700_000_000_000 + (seq * 25 + i) * 1000
                smp.value = float(seq + i)
        return req.SerializeToString()

    async def ingest_run(with_scrape: bool) -> float:
        eng = await MetricEngine.open(
            "telbench", MemStore(), enable_compaction=False,
            ingest_buffer_rows=10_000,
        )
        col = SelfScrapeCollector(eng) if with_scrape else None
        every = max(n_payloads // 4, 1)
        t0 = time.perf_counter()
        try:
            for i in range(n_payloads):
                await eng.write_payload(payload(i))
                if col is not None and i % every == every - 1:
                    await col.tick()
            await eng.flush()
        finally:
            await eng.close()
        return time.perf_counter() - t0

    async def run() -> dict:
        eng = await MetricEngine.open(
            "telbench_t", MemStore(), enable_compaction=False,
        )
        col = SelfScrapeCollector(eng)
        try:
            n_families, snap = col.snapshot()
            t0 = time.perf_counter()
            for _ in range(n_snap):
                col.snapshot()
            snap_s = (time.perf_counter() - t0) / n_snap
            ticks = []
            for _ in range(n_tick):
                t0 = time.perf_counter()
                s = await col.tick()
                ticks.append(time.perf_counter() - t0)
                assert not s.get("error"), s
        finally:
            await eng.close()
        tick_s = sum(ticks) / len(ticks)
        base_wall = await ingest_run(False)
        scrape_wall = await ingest_run(True)
        n_samples = n_payloads * 100
        return {
            "families": n_families,
            "samples_per_tick": len(snap),
            "snapshot_ns_per_family": round(snap_s / max(n_families, 1) * 1e9),
            "tick_ms": round(tick_s * 1000, 3),
            "duty_pct_at_default_interval": round(
                tick_s / DEFAULT_INTERVAL_S * 100, 4
            ),
            "ingest_base_samples_per_sec": round(n_samples / base_wall),
            "ingest_with_scrape_samples_per_sec": round(
                n_samples / scrape_wall
            ),
            # interleaved at ~4 ticks per sub-second run — orders of
            # magnitude above any real scrape_interval; duty cycle above
            # is the deployment-shaped number
            "ingest_interleaved_overhead_pct": round(
                (scrape_wall - base_wall) / base_wall * 100, 2
            ),
        }

    return {"self_telemetry": asyncio.run(run())}


def scan_encoded_lane(smoke: bool) -> dict:
    """Compressed-domain scan lane (storage/encoding.py + ops/decode.py):

    - encode ns/row the flush path pays for the `.enc` sidecar;
    - bytes/row on the wire per lane (the H2D shrink the encodings buy —
      the acceptance bar is >=2x on the tsid/ts lanes);
    - decode rows/s per (codec, impl) through the sanctioned funnel, plus
      which impl the calibrated dispatcher picks per codec;
    - end-to-end storage scans on the SAME tree, encoded-auto vs
      HORAEDB_DECODE_IMPL=raw (the A/B honesty control): a filtered
      config-2 shape (tsid InSet + value predicate) and a full-table
      config-5 shape, best-of-3, scan block cache OFF so both paths pay
      their decode every pass."""
    import asyncio

    import pyarrow as pa

    from horaedb_tpu.objstore import MemStore
    from horaedb_tpu.ops import decode as decode_ops
    from horaedb_tpu.ops import filter as F
    from horaedb_tpu.storage import (
        ObjectBasedStorage,
        ScanRequest,
        StorageConfig,
        TimeRange,
        WriteRequest,
    )
    from horaedb_tpu.storage import encoding as enc_mod
    from horaedb_tpu.common.size_ext import ReadableSize
    from horaedb_tpu.storage.config import EncodingConfig

    n = 30_000 if smoke else 1_000_000
    n_series = 64 if smoke else 512
    rng = np.random.default_rng(7)
    tsid = np.sort(rng.integers(0, n_series, n, dtype=np.int64))
    ts = 1_700_000_000_000 + np.arange(n, dtype=np.int64) * 15_000 \
        + rng.integers(-4, 5, n)
    vals = rng.normal(size=n)
    table = pa.table({"tsid": tsid, "ts": ts, "value": vals})

    # ---- encode cost + wire bytes --------------------------------------
    reps = 2 if smoke else 3
    t0 = time.perf_counter()
    for _ in range(reps):
        e = enc_mod.encode_table(table, time_column="ts")
    encode_ns = (time.perf_counter() - t0) / (reps * n) * 1e9
    lane_ratio = {
        name: round(l.decoded_bytes() / max(l.encoded_bytes(), 1), 2)
        for name, l in e.lanes.items()
    }
    raw_bpr = sum(l.decoded_bytes() for l in e.lanes.values()) / n
    enc_bpr = sum(l.encoded_bytes() for l in e.lanes.values()) / n

    # ---- decode rows/s per (codec, impl) through the funnel ------------
    # bench lane measuring the funnel's own decode rate
    decode_rps: dict[str, dict] = {}
    auto_impl: dict[str, str] = {}
    for name, lane in e.lanes.items():
        codec = lane.codec
        if codec in decode_rps or codec in ("raw", "null"):
            continue
        per = {}
        for impl in decode_ops.DECODE_IMPLS:
            try:
                enc_mod.decode_lane(lane, impl=impl)  # warm/compile
                t0 = time.perf_counter()
                for _ in range(reps):
                    enc_mod.decode_lane(lane, impl=impl)
                per[impl] = round(n / ((time.perf_counter() - t0) / reps))
            except Exception:  # noqa: BLE001 — impl loses by forfeit
                continue
        decode_rps[codec] = per
        auto_impl[codec] = decode_ops.choose(codec, n)

    # ---- end-to-end scans: encoded-auto vs forced-raw ------------------
    SEG = 24 * 3_600_000
    cfg = StorageConfig(
        encoding=EncodingConfig(enabled=True, min_rows=1),
        scan_cache=ReadableSize(0),
    )
    schema = pa.schema([
        ("tsid", pa.int64()), ("ts", pa.int64()), ("value", pa.float64()),
    ])

    async def build():
        store = MemStore()
        eng = await ObjectBasedStorage.try_new(
            "bench", store, schema, num_primary_keys=1,
            segment_duration_ms=SEG, config=cfg,
            enable_compaction_scheduler=False,
            start_background_merger=False,
        )
        # one segment: normalize ts into an ALIGNED [k*SEG, (k+1)*SEG)
        t_lo = (1_700_000_000_000 // SEG + 1) * SEG
        ts_n = t_lo + (ts - ts[0]) % SEG
        batch = pa.RecordBatch.from_pydict(
            {"tsid": tsid, "ts": ts_n, "value": vals}, schema=schema,
        )
        await eng.write(WriteRequest(
            batch, TimeRange(int(ts_n.min()), int(ts_n.max()) + 1),
        ))
        return eng

    async def scan_rows(eng, req) -> int:
        rows = 0
        async for b in eng.scan(req):
            rows += b.num_rows
        return rows

    def timed_scan(eng, req, mode: str) -> float:
        prior = os.environ.get("HORAEDB_DECODE_IMPL")
        os.environ["HORAEDB_DECODE_IMPL"] = mode
        try:
            best = None
            for _ in range(3 if not smoke else 2):
                t0 = time.perf_counter()
                asyncio.run(scan_rows(eng, req))
                el = time.perf_counter() - t0
                best = el if best is None else min(best, el)
            return best
        finally:
            if prior is None:
                os.environ.pop("HORAEDB_DECODE_IMPL", None)
            else:
                os.environ["HORAEDB_DECODE_IMPL"] = prior

    eng = asyncio.run(build())
    sel = tuple(int(x) for x in rng.choice(n_series, 8, replace=False))
    shapes = {
        "filtered": ScanRequest(
            range=TimeRange(0, 2**62),
            predicate=F.And(F.InSet("tsid", sel),
                            F.Compare("value", "gt", 0.0)),
        ),
        "full": ScanRequest(range=TimeRange(0, 2**62)),
    }
    e2e: dict[str, dict] = {}
    try:
        for shape, req in shapes.items():
            raw_s = timed_scan(eng, req, "raw")
            enc_s = timed_scan(eng, req, "auto")
            e2e[shape] = {
                "raw_rows_per_sec": round(n / raw_s),
                "encoded_rows_per_sec": round(n / enc_s),
                "speedup": round(raw_s / enc_s, 3),
            }
    finally:
        asyncio.run(eng.close())

    return {
        "scan_encoded": {
            "rows": n,
            "encode_ns_per_row": round(encode_ns, 1),
            "bytes_per_row": {
                "raw": round(raw_bpr, 2),
                "encoded": round(enc_bpr, 2),
                "ratio": round(raw_bpr / max(enc_bpr, 1e-9), 2),
            },
            "lane_ratios": lane_ratio,
            "lane_codecs": dict(e.descriptor()),
            "decode_rows_per_sec": decode_rps,
            "decode_auto_impl": auto_impl,
            "e2e": e2e,
        }
    }


def copy_tax_lane(smoke: bool) -> dict:
    """Memory observatory lane (common/memtrace.py): the copy tax in
    bytes per row, measured on a real storage tree.

    - ingest leg: one write (sort + parquet encode + upload) under a
      lineage ledger -> bytes copied/allocated per row ingested, by stage;
    - scan leg: a cold full-table scan under a ledger -> bytes copied per
      row scanned, by stage (the ROOFLINE §4 copy-tax numbers);
    - overhead leg: the same scan timed with memtrace default vs off —
      the ISSUE's <2% acceptance bar on query p50 (funnels perform the
      identical array ops in both modes; only the ledger adds work)."""
    import asyncio

    import pyarrow as pa

    from horaedb_tpu.common import memtrace
    from horaedb_tpu.common.size_ext import ReadableSize
    from horaedb_tpu.objstore import MemStore
    from horaedb_tpu.storage import (
        ObjectBasedStorage,
        ScanRequest,
        StorageConfig,
        TimeRange,
        WriteRequest,
        scanstats,
    )

    n = 30_000 if smoke else 500_000
    n_series = 64 if smoke else 512
    rng = np.random.default_rng(11)
    SEG = 24 * 3_600_000
    t_lo = (1_700_000_000_000 // SEG + 1) * SEG
    tsid = np.sort(rng.integers(0, n_series, n, dtype=np.int64))
    ts = t_lo + (np.arange(n, dtype=np.int64) * 15_000) % SEG
    vals = rng.normal(size=n)
    schema = pa.schema([
        ("tsid", pa.int64()), ("ts", pa.int64()), ("value", pa.float64()),
    ])
    # scan cache OFF: every pass pays materialize/host_prep, so the
    # per-row tax is the cold-scan number ROOFLINE quotes
    cfg = StorageConfig(scan_cache=ReadableSize(0))

    async def build():
        # pk = (tsid, ts): rows stay distinct under the LWW merge, so
        # the scan leg reads all n rows, not one per series
        eng = await ObjectBasedStorage.try_new(
            "bench_mem", MemStore(), schema, num_primary_keys=2,
            segment_duration_ms=SEG, config=cfg,
            enable_compaction_scheduler=False,
            start_background_merger=False,
        )
        return eng

    async def write(eng):
        batch = pa.RecordBatch.from_pydict(
            {"tsid": tsid, "ts": ts, "value": vals}, schema=schema,
        )
        await eng.write(WriteRequest(
            batch, TimeRange(int(ts.min()), int(ts.max()) + 1),
        ))

    async def scan_rows(eng) -> int:
        rows = 0
        req = ScanRequest(range=TimeRange(0, 2**62))
        async for b in eng.scan(req):
            rows += b.num_rows
        return rows

    def per_stage(verdict: dict, rows: int) -> dict:
        return {
            stage: {
                "copied_bytes_per_row": round(
                    row.get("copy_bytes", 0) / max(rows, 1), 2
                ),
                "alloc_bytes_per_row": round(
                    row.get("alloc_bytes", 0) / max(rows, 1), 2
                ),
            }
            for stage, row in sorted(verdict["per_stage"].items())
        }

    prior_mode = memtrace.mode()
    memtrace.configure("")
    try:
        eng = asyncio.run(build())
        try:
            with scanstats.scan_stats() as st:
                asyncio.run(write(eng))
            ingest_v = memtrace.verdict(st.mem)
            with scanstats.scan_stats() as st:
                rows = asyncio.run(scan_rows(eng))
            scan_v = memtrace.verdict(st.mem)
            # stage walls off the same ledger context: the zero-copy
            # spine's acceptance bar is host_prep+materialize wall, not
            # just byte counts — a refactor that trades copies for slow
            # chunk-walking would show up here
            scan_walls = {
                k: round(v, 5) for k, v in sorted(st.seconds.items())
            }
            hp_mat_ms = round(
                (st.seconds.get("host_prep", 0.0)
                 + st.seconds.get("materialize", 0.0)) * 1e3, 3)

            # overhead leg: median (p50) of N scans, default vs off —
            # min-of-few is noise-dominated at millisecond scan times
            def p50_scan(reps: int) -> float:
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    with scanstats.scan_stats():
                        asyncio.run(scan_rows(eng))
                    times.append(time.perf_counter() - t0)
                times.sort()
                return times[len(times) // 2]

            reps = 7 if smoke else 9
            p50_scan(2)  # warm both code paths
            on_s = p50_scan(reps)
            memtrace.configure("off")
            off_s = p50_scan(reps)
        finally:
            asyncio.run(eng.close())
    finally:
        memtrace.configure(prior_mode)

    return {
        "copy_tax": {
            "rows": n,
            "ingest": {
                "bytes_copied_per_row": round(
                    ingest_v["bytes_copied"] / n, 2
                ),
                "bytes_allocated_per_row": round(
                    ingest_v["bytes_allocated"] / n, 2
                ),
                "per_stage": per_stage(ingest_v, n),
            },
            "scan": {
                "rows_scanned": rows,
                "bytes_copied_per_row": round(
                    scan_v["bytes_copied"] / max(rows, 1), 2
                ),
                "bytes_allocated_per_row": round(
                    scan_v["bytes_allocated"] / max(rows, 1), 2
                ),
                "copies": scan_v["copies"],
                "views": scan_v["views"],
                "per_stage": per_stage(scan_v, rows),
                "stage_walls_s": scan_walls,
                "host_prep_materialize_ms": hp_mat_ms,
            },
            "overhead": {
                "scan_default_s": round(on_s, 4),
                "scan_off_s": round(off_s, 4),
                "overhead_pct": round(
                    (on_s - off_s) / max(off_s, 1e-9) * 100, 2
                ),
            },
        }
    }


def main() -> None:
    from horaedb_tpu.common import compile_cache

    compile_cache.enable()
    import jax

    import jax.numpy as jnp

    from horaedb_tpu.ops import agg_registry
    from horaedb_tpu.ops import filter as F
    from horaedb_tpu.parallel import make_mesh
    from horaedb_tpu.parallel.scan import build_sharded_downsample

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not SMOKE:
        sys.exit(
            f"bench.py measures on the TPU; this process got platform "
            f"{platform!r}. Use --smoke for the CPU plumbing gate."
        )
    on_accel = platform not in ("cpu",)

    num_series = 10_000
    bucket_ms = 300_000  # 5 minutes
    span_ms = 24 * 3600_000  # 1 day
    num_buckets = span_ms // bucket_ms  # 288
    if SMOKE:
        n_rows, iters = 256_000, 2
    else:
        n_rows = 64_000_000 if on_accel else 2_000_000
        iters = 10 if on_accel else 3
    num_cells = num_series * int(num_buckets)

    rng = np.random.default_rng(0)
    # i32 time offsets & f32 values: native lane widths on TPU (the engine
    # normalizes per-segment i64 timestamps to i32 offsets before dispatch)
    ts = rng.integers(0, span_ms, n_rows, dtype=np.int64).astype(np.int32)
    sid = rng.integers(0, num_series, n_rows, dtype=np.int64).astype(np.int32)
    vals = rng.normal(size=n_rows).astype(np.float32)

    mesh = make_mesh(1)
    pred = F.Compare("__val__", "gt", -1.0)
    # mean-downsample: sum+count, dispatcher-resolved (the TSBS 5m-avg
    # shape); under jit the registry restricts to traceable impls
    fn = build_sharded_downsample(
        mesh, num_series, num_buckets, predicate=pred, with_minmax=False
    )

    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P("rows"))
    d_ts = jax.device_put(ts, sh)
    d_sid = jax.device_put(sid, sh)
    d_vals = jax.device_put(vals, sh)
    d_valid = jax.device_put(np.ones(n_rows, dtype=bool), sh)
    lits = (jnp.asarray(-1.0, dtype=jnp.float32),)
    t0 = jnp.asarray(0, dtype=jnp.int32)
    bkt = jnp.asarray(bucket_ms, dtype=jnp.int32)

    # Scalar probe forces completion of the whole in-order device queue with
    # an 8-byte transfer (a full-grid D2H would measure link bandwidth, not
    # compute).
    probe = jax.jit(lambda o: o["sum"].sum() + o["count"].sum())

    def timed(f, *args) -> float:
        """Mean seconds per pass (scalar-probe completion)."""
        o = f(*args)
        float(np.asarray(probe(o)))  # warmup/compile
        t_start = time.perf_counter()
        for _ in range(iters):
            o = f(*args)
        float(np.asarray(probe(o)))
        return (time.perf_counter() - t_start) / iters

    def timed_host(f) -> float:
        """Mean seconds per pass of a synchronous host (numpy) pipeline."""
        f()  # warmup (allocator, page faults)
        t_start = time.perf_counter()
        for _ in range(iters):
            f()
        return (time.perf_counter() - t_start) / iters

    dev_elapsed = timed(fn, d_ts, d_sid, d_vals, d_valid, lits, t0, bkt)
    out = fn(d_ts, d_sid, d_vals, d_valid, lits, t0, bkt)
    out_counts = np.asarray(out["count"])

    # ---- unsorted lane: A/B EVERY registered impl on this platform ------
    unsorted_results: dict[str, float] = {"auto_jit": n_rows / dev_elapsed}
    for u_impl in agg_registry.unsorted_impl_names(platform):
        if agg_registry.is_host_impl(u_impl):
            # impl=u_impl: the pipeline dispatches by NAME (KeyError on an
            # unmapped impl) — a new host lane must never silently time as
            # an old one under its name
            elapsed = timed_host(lambda u=u_impl: agg_registry.host_downsample_unsorted(
                ts, sid, vals, 0, bucket_ms, num_series, int(num_buckets),
                with_minmax=False, valid=vals > np.float32(-1.0), impl=u,
            ))
        else:
            fn_u = build_sharded_downsample(
                mesh, num_series, num_buckets, predicate=pred,
                with_minmax=False, unsorted_impl=u_impl,
            )
            elapsed = timed(fn_u, d_ts, d_sid, d_vals, d_valid, lits, t0, bkt)
        unsorted_results[u_impl] = n_rows / elapsed

    # dispatcher's automatic pick for concrete host-side input (what the
    # engine's materialized path would run); the jit pipeline's trace-time
    # pick rides "auto_jit"
    unsorted_choice = agg_registry.choose_unsorted(
        n_rows, num_cells, concrete=True, platform=platform
    )
    dev_rows_per_sec = unsorted_results.get(
        unsorted_choice, unsorted_results["auto_jit"]
    )

    # ---- sorted lane: the engine's natural scan order is SORTED by
    # (series, ts). Sort once on host (outside timing), A/B every impl. --
    order = np.lexsort((ts, sid))
    ts_s, sid_s, vals_s = ts[order], sid[order], vals[order]
    s_ts = jax.device_put(ts_s, sh)
    s_sid = jax.device_put(sid_s, sh)
    s_vals = jax.device_put(vals_s, sh)

    sorted_results: dict[str, float] = {}
    for impl_name in agg_registry.sorted_impl_names(platform):
        if agg_registry.is_host_impl(impl_name):
            # name-dispatched (see the unsorted loop) and output captured
            # from the TIMED closure — no extra full pass just for counts
            host_out: dict = {}

            def run_host(i=impl_name):
                host_out["out"] = agg_registry.host_downsample_sorted(
                    ts_s, sid_s, vals_s, 0, bucket_ms, num_series,
                    int(num_buckets), with_minmax=False,
                    valid=vals_s > np.float32(-1.0), impl=i,
                )
                return host_out["out"]

            elapsed = timed_host(run_host)
            out_sorted_counts = np.asarray(host_out["out"]["count"])
        else:
            fn_sorted = build_sharded_downsample(
                mesh, num_series, num_buckets, predicate=pred,
                with_minmax=False, sorted_input=True, sorted_impl=impl_name,
            )
            elapsed = timed(fn_sorted, s_ts, s_sid, s_vals, d_valid, lits, t0, bkt)
            out_sorted_counts = np.asarray(
                fn_sorted(s_ts, s_sid, s_vals, d_valid, lits, t0, bkt)["count"]
            )
        sorted_results[impl_name] = n_rows / elapsed
        np.testing.assert_allclose(out_sorted_counts, out_counts, rtol=1e-6)

    sorted_choice = agg_registry.choose_sorted(
        n_rows, num_cells, concrete=True, platform=platform
    )
    if sorted_choice not in sorted_results:
        # an env pin can name an impl this platform's A/B never ran
        # (e.g. HORAEDB_AGG_IMPL=reduceat on an accelerator): report the
        # measured best rather than KeyError-ing the whole round
        sorted_choice = max(sorted_results, key=sorted_results.get)
    sorted_rows_per_sec = sorted_results[sorted_choice]

    # headline = the faster DISPATCHER-CHOSEN pipeline (both are real
    # engine shapes; scan output is sorted, so the sorted path is the
    # representative one when it wins). Per-impl maxima stay visible in
    # the ab dicts — the headline must be reproducible without pinning.
    best_rows_per_sec = max(dev_rows_per_sec, sorted_rows_per_sec)

    # calibration-cache provenance: did this run pay the micro-A/B (cold)
    # or ride the persisted verdict (warm), and what did it measure?
    calib_entry, calib_source = agg_registry.calibration_entry(
        "sorted", n_rows, num_cells, platform=platform
    )
    dispatcher_info = {
        "sorted": sorted_choice,
        "unsorted": unsorted_choice,
        "source": calib_source,
        "cache": agg_registry.cache_path(),
        "calib_ab": calib_entry.get("ab", {}),
        "calib_rejected": calib_entry.get("rejected", {}),
    }

    # compile vs steady-state split (common/xprof.py): every device
    # pipeline above routed through instrumented xjit wrappers, so the
    # process totals separate a compile-time regression (recompiles /
    # compile_s grew) from a kernel regression (steady_s grew) — the two
    # used to be indistinguishable in device_s_per_pass alone.
    from horaedb_tpu.common import xprof

    xprof_totals = xprof.snapshot()

    # CPU baseline timing on a bounded sample (single-thread numpy)
    sample = min(n_rows, 4_000_000)
    b_start = time.perf_counter()
    numpy_baseline(
        ts[:sample], sid[:sample], vals[:sample].astype(np.float64),
        bucket_ms, num_series, num_buckets, -1.0,
    )
    base_elapsed = time.perf_counter() - b_start
    base_rows_per_sec = sample / base_elapsed

    # correctness cross-check over the FULL dataset (outside the timed loop)
    sums, counts = numpy_baseline(
        ts, sid, vals.astype(np.float64), bucket_ms, num_series, num_buckets, -1.0
    )
    np.testing.assert_allclose(out_counts.reshape(-1), counts, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out["sum"]).reshape(-1), sums, rtol=2e-2, atol=2e-1
    )

    result = {
        "metric": "downsample_rows_per_sec",
        "value": round(best_rows_per_sec),
        "unit": "rows/s",
        "vs_baseline": round(best_rows_per_sec / base_rows_per_sec, 3),
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        # CPU-fallback ratios depend on the box: XLA-CPU multithreads, the
        # numpy baseline does not, so vs_baseline shrinks on small
        # containers (r05's 1-core box: 1.43 vs r04's 2.12 for the SAME
        # code). Recorded so cross-round CPU comparisons stay honest.
        "cores": len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
        else (os.cpu_count() or 1),
        "n_rows": n_rows,
        "num_series": num_series,
        "num_buckets": int(num_buckets),
        # seconds per pass of the HEADLINE path (consistent with `value`)
        "device_s_per_pass": round(n_rows / best_rows_per_sec, 4),
        # steady-state per-pass seconds (cache-hit; identical to
        # device_s_per_pass — named so the split reads unambiguously next
        # to compile_s) vs TOTAL one-time compile seconds this process
        # paid across every kernel/shape the A/B sweep traced
        "steady_s": round(n_rows / best_rows_per_sec, 4),
        "compile_s": xprof_totals["total_compile_seconds"],
        "recompiles": xprof_totals["total_compiles"],
        "baseline_rows_per_sec": round(base_rows_per_sec),
        "unsorted_rows_per_sec": round(dev_rows_per_sec),
        "unsorted_impl": unsorted_choice,
        "unsorted_ab": {k: round(v) for k, v in unsorted_results.items()},
        "sorted_rows_per_sec": round(sorted_rows_per_sec),
        "sorted_impl": sorted_choice,
        "sorted_ab": {k: round(v) for k, v in sorted_results.items()},
        "agg_dispatcher": dispatcher_info,
        "smoke": SMOKE,
    }
    # ingest lane (overlapped ingest->flush pipeline): pure vs with-flush
    # samples/s ride the same JSON line (bench-smoke asserts them)
    result.update(ingest_lane(SMOKE))
    # query QPS lane (admission scheduler): closed-loop p50/p99 vs
    # concurrency at 1/8/64 clients + shed rate (bench-smoke asserts it)
    result.update(query_qps_lane(SMOKE))
    # compressed-domain scan lane (encoded sidecars + decode funnel):
    # wire bytes/row, encode/decode rates, encoded-vs-raw e2e scans
    result.update(scan_encoded_lane(SMOKE))
    # serving-tier lane (rollups + result cache): zipf-repeated dashboard
    # panels, cold/warm p50/p99, hit rate, substitution rate
    result.update(query_serving_lane(SMOKE))
    # rule-storm lane (horaedb_tpu/rules): materialize vs incremental vs
    # quiet ticks over 10k standing rules — the dirty-set proof
    result.update(rule_storm_lane(SMOKE))
    # self-telemetry lane (horaedb_tpu/telemetry): scrape-tick cost and
    # the steady-state duty cycle the <2% overhead budget pins
    result.update(self_telemetry_lane(SMOKE))
    # cluster lane (horaedb_tpu/cluster): 1 writer vs writer + 2 read
    # replicas on one bucket — scale-out factor + replica lag p99
    result.update(cluster_scaleout_lane(SMOKE))
    # memory observatory lane (common/memtrace.py): bytes copied per row
    # ingested/scanned by stage + the memtrace-off overhead control
    result.update(copy_tax_lane(SMOKE))

    print(json.dumps(result))


if __name__ == "__main__":
    main()
