"""Observability smoke gate (`make smoke-metrics`).

Boots the real server (build_app) against the in-process fake S3 object
store, pushes one remote-write batch, runs one raw and one downsample
query, then fails loudly unless:

- every /metrics line passes the Prometheus text-format validator
  (tools/promcheck.py);
- the expected metric families are present (per-stage scan histograms,
  ingest/flush/storage/compaction families, HTTP latency, and the
  horaedb_jit_* compile-telemetry families with at least one labeled
  kernel);
- the query response echoed an X-Horaedb-Trace-Id whose span tree
  round-trips through GET /debug/traces/{id};
- a `?explain=1` downsample query returns a plan with the dispatcher
  impl, per-lane stage seconds, and a compile/steady split;
- GET /debug/kernels serves the instrumented-kernel catalog and
  GET /debug/slowlog returns the recorded query.

This is the end-to-end check the unit tests can't give: the families are
registered at import time across six modules, and only a live request
drives them all through one process.

Run: python tools/smoke_metrics.py
"""

from __future__ import annotations

import asyncio
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from promcheck import validate, validate_openmetrics  # noqa: E402

REQUIRED_FAMILIES = (
    "horaedb_scan_stage_seconds_bucket",
    'horaedb_scan_stage_seconds_bucket{stage="io_decode"',
    'horaedb_scan_stage_seconds_bucket{stage="transfer"',
    'horaedb_scan_stage_seconds_bucket{stage="kernel"',
    'horaedb_scan_stage_seconds_bucket{stage="host_prep"',
    "horaedb_scan_path_total",
    "horaedb_agg_impl_total",
    "horaedb_remote_write_samples_total",
    "horaedb_remote_write_batch_samples_bucket",
    "horaedb_ingest_parse_seconds_bucket",
    "horaedb_storage_write_seconds_bucket",
    "horaedb_storage_scan_seconds_bucket",
    "horaedb_sst_bytes_bucket",
    "horaedb_compaction_queue_depth",
    "horaedb_compaction_seconds_bucket",
    "horaedb_http_request_seconds_bucket",
    "horaedb_ingest_flush_seconds_bucket",
    # overlapped ingest->flush pipeline (engine/flush_executor.py): the
    # bulk write below crosses the buffer threshold, so a background
    # flush must have run and fed the stage histograms
    "horaedb_flush_queue_depth",
    "horaedb_ingest_stall_seconds_bucket",
    # (table renders before stage in this family's label set)
    "horaedb_flush_stage_seconds_bucket",
    'stage="drain"',
    'stage="encode"',
    'stage="upload"',
    "horaedb_flush_failures_total",
    "horaedb_flush_overlap_ratio_bucket",
    "horaedb_uptime_seconds",
    # device-side compile telemetry (common/xprof.py): the counter must
    # carry at least one real labeled kernel after the queries ran
    "horaedb_jit_compile_total",
    'horaedb_jit_compile_total{kernel="',
    "horaedb_jit_compile_seconds_bucket",
    "horaedb_jit_cache_entries",
    'horaedb_scan_stage_seconds_bucket{stage="compile"',
    "horaedb_slowlog_records_total",
    # object-store resilience layer (objstore/resilient.py): the server
    # wraps its store in a ResilientStore at boot, so the families must
    # render with per-verb children from the manifest/boot traffic alone
    "horaedb_objstore_attempts_total",
    'horaedb_objstore_attempts_total{op="put",result="ok"',
    'horaedb_objstore_attempts_total{op="get",result="ok"',
    "horaedb_objstore_retries_total",
    "horaedb_objstore_gave_up_total",
    "horaedb_objstore_breaker_state",
    "horaedb_orphan_ssts_gc_total",
    # dirty-traffic hardening families: all must render from boot (the
    # engine/storage pre-register their children), counters move only
    # when late/deleted/over-limit traffic arrives
    "horaedb_series_cardinality",
    "horaedb_late_samples_total",
    "horaedb_tombstones_applied_total",
    'horaedb_tombstones_applied_total{table="metrics/data",context="scan"',
    "horaedb_tombstones_created_total",
    "horaedb_cardinality_rejected_samples_total",
    "horaedb_cardinality_rejected_series_total",
    "horaedb_cardinality_limited_requests_total",
    # query-path admission control (server/admission.py): gauges +
    # shed/deadline counters render from boot (children pre-registered),
    # and queue wait is a first-class scan stage
    "horaedb_query_inflight",
    "horaedb_query_queued",
    "horaedb_query_shed_total",
    'horaedb_query_shed_total{reason="queue_full"',
    'horaedb_query_shed_total{reason="stall"',
    'horaedb_query_shed_total{reason="client_disconnect"',
    "horaedb_query_deadline_exceeded_total",
    'horaedb_scan_stage_seconds_bucket{stage="queue_wait"',
    # serving tier (horaedb_tpu/serving): all families render from boot
    # (children pre-registered); the repeated-query flow below moves the
    # hit/miss counters and the write moves the invalidation counter
    "horaedb_serving_cache_requests_total",
    'horaedb_serving_cache_requests_total{result="hit"',
    'horaedb_serving_cache_requests_total{result="miss"',
    'horaedb_serving_cache_requests_total{result="bypass"',
    "horaedb_serving_cache_bytes",
    "horaedb_serving_cache_entries",
    "horaedb_serving_cache_evictions_total",
    "horaedb_serving_invalidations_total",
    'horaedb_serving_invalidations_total{reason="flush"',
    'horaedb_serving_invalidations_total{reason="compact"',
    'horaedb_serving_invalidations_total{reason="delete"',
    "horaedb_serving_rollups_built_total",
    "horaedb_serving_rollup_substitutions_total",
    "horaedb_serving_rollup_rows_total",
    # streaming rule engine (horaedb_tpu/rules): families render from
    # boot (zero states pre-registered); the rule flow below moves the
    # eval/tick/transition counters
    "horaedb_rules_registered",
    'horaedb_rules_registered{kind="recording"',
    'horaedb_rules_registered{kind="alert"',
    "horaedb_rules_eval_seconds_bucket",
    "horaedb_rules_evals_total",
    'horaedb_rules_evals_total{kind="recording",result="ok"',
    "horaedb_rules_dirty_skips_total",
    "horaedb_rules_ticks_total",
    "horaedb_rules_eval_lag_seconds",
    "horaedb_rules_samples_written_total",
    "horaedb_rules_write_degraded_total",
    "horaedb_rules_alert_transitions_total",
    'horaedb_rules_alert_transitions_total{transition="firing"',
    "horaedb_rules_alerts_active",
    # self-telemetry pipeline (horaedb_tpu/telemetry): the per-tenant
    # usage funnel's families carry the default tenant from the traffic
    # above and `_system` from the forced self-scrape tick; the
    # telemetry meta-families render from boot
    "horaedb_tenant_rows_ingested_total",
    'horaedb_tenant_rows_ingested_total{tenant="default"',
    'horaedb_tenant_rows_ingested_total{tenant="_system"',
    "horaedb_tenant_samples_rejected_total",
    "horaedb_tenant_bytes_scanned_total",
    'horaedb_tenant_bytes_scanned_total{tenant="default"',
    "horaedb_tenant_queue_wait_seconds_total",
    "horaedb_tenant_queries_total",
    "horaedb_tenant_sheds_total",
    "horaedb_tenant_deadline_exceeded_total",
    "horaedb_telemetry_ticks_total",
    'horaedb_telemetry_ticks_total{result="ok"',
    "horaedb_telemetry_samples_total",
    "horaedb_telemetry_series",
    "horaedb_telemetry_dropped_series_total",
    "horaedb_telemetry_scrape_seconds_bucket",
    # query batcher (server/batching.py): every family renders from boot
    # (pre-registered children); the same-shape panel burst below moves
    # the batched counter and the group-size/pad-waste histograms
    "horaedb_batch_group_size_bucket",
    "horaedb_batch_pad_waste_ratio_bucket",
    "horaedb_batch_window_wait_seconds_bucket",
    "horaedb_batch_queries_total",
    'horaedb_batch_queries_total{mode="batched"',
    'horaedb_batch_queries_total{mode="solo_lone"',
    'horaedb_batch_queries_total{mode="solo_deadline"',
    'horaedb_batch_queries_total{mode="solo_off"',
    "horaedb_batch_launches_total",
    'horaedb_scan_stage_seconds_bucket{stage="batch_window"',
    # memory observatory (common/memtrace.py + common/bytebudget.py):
    # lineage counters pre-register every (stage, kind) child and the
    # pool registry pre-registers all four byte-budgeted caches, so
    # every family renders the zero state from boot
    "horaedb_mem_bytes_total",
    'horaedb_mem_bytes_total{stage="host_prep",kind="copy"',
    'horaedb_mem_bytes_total{stage="materialize",kind="view"',
    "horaedb_mem_events_total",
    'horaedb_mem_events_total{stage="decode",kind="alloc"',
    "horaedb_mem_device_staging_bytes_total",
    "horaedb_pool_bytes",
    'horaedb_pool_bytes{pool="scan"',
    'horaedb_pool_bytes{pool="sidecar"',
    'horaedb_pool_bytes{pool="result"',
    'horaedb_pool_bytes{pool="rollup"',
    "horaedb_pool_entries",
    "horaedb_pool_capacity_bytes",
    'horaedb_pool_capacity_bytes{pool="result"',
    "horaedb_pool_evictions_total",
    'horaedb_pool_evictions_total{pool="scan"',
)


def make_payload() -> bytes:
    from horaedb_tpu.pb import remote_write_pb2

    req = remote_write_pb2.WriteRequest()
    for host, samples in (("a", [(1000, 1.5), (2000, 2.5)]),
                          ("b", [(1500, 7.0)])):
        ts = req.timeseries.add()
        for k, v in ((b"__name__", b"smoke_cpu"), (b"host", host.encode())):
            lab = ts.labels.add()
            lab.name = k
            lab.value = v
        for t, v in samples:
            s = ts.samples.add()
            s.timestamp = t
            s.value = v
    return req.SerializeToString()


def make_payload_named(metric: str) -> bytes:
    """One-sample payload under a FRESH metric name, so ingest cannot be
    served from caches — registration must touch the object store."""
    from horaedb_tpu.pb import remote_write_pb2

    req = remote_write_pb2.WriteRequest()
    ts = req.timeseries.add()
    for k, v in ((b"__name__", metric.encode()), (b"host", b"shed")):
        lab = ts.labels.add()
        lab.name = k
        lab.value = v
    s = ts.samples.add()
    s.timestamp = 1000
    s.value = 1.0
    return req.SerializeToString()


def make_bulk_payload(n_series: int, n_samples: int) -> bytes:
    """Enough rows to cross the ingest buffer threshold, so at least one
    BACKGROUND flush runs and the pipeline stage histograms get fed."""
    from horaedb_tpu.pb import remote_write_pb2

    req = remote_write_pb2.WriteRequest()
    for s in range(n_series):
        ts = req.timeseries.add()
        for k, v in ((b"__name__", b"smoke_bulk"),
                     (b"host", f"bulk-{s:03d}".encode())):
            lab = ts.labels.add()
            lab.name = k
            lab.value = v
        for i in range(n_samples):
            smp = ts.samples.add()
            smp.timestamp = 1000 + i * 1000
            smp.value = float(s + i)
    return req.SerializeToString()


async def run() -> int:
    import aiohttp
    from aiohttp import web

    from horaedb_tpu.objstore.fake_s3 import FakeS3
    from horaedb_tpu.server.config import Config
    from horaedb_tpu.server.main import build_app

    failures: list[str] = []

    def check(ok: bool, msg: str) -> None:
        print(("ok   " if ok else "FAIL ") + msg)
        if not ok:
            failures.append(msg)

    import tempfile

    scratch = tempfile.mkdtemp(prefix="horaedb-smoke-")
    fake = FakeS3()
    url = await fake.start()
    cfg = Config.from_dict({
        "metric_engine": {
            "storage": {"object_store": {
                "type": "S3Like", "endpoint": url, "bucket": fake.bucket,
                "region": "smoke", "key_id": "smoke", "key_secret": "smoke",
                # fresh local scratch: the slowlog spool must start empty so
                # "the recorded request comes back" proves THIS process
                # wrote it
                "data_dir": scratch,
            }},
            # small buffer + explicit executor sizing: the bulk write must
            # cross the threshold and take the BACKGROUND flush path
            "ingest_buffer_rows": 64,
            "ingest": {"flush_workers": 2, "flush_queue_max": 4},
            # series-cardinality limit ([metric_engine.limits]): high
            # enough for the base traffic (~44 series), crossed by the
            # card_fill flood below so the partial-accept 503 fires
            "limits": {"max_series": 60},
        },
    })
    app = await build_app(cfg)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/api/v1/write",
                              data=make_payload()) as r:
                body = await r.json()
                check(r.status == 200 and body.get("samples") == 3,
                      f"remote-write accepted: {body}")
            # bulk write: 40 series x 4 samples = 160 rows vs the 64-row
            # buffer -> the threshold seals a memtable to the background
            # flush executor (queue depth / stall / stage families)
            async with s.post(f"{base}/api/v1/write",
                              data=make_bulk_payload(40, 4)) as r:
                body = await r.json()
                check(r.status == 200 and body.get("samples") == 160,
                      f"bulk remote-write accepted: {body}")
            async with s.post(f"{base}/api/v1/query", json={
                "metric": "smoke_bulk", "start_ms": 0, "end_ms": 10_000,
            }) as r:
                body = await r.json()
                check(r.status == 200 and body.get("rows") == 160,
                      f"bulk rows visible after background flush: {body}")
            async with s.post(f"{base}/api/v1/query", json={
                "metric": "smoke_cpu", "start_ms": 0, "end_ms": 10_000,
            }) as r:
                body = await r.json()
                trace_id = r.headers.get("X-Horaedb-Trace-Id", "")
                check(r.status == 200 and body.get("rows") == 3,
                      f"raw query answered: {body}")
                check(bool(trace_id), "query echoed X-Horaedb-Trace-Id")
            # ---- per-tenant usage metering: the ledger must match the
            # requests THIS smoke actually issued so far — 3 + 160
            # ingested samples, exactly 2 admitted queries, and a real
            # bytes-scanned figure from the SST reads above
            async with s.get(f"{base}/api/v1/usage?tenant=default"
                             f"&window=5m") as r:
                u = ((await r.json()).get("data") or {})
                boot = u.get("since_boot") or {}
                check(r.status == 200 and boot.get("rows_ingested") == 163,
                      f"usage rows_ingested matches issued writes "
                      f"(3+160): {boot}")
                check(boot.get("queries") == 2,
                      f"usage queries matches admitted queries: {boot}")
                check(boot.get("bytes_scanned", 0) > 0,
                      f"usage bytes_scanned moved: {boot}")
                win = u.get("window") or {}
                check(win.get("rows_ingested") == 163,
                      f"windowed usage agrees since boot < window: {win}")
            async with s.post(f"{base}/api/v1/query?explain=1", json={
                "metric": "smoke_cpu", "start_ms": 0, "end_ms": 4000,
                "bucket_ms": 2000,
            }) as r:
                body = await r.json()
                check(r.status == 200, "downsample query answered")
                plan = body.get("explain") or {}
                check(plan.get("mode") == "downsample"
                      and bool(plan.get("agg_impl")),
                      f"explain carries the dispatcher impl: "
                      f"{plan.get('agg_impl')!r}")
                lanes = plan.get("lanes_s") or {}
                check(
                    {"io", "transfer", "kernel", "compile", "host"}
                    <= set(lanes),
                    f"explain carries per-lane stage seconds: {lanes}",
                )
                check("compile_s" in plan and "steady_s" in plan
                      and plan.get("bound") is not None,
                      f"explain carries the compile/steady split + bound: "
                      f"compile_s={plan.get('compile_s')} "
                      f"steady_s={plan.get('steady_s')} "
                      f"bound={plan.get('bound')}")
                adm = plan.get("admission") or {}
                check(adm.get("admitted") is True
                      and "queue_wait_s" in adm,
                      f"explain carries the admission verdict: {adm}")
            # ---- serving tier: a repeated query flips the EXPLAIN cache
            # verdict miss -> hit; a write to the table invalidates so the
            # third run is a miss again (the result cache can never serve
            # across a data change)
            srv_q = {"metric": "smoke_cpu", "start_ms": 0, "end_ms": 8000,
                     "bucket_ms": 1000}
            verdicts = []
            for step in ("first", "repeat"):
                async with s.post(f"{base}/api/v1/query?explain=1",
                                  json=srv_q) as r:
                    body = await r.json()
                    check(r.status == 200, f"serving {step} query answered")
                    verdicts.append(
                        (body.get("explain") or {}).get("serving") or {}
                    )
            check(verdicts[0].get("cache") == "miss",
                  f"first serving query is a cache miss: {verdicts[0]}")
            check(verdicts[1].get("cache") == "hit",
                  f"repeated serving query is a cache hit: {verdicts[1]}")
            async with s.post(f"{base}/api/v1/write",
                              data=make_payload()) as r:
                check(r.status == 200, "invalidating write accepted")
            async with s.post(f"{base}/api/v1/query?explain=1",
                              json=srv_q) as r:
                body = await r.json()
                srv = (body.get("explain") or {}).get("serving") or {}
                check(srv.get("cache") == "miss",
                      f"post-write re-query is a miss again (invalidation "
                      f"funnel fired): {srv}")
            # ---- query batcher: a concurrent burst of same-shape panels
            # (distinct host filters -> distinct cache keys, all misses)
            # must coalesce into a stacked launch (EXPLAIN batched_with >
            # 1), while a lone query afterwards stays batched_with=1 with
            # ZERO window hold — the 1-client p50 contract
            async def one_panel(host: str) -> dict:
                async with s.post(f"{base}/api/v1/query?explain=1", json={
                    "metric": "smoke_bulk", "start_ms": 0,
                    "end_ms": 4000, "bucket_ms": 1000,
                    "filters": {"host": host},
                }) as r:
                    body = await r.json()
                    return ((body.get("explain") or {}).get("batching")
                            or {})
            burst = await asyncio.gather(
                *(one_panel(f"bulk-{i:03d}") for i in range(8))
            )
            widths = [b.get("batched_with") for b in burst]
            check(any(w and w > 1 for w in widths),
                  f"concurrent same-shape burst coalesced "
                  f"(batched_with mix {widths})")
            coalesced = next(b for b in burst
                             if (b.get("batched_with") or 0) > 1)
            check(coalesced.get("shape_class") is not None,
                  f"EXPLAIN carries the shape class: {coalesced}")
            check("pad_waste_pct" in coalesced,
                  f"EXPLAIN carries pad waste: {coalesced}")
            lone = await one_panel("bulk-009")
            check(lone.get("batched_with") == 1
                  and lone.get("window_wait_s") == 0.0,
                  f"lone query stays batched_with=1 with no window "
                  f"penalty: {lone}")
            # ---- streaming rule engine: register a recording rule + an
            # alert rule over HTTP, drive a threshold-crossing write,
            # force a tick, and assert the rule series is queryable, the
            # alert reached firing, and the families moved
            from horaedb_tpu.common.time_ext import now_ms as _now_ms

            now = _now_ms()
            r_reg = {
                "kind": "recording", "name": "smoke:sig:sum",
                "expr": "sum by (host) (sum_over_time(smoke_sig[1m]))",
                "interval": "1m", "since_ms": now - 600_000,
            }
            async with s.post(f"{base}/api/v1/rules", json=r_reg) as r:
                check(r.status == 200, f"recording rule registered "
                                       f"({r.status})")
            a_reg = {
                "kind": "alert", "name": "SmokeSignal",
                "expr": 'smoke_sig{host="sig"}', "for": 0,
                "labels": {"severity": "smoke"},
            }
            async with s.post(f"{base}/api/v1/rules", json=a_reg) as r:
                check(r.status == 200, f"alert rule registered ({r.status})")
            # the threshold-crossing write: recent samples so the alert's
            # instant evaluation (5m lookback) sees them
            from horaedb_tpu.pb import remote_write_pb2

            sig = remote_write_pb2.WriteRequest()
            tser = sig.timeseries.add()
            for k, v in ((b"__name__", b"smoke_sig"), (b"host", b"sig")):
                lab = tser.labels.add()
                lab.name = k
                lab.value = v
            for i in range(5):
                smp = tser.samples.add()
                smp.timestamp = now - (5 - i) * 60_000
                smp.value = float(10 + i)
            async with s.post(f"{base}/api/v1/write",
                              data=sig.SerializeToString()) as r:
                check(r.status == 200, "rule-signal write accepted")
            async with s.post(f"{base}/api/v1/rules/tick") as r:
                tick = (await r.json()).get("data") or {}
                check(r.status == 200 and tick.get("errors") == 0
                      and tick.get("evaluated", 0) >= 2,
                      f"forced rule tick evaluated both rules: {tick}")
                check(tick.get("samples_written", 0) > 0,
                      f"recording rule wrote output samples: {tick}")
            async with s.post(f"{base}/api/v1/query?explain=1", json={
                "metric": "smoke:sig:sum", "start_ms": now - 900_000,
                "end_ms": now + 60_000,
            }) as r:
                body = await r.json()
                check(r.status == 200 and body.get("rows", 0) > 0,
                      f"rule-produced series is queryable: "
                      f"rows={body.get('rows')}")
                rp = ((body.get("explain") or {}).get("rules")
                      or {}).get("rule_produced") or {}
                check("smoke:sig:sum" in rp,
                      f"EXPLAIN carries rule provenance: {rp}")
            async with s.get(f"{base}/api/v1/alerts") as r:
                alerts = ((await r.json()).get("data") or {}).get(
                    "alerts") or []
                firing = [a for a in alerts
                          if a["labels"].get("alertname") == "SmokeSignal"]
                check(bool(firing) and firing[0]["state"] == "firing",
                      f"alert reached firing: {alerts}")
            async with s.get(f"{base}/api/v1/rules") as r:
                body = await r.json()
                groups = (body.get("data") or {}).get("groups") or []
                check(r.status == 200 and {g["name"] for g in groups}
                      == {"recording", "alerting"},
                      f"/api/v1/rules lists both groups "
                      f"({[g.get('name') for g in groups]})")
            async with s.get(f"{base}/debug/kernels") as r:
                cat = await r.json()
                check(
                    r.status == 200 and isinstance(cat.get("kernels"), list)
                    and len(cat["kernels"]) > 0,
                    f"/debug/kernels serves the catalog "
                    f"({len(cat.get('kernels', []))} kernels)",
                )
            async with s.get(f"{base}/debug/slowlog") as r:
                slog = await r.json()
                ids = [e.get("trace_id") for e in slog.get("entries", [])]
                check(
                    r.status == 200 and slog.get("enabled") is True
                    and trace_id in ids,
                    f"/debug/slowlog recorded the query "
                    f"({len(ids)} entries)",
                )
            async with s.get(f"{base}/debug/traces/{trace_id}") as r:
                t = await r.json()
                check(
                    r.status == 200 and t.get("trace_id") == trace_id
                    and t.get("root") is not None,
                    "/debug/traces/{id} round-trips the span tree",
                )
            # ---- overload shedding: with the store's circuit breaker
            # forced open, a write that must touch the store (fresh
            # metric name -> registration) answers 503 + Retry-After —
            # the graceful-degradation contract (server/errors.py)
            from horaedb_tpu.server.main import STATE_KEY

            store = app[STATE_KEY].engine._store
            store.breaker.force_open()
            try:
                async with s.post(f"{base}/api/v1/write",
                                  data=make_payload_named("smoke_shed")) as r:
                    check(r.status == 503,
                          f"breaker-open write answers 503 (got {r.status})")
                    check(r.headers.get("Retry-After", "").isdigit(),
                          f"503 carries Retry-After "
                          f"({r.headers.get('Retry-After')!r})")
            finally:
                store.breaker.reset()
            async with s.post(f"{base}/api/v1/write",
                              data=make_payload_named("smoke_shed")) as r:
                check(r.status == 200, "write recovers after breaker reset")
            # ---- cardinality defense: flood past max_series, then a
            # write carrying one EXISTING series + new ones must answer
            # the counted 503/Retry-After partial-accept
            # ~43 series exist (smoke_cpu a/b + 40 smoke_bulk hosts +
            # smoke_shed); 22 more cross the 60 limit (the gate engages on
            # the NEXT new series, not retroactively)
            async with s.post(f"{base}/api/v1/write",
                              data=make_bulk_payload(62, 1)) as r:
                check(r.status == 200, "flood crossing the limit accepted")
            over = make_bulk_payload(64, 1)  # 62 exist + 2 brand-new hosts
            async with s.post(f"{base}/api/v1/write", data=over) as r:
                body = await r.json()
                check(r.status == 503 and body.get("partial_accept") is True,
                      f"cardinality breach answers 503 partial-accept "
                      f"(got {r.status}: {body})")
                check(body.get("rejected_series") == 2
                      and body.get("accepted_samples") == 62,
                      f"partial-accept accounting exact ({body})")
                check(r.headers.get("Retry-After", "").isdigit(),
                      "cardinality 503 carries Retry-After")
            # in-budget traffic still flows at the limit
            async with s.post(f"{base}/api/v1/write",
                              data=make_bulk_payload(40, 1)) as r:
                check(r.status == 200,
                      "existing-series write still 200 at the limit")
            # ---- query admission shedding: with the scheduler forced
            # full, a query answers 503 + Retry-After (never a hang);
            # reset restores service. A tiny per-request timeout= must
            # answer 504 with the deadline taxonomy.
            adm_ctl = app[STATE_KEY].admission
            adm_ctl.force_full()
            try:
                async with s.post(f"{base}/api/v1/query", json={
                    "metric": "smoke_cpu", "start_ms": 0, "end_ms": 10_000,
                }) as r:
                    check(r.status == 503,
                          f"forced queue-full query answers 503 "
                          f"(got {r.status})")
                    check(r.headers.get("Retry-After", "").isdigit(),
                          f"admission 503 carries Retry-After "
                          f"({r.headers.get('Retry-After')!r})")
            finally:
                adm_ctl.reset_forced()
            async with s.post(f"{base}/api/v1/query", json={
                "metric": "smoke_cpu", "start_ms": 0, "end_ms": 10_000,
            }) as r:
                check(r.status == 200, "query recovers after admission reset")
            async with s.post(f"{base}/api/v1/query", json={
                "metric": "smoke_cpu", "start_ms": 0, "end_ms": 10_000,
                "timeout": 1e-9,
            }) as r:
                body = await r.json()
                check(r.status == 504
                      and body.get("deadline_exceeded") is True,
                      f"tiny timeout= answers 504 deadline-exceeded "
                      f"(got {r.status}: {body})")
            check(adm_ctl.inflight == 0,
                  f"admission slots all freed (inflight="
                  f"{adm_ctl.inflight})")
            # ---- self-telemetry: a SECOND server over a fresh store
            # (this one's 60-series cardinality cap would reject the
            # ~400-series self-scrape) proves the closed loop: a forced
            # scrape tick writes the registry through the ingest path,
            # and a PromQL range query over the self-written series
            # returns the snapshot BIT-EQUAL
            tel_scratch = tempfile.mkdtemp(prefix="horaedb-smoke-tel-")
            tel_cfg = Config.from_dict({
                "metric_engine": {
                    "storage": {"object_store": {
                        "type": "Local", "data_dir": tel_scratch,
                    }},
                    "telemetry": {"scrape_interval": "1h"},
                },
            })
            tel_app = await build_app(tel_cfg)
            tel_runner = web.AppRunner(tel_app)
            await tel_runner.setup()
            tel_site = web.TCPSite(tel_runner, "127.0.0.1", 0)
            await tel_site.start()
            tel_port = tel_site._server.sockets[0].getsockname()[1]
            tel = f"http://127.0.0.1:{tel_port}"
            try:
                fam = "horaedb_remote_write_samples_total"
                async with s.post(
                    f"{tel}/api/v1/telemetry/scrape?include={fam}"
                ) as r:
                    data = (await r.json()).get("data") or {}
                    check(r.status == 200 and data.get("written", 0) > 100,
                          f"forced self-scrape wrote the registry "
                          f"({data.get('written')} samples)")
                    check(data.get("dropped") == 0,
                          f"no series dropped by the budget: {data}")
                    matched = data.get("matched") or []
                    check(len(matched) == 1,
                          f"scrape echoed the {fam} snapshot: {matched}")
                    snap_v = matched[0]["value"]
                    ts_s = data["ts_ms"] / 1000.0
                async with s.get(
                    f"{tel}/api/v1/query_range?query={fam}"
                    f"&start={ts_s}&end={ts_s}&step=15"
                ) as r:
                    body = await r.json()
                    res = ((body.get("data") or {}).get("result") or [])
                    check(r.status == 200 and len(res) == 1,
                          f"range query over the self-series answered: "
                          f"{body}")
                    vals = res[0].get("values") or [] if res else []
                    check(
                        bool(vals) and float(vals[0][1]) == float(snap_v),
                        f"self-scraped value BIT-EQUAL to the registry "
                        f"snapshot ({vals[:1]} vs {snap_v})",
                    )
                async with s.get(f"{tel}/api/v1/usage?tenant=_system") as r:
                    u = ((await r.json()).get("data") or {}).get(
                        "since_boot") or {}
                    check(u.get("rows_ingested", 0) > 100,
                          f"_system tenant metered the scrape's rows: {u}")
            finally:
                await tel_runner.cleanup()
                import shutil as _shutil

                _shutil.rmtree(tel_scratch, ignore_errors=True)
            # ---- OpenMetrics negotiation: # EOF-terminated, exemplar-
            # carrying, and clean under the OpenMetrics validator
            async with s.get(f"{base}/metrics", headers={
                "Accept": "application/openmetrics-text",
            }) as r:
                om = await r.text()
                check("openmetrics-text" in r.headers.get(
                    "Content-Type", ""),
                    f"openmetrics content type negotiated "
                    f"({r.headers.get('Content-Type')!r})")
                check(om.rstrip().endswith("# EOF"),
                      "openmetrics body ends with # EOF")
                check('# {trace_id="' in om,
                      "openmetrics carries trace-id exemplars")
                om_errors = validate_openmetrics(om)
                for e in om_errors[:10]:
                    print(f"FAIL promcheck[openmetrics]: {e}")
                check(not om_errors,
                      f"openmetrics body passes the validator "
                      f"({len(om.splitlines())} lines)")
            async with s.get(f"{base}/metrics") as r:
                text = await r.text()
        errors = validate(text)
        for e in errors[:20]:
            print(f"FAIL promcheck: {e}")
        check(not errors,
              f"/metrics passes the exposition-format validator "
              f"({len(text.splitlines())} lines)")
        for fam in REQUIRED_FAMILIES:
            check(fam in text, f"/metrics exposes {fam}")
    finally:
        await runner.cleanup()
        await fake.stop()
        import shutil

        shutil.rmtree(scratch, ignore_errors=True)
    print(f"smoke-metrics: {len(failures)} failure(s)")
    return 1 if failures else 0


def main() -> None:
    import os
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # cold aggregation-calibration cache: the first downsample then pays
    # the registry micro-A/B, which drives the instrumented device kernels
    # and guarantees horaedb_jit_compile_total carries labeled kernels
    os.environ["HORAEDB_AGG_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="horaedb-smoke-calib-"), "agg_calib.json"
    )
    raise SystemExit(asyncio.run(run()))


if __name__ == "__main__":
    main()
