"""Server entrypoint (reference: src/server/src/main.rs:87-233).

Bootstrap mirrors the reference: structured logging with file/line/time
(tracing-subscriber analog), `--config <toml>`, LocalFileSystem object store,
an ObjectBasedStorage on the hardcoded demo schema (pk1,pk2,pk3,value Int64,
num_primary_keys=3, main.rs:178-185), the optional self-write load generator
(bench_write, main.rs:187-233), and the HTTP surface:

    GET  /                 greeting/health
    GET  /toggle           flip the load generator (main.rs:59-80)
    GET  /compact          manual compaction trigger
    GET  /metrics          Prometheus text metrics (beyond the reference)
    GET  /debug/traces     recent request traces; /debug/traces/{id} is the
                           span tree for the X-Horaedb-Trace-Id a query
                           response echoed (common/tracing.py)

plus the ingest/query endpoints the reference defines but never wired
(remote_write "NOT yet wired into server", SURVEY L5):

    POST /api/v1/write     Prometheus remote-write (snappy or raw protobuf)
    POST /api/v1/query     JSON query -> rows or downsample grids
    GET  /api/v1/query     query-string form (filters = leftover params)
    GET  /api/v1/labels    label values via the inverted index
    GET  /api/v1/metrics   metric-name listing
    GET  /api/v1/series    per-metric series listing
    GET  /api/v1/metadata  metric-family metadata (Prometheus shape)

plus the streaming rule engine (horaedb_tpu/rules):

    POST /api/v1/rules        register one recording/alert rule (durable)
    GET  /api/v1/rules        registered rules, Prometheus groups shape
    DELETE /api/v1/rules/{n}  unregister
    GET  /api/v1/alerts       active alerts (+ ?transitions=<rule> tail)
    POST /api/v1/rules/tick   force one evaluator tick (admin/debug)

Run: python -m horaedb_tpu.server.main --config docs/example.toml
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import sys
import time

import numpy as np
import pyarrow as pa
from aiohttp import web

from horaedb_tpu.common import deadline as deadline_ctx
from horaedb_tpu.common import compile_cache, memtrace, tracing, xprof
from horaedb_tpu.common.bytebudget import GLOBAL_POOLS, rss_bytes
from horaedb_tpu.common.error import (
    DeadlineExceeded,
    HoraeError,
    UnavailableError,
)
from horaedb_tpu.common.time_ext import now_ms
from horaedb_tpu.engine import MetricEngine, QueryRequest
from horaedb_tpu.ingest import ParserPool
from horaedb_tpu.ingest.pooled_parser import parser_backend
from horaedb_tpu.ingest.cardinality import CardinalityLimited
from horaedb_tpu.objstore import LocalStore
from horaedb_tpu.objstore.resilient import ResilientStore
from horaedb_tpu.server import admission
from horaedb_tpu.server.admission import AdmissionController
from horaedb_tpu.server.config import Config
from horaedb_tpu.server.errors import deadline_response, unavailable_response
from horaedb_tpu.server.metrics import GLOBAL_METRICS as METRICS
from horaedb_tpu.server.slowlog import SlowLog, build_entry
from horaedb_tpu.storage import scanstats
from horaedb_tpu.storage.read import CompactRequest, WriteRequest
from horaedb_tpu.storage.storage import ObjectBasedStorage
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.telemetry.metering import GLOBAL_METER as _METER

logger = logging.getLogger("horaedb_tpu.server")

STATE_KEY = web.AppKey("state", object)

# canonical spellings live in common/tracing.py (the cluster router
# funnel injects them; this tier adopts + echoes them)
TRACE_HEADER = tracing.TRACE_HEADER

HTTP_SECONDS = METRICS.histogram(
    "horaedb_http_request_seconds",
    help="HTTP request latency by route template and method.",
    labelnames=("endpoint", "method"),
    # OpenMetrics exemplars: route-latency buckets carry the trace id of
    # their latest observation (rendered under content negotiation)
    exemplars=True,
)
HTTP_REQUESTS = METRICS.counter(
    "horaedb_http_requests_total",
    help="HTTP requests by route template, method, and status.",
    labelnames=("endpoint", "method", "status"),
)
INGEST_BATCH_SAMPLES = METRICS.histogram(
    "horaedb_remote_write_batch_samples",
    help="Samples per accepted remote-write request.",
    buckets=(1.0, 10.0, 100.0, 1000.0, 10_000.0, 100_000.0, 1_000_000.0),
)


# Routes whose finished traces feed the slow-query flight recorder (the
# diagnosis surfaces themselves never spool).
QUERY_ENDPOINTS = frozenset((
    "/api/v1/query", "/api/v1/query_range", "/api/v1/query_exemplars",
))


def _record_slow_query(slowlog: "SlowLog | None", t) -> None:
    """Feed one FINISHED query trace to the flight recorder. The root
    span's attrs already carry the EXPLAIN payload and scanstats stages
    the handler attached, so the spooled entry is the full diagnosis the
    operator would have gotten live with ?explain=1."""
    if slowlog is None:
        return
    root = t.root
    if root is None or root.duration_s is None:
        return
    if not slowlog.admit(root.duration_s):
        return  # cheap pre-check; record() re-validates under its lock
    entry = build_entry(t.as_dict(), root.attrs.get("explain"))
    slowlog.record(t.trace_id, root.duration_s, entry)


def _remote_trace_context(request: web.Request):
    """(remote trace id, remote parent span id) when this request arrived
    through a peer's traced client funnel; (None, None) otherwise. The
    parent-span header is the gate: only the funnel sends it, so a client
    replaying an X-Horaedb-Trace-Id from a previous response cannot make
    this node adopt (and clobber) an old ring entry."""
    parent_raw = request.headers.get(tracing.PARENT_SPAN_HEADER)
    if parent_raw is None:
        return None, None
    remote_id = request.headers.get(TRACE_HEADER)
    try:
        parent = int(parent_raw)
    except ValueError:
        parent = None
    return remote_id, parent


@web.middleware
async def observability_middleware(request: web.Request, handler):
    """Every request (except the observability surfaces themselves) gets a
    trace (subject to sampling) and a latency histogram sample; the trace
    id is echoed in the X-Horaedb-Trace-Id response header so a caller can
    fetch its span tree from /debug/traces/{id}. Finished traces of query
    endpoints feed the slow-query flight recorder (including failed
    requests — a slow 500 is exactly what the recorder exists for).

    Cross-node plumbing: a request carrying the router funnel's trace
    headers ADOPTS the origin's trace id instead of minting one, and the
    finished span subtree ships back in the response's SPANS_HEADER so
    the origin grafts it into one stitched, node-labeled tree."""
    resource = request.match_info.route.resource
    endpoint = resource.canonical if resource is not None else "unmatched"
    if request.path.startswith(("/metrics", "/debug")):
        return await handler(request)
    remote_id, remote_parent = _remote_trace_context(request)
    t0 = time.perf_counter()
    status = 500
    finished = None
    try:
        with tracing.trace(
            f"{request.method} {endpoint}", remote_id=remote_id,
            remote_parent=remote_parent, method=request.method,
            path=request.path,
        ) as t:
            finished = t
            try:
                resp = await handler(request)
                status = resp.status
            except web.HTTPException as e:
                status = e.status
                if t is not None:
                    e.headers[TRACE_HEADER] = t.trace_id
                raise
            finally:
                tracing.add_attr(status=status)
                HTTP_SECONDS.labels(endpoint, request.method).observe(
                    time.perf_counter() - t0
                )
                HTTP_REQUESTS.labels(endpoint, request.method, str(status)).inc()
    except web.HTTPException as e:
        # the trace finished when the with-block unwound: a forwarded
        # request's error response still ships its span subtree home
        if finished is not None and remote_id == finished.trace_id:
            e.headers[tracing.SPANS_HEADER] = tracing.export_spans(finished)
        raise
    finally:
        # the trace context exited above, so duration_s is final here
        if finished is not None and endpoint in QUERY_ENDPOINTS:
            state: ServerState = request.app[STATE_KEY]
            try:
                _record_slow_query(state.slowlog, finished)
            except Exception:  # noqa: BLE001 — the flight recorder must
                # never fail the request it is observing
                logger.exception("slowlog record failed")
    if finished is not None:
        resp.headers[TRACE_HEADER] = finished.trace_id
        if remote_id == finished.trace_id:
            # adopted context: the callee's half of the cross-node tree
            # rides home in one bounded header (export degrades under
            # budget instead of overflowing aiohttp's field cap)
            resp.headers[tracing.SPANS_HEADER] = tracing.export_spans(finished)
    return resp


# Read endpoints the cluster router may offload from a writer to a
# healthy replica (the expensive query surface; discovery endpoints are
# index-cheap and always serve locally).
CLUSTER_READ_ROUTES = frozenset((
    "/api/v1/query", "/api/v1/query_range", "/api/v1/query_exemplars",
))


@web.middleware
async def cluster_middleware(request: web.Request, handler):
    """Cluster routing in the HTTP tier (horaedb_tpu/cluster/router.py):

    - On a WRITER with healthy replicas (`route_reads`), query requests
      forward to the rendezvous-picked replica (one panel's repeats keep
      hitting one replica's caches); a replica failure fails over to the
      local engine — hedged, never user-visible.
    - On a REPLICA (or standby), every query response carries the
      bounded-staleness token as `X-Horaedb-Staleness-Ms`.
    - `X-Horaedb-Forwarded` marks proxied requests; they are never
      re-routed (loop guard). Write forwarding lives in the write
      handler (it needs the body + partial-ownership split)."""
    from horaedb_tpu.cluster.router import FORWARD_HEADER, STALENESS_HEADER

    state: ServerState = request.app[STATE_KEY]
    cl = state.cluster
    if cl is None:
        return await handler(request)
    failed_peer = None
    if (
        cl.role == "writer" and not cl.standby
        and cl.config.route_reads
        and FORWARD_HEADER not in request.headers
        and request.path in CLUSTER_READ_ROUTES
        and request.method in ("GET", "POST")
    ):
        key = request.path_qs.encode()
        body = None
        if request.method == "POST":
            body = await request.read()  # cached: the handler re-reads
            key += body
        # a split-eligible grid query is worth more than one replica's
        # caches: fall through to the local handler, which scatters
        # region shards across the computing nodes instead
        peer = (None if _split_eligible(state, request, body)
                else cl.router.pick_read_peer(key))
        if peer is not None:
            res = await cl.router.forward(
                peer.node, request.method, request.path_qs,
                request.headers, body, "read",
            )
            if res is not None and res[0] < 500:
                status, hdrs, out = res
                out = _fleet_merge_body(state, out, remote_node=peer.node,
                                        wire_bytes=len(out))
                resp = web.Response(status=status, body=out)
                resp.headers["Content-Type"] = hdrs.get(
                    "Content-Type", "application/json"
                )
                if STALENESS_HEADER in hdrs:
                    resp.headers[STALENESS_HEADER] = hdrs[STALENESS_HEADER]
                return resp
            # replica error / unreachable: hedged failover to local
            cl.router.note_failover()
            failed_peer = peer.node
    resp = await handler(request)
    if failed_peer is not None and resp.body:
        # the dead peer's EXPLAIN fragment degrades to a counted partial
        # on the locally-served answer — the fleet verdict never hangs
        # on (or silently forgets) a replica that failed mid-route
        local_body = bytes(resp.body)
        merged = _fleet_merge_body(state, local_body,
                                   remote_node=None, partial=1)
        if merged is not local_body:
            fresh = web.Response(status=resp.status, body=merged)
            fresh.headers["Content-Type"] = resp.headers.get(
                "Content-Type", "application/json"
            )
            resp = fresh
    if (cl.replica is not None
            and request.path.startswith("/api/v1/")
            and request.path != "/api/v1/cluster/status"):
        from horaedb_tpu.cluster.router import STALENESS_HEADER as _SH

        resp.headers[_SH] = str(round(cl.replica.staleness_ms(), 1))
    return resp


def _cluster_verdict(state: "ServerState") -> dict:
    """EXPLAIN `cluster` verdict: who served this query and how stale
    its view may be. Standalone deployments report the role alone."""
    cl = state.cluster
    if cl is None:
        return {"role": "standalone"}
    out = {"role": "replica" if (cl.replica is not None) else cl.role,
           "node": cl.node_id}
    try:
        if cl.replica is not None:
            out.update(cl.replica.staleness())
        else:
            out["manifest_epoch"] = state.engine.manifest_epoch()
            out["staleness_ms"] = 0.0
    except Exception:  # noqa: BLE001 — verdict must never fail a query
        pass
    return out


def _fleet_merge_body(state: "ServerState", out: bytes,
                      remote_node: "str | None", partial: int = 0,
                      wire_bytes: "int | None" = None) -> bytes:
    """Splice the federated `fleet` verdict into a JSON query response
    carrying an EXPLAIN payload. `remote_node` names the peer whose
    engine produced the response (read offload); None means this node
    executed it (local serve / hedged failover). `partial` counts
    fragments lost to dead peers. Returns `out` UNCHANGED (same object —
    callers compare identity) when there is no EXPLAIN to merge into or
    the body isn't parseable; the cheap substring gate keeps the
    non-EXPLAIN forwarded path at zero parse cost."""
    cl = state.cluster
    if cl is None or not out or b'"explain"' not in out:
        return out
    from horaedb_tpu import cluster as cluster_mod

    try:
        body = json.loads(out)
        explain = body.get("explain") if isinstance(body, dict) else None
        if not isinstance(explain, dict):
            return out
        executed_by = remote_node if remote_node is not None else cl.node_id
        frags = []
        frag = cluster_mod.fleet_fragment(executed_by, explain)
        if frag is None:
            partial += 1
        if remote_node is not None:
            # the origin routed but did not execute: it contributes its
            # identity + freshness token, so the merged verdict names
            # BOTH halves of the hop (the scatter-gather shape)
            origin_frag = cluster_mod.fleet_fragment(
                cl.node_id, {"cluster": _cluster_verdict(state)}
            )
            if origin_frag is not None:
                frags.append(origin_frag)
        if frag is not None:
            frags.append(frag)
        explain["fleet"] = cluster_mod.fleet_verdict(
            cl.node_id, frags, partial, wire_bytes=wire_bytes
        )
        return json.dumps(body).encode()
    except Exception:  # noqa: BLE001 — the merge must never turn a good
        # answer into a 500; the un-merged body is still correct
        logger.exception("fleet EXPLAIN merge failed")
        return out


async def _cluster_forward_write(state: "ServerState", request: web.Request,
                                 raw_body: bytes) -> "web.Response | None":
    """Whole-payload write forwarding: a replica (or standby writer)
    routes every write to the owning writer, raw body + headers intact
    (snappy stays snappy). None = handle locally."""
    from horaedb_tpu.cluster.router import FORWARD_HEADER

    cl = state.cluster
    if cl is None or FORWARD_HEADER in request.headers:
        return None
    if cl.role != "replica" and not cl.standby:
        return None
    targets = cl.router.write_targets(0)
    if not targets:
        return unavailable_response(UnavailableError(
            "replica knows no healthy writer to forward the write to"
        ))
    res = None
    for node in targets:
        res = await cl.router.forward(
            node, "POST", request.path_qs, request.headers, raw_body,
            "write",
        )
        if res is not None:
            break
    if res is None:
        return unavailable_response(UnavailableError(
            f"no reachable writer (tried {targets!r})"
        ))
    status, hdrs, out = res
    resp = web.Response(status=status, body=out)
    resp.headers["Content-Type"] = hdrs.get("Content-Type",
                                            "application/json")
    return resp


async def _cluster_split_write(
    state: "ServerState", body: bytes, tenant: str,
) -> "tuple[int, int]":
    """Partial-writer write path: split the (decompressed) payload per
    region owner — the local subset lands through the normal parsed
    write, non-owned subsets re-encode and forward to their owners WITH
    the caller's tenant identity (the owner meters its own subset; the
    origin meters only the local one — the J015 ledger must neither
    double-count nor misattribute forwarded rows to "default").
    Returns (total accepted, locally landed); raises on a failed
    forward (the sender retries the whole batch; local writes are
    LWW-idempotent)."""
    from horaedb_tpu.cluster.router import split_by_owner

    cl = state.cluster
    tenant_hdr = state.config.metric_engine.query.tenant_header
    parsed = await state.parser_pool.decode(body)
    local, remote = split_by_owner(
        parsed, state.engine.router, cl.router.assignment, cl.node_id,
    )
    total = local_n = 0
    if local is not None:
        local_n = await state.engine.write_parsed(local)
        total += local_n
    for node, payload in remote.items():
        res = await cl.router.forward(
            node, "POST", "/api/v1/write", {tenant_hdr: tenant}, payload,
            "write",
        )
        if res is None or res[0] >= 300:
            raise UnavailableError(
                f"forwarded write subset to {node!r} failed "
                f"(status {res[0] if res else 'unreachable'})"
            )
        try:
            import json as _json

            total += int(_json.loads(res[2]).get("samples", 0))
        except Exception:  # noqa: BLE001 — body shape is ours, but be safe
            pass
    return total, local_n


def _split_eligible(state: "ServerState", request: web.Request,
                    body: "bytes | None") -> bool:
    """Cheap pre-parse gate for the scatter-gather read path: is this a
    native grid query this node could SPLIT across computing nodes
    instead of forwarding whole? False negatives only cost the split
    (the query still answers, whole-forwarded); a false positive (e.g.
    `"bucket_ms": null` in the body) just serves locally — the full
    eligibility check re-runs on the parsed request in `_scatter_plan`.
    """
    cl = state.cluster
    if cl is None or not cl.config.distributed.enabled:
        return False
    if request.path != "/api/v1/query":
        return False
    if "query" in request.query:  # PromQL rides the whole-forward path
        return False
    if request.method == "POST":
        if not body or b"bucket_ms" not in body or b'"query"' in body:
            return False
    elif "bucket_ms" not in request.query:
        return False
    engines = getattr(state.engine, "engines", None)
    if not engines or getattr(state.engine, "_legacy", True):
        return False
    if len(engines) < max(2, cl.config.distributed.min_regions):
        return False
    return bool(cl.router.compute_nodes())


def _scatter_plan(state: "ServerState", request: web.Request, req):
    """Full split eligibility on the PARSED query + the shard plan:
    {node: [region ids]} across self + healthy computing peers, or None
    (execute the single-node way). Only a non-standby regioned writer
    coordinates; forwarded requests never re-split (loop guard, same as
    the whole-forward path)."""
    from horaedb_tpu.cluster.router import FORWARD_HEADER

    cl = state.cluster
    if (cl is None or req.bucket_ms is None
            or FORWARD_HEADER in request.headers
            or cl.role != "writer" or cl.standby
            or not cl.config.distributed.enabled):
        return None
    engines = getattr(state.engine, "engines", None)
    if not engines or getattr(state.engine, "_legacy", True):
        return None
    regions = [int(r) for r in engines]
    if len(regions) < max(2, cl.config.distributed.min_regions):
        return None
    return cl.router.plan_scatter(
        regions, max_fanout=cl.config.distributed.max_fanout
    )


async def _run_distributed(state: "ServerState", req, q: dict, tenant: str,
                           cells: "int | None", plan: dict):
    """Drive one scatter-gather query: local shards compute through the
    normal admitted engine path while remote fragments are in flight;
    any failed fragment's shards re-run locally (counted in the fleet
    `partial`, never waited on past the fragment timeout); all
    per-region partials fold in canonical region order
    (cluster/partial.py) — bit-exact vs the single-node merge.

    Returns (merged out | None, admission slot, dist provenance dict).
    """
    from dataclasses import replace

    from horaedb_tpu import cluster as cluster_mod
    from horaedb_tpu.cluster import partial as partial_mod
    from horaedb_tpu.parallel.mesh import active_mesh
    from horaedb_tpu.server import admission

    cl = state.cluster
    dcfg = cl.config.distributed
    order = [int(r) for r in state.engine.engines]
    total = max(1, len(order))
    my_regions = list(plan.get(cl.node_id, []))
    remote_plan = {n: rs for n, rs in plan.items() if n != cl.node_id}
    tenant_hdr = state.config.metric_engine.query.tenant_header

    def _frag_body(regions: "list[int]") -> bytes:
        body = {k: v for k, v in q.items()
                if k not in ("explain", "partial_grids", "regions")}
        body["partial_grids"] = True
        body["regions"] = [int(r) for r in regions]
        return json.dumps(body).encode()

    def _cells_for(regions: "list[int]") -> "int | None":
        if cells is None:
            return None
        return max(1, cells * len(regions) // total)

    async def _local(regions: "list[int]"):
        lreq = replace(req, regions=[int(r) for r in regions])
        return await admission.run_query_partials(
            state.admission, state.engine, lreq, tenant=tenant,
            cells=_cells_for(regions),
        )

    remote_tasks = {
        node: asyncio.create_task(cl.router.fetch_partials(
            node, _frag_body(regions), headers={tenant_hdr: tenant},
            timeout_s=dcfg.fragment_timeout.seconds,
        ))
        for node, regions in remote_plan.items()
    }
    try:
        parts, slot = await _local(my_regions)
    except BaseException:
        for t in remote_tasks.values():
            t.cancel()
        raise
    parts = list(parts)
    frags: list[dict] = []
    memory_frags: list[dict] = []
    failed: list[int] = []
    partial_count = 0
    wire_bytes = 0
    for node, task in remote_tasks.items():
        payload = await task
        decoded = None
        if payload is not None:
            try:
                decoded = partial_mod.decode_partials(payload)
            except Exception:  # noqa: BLE001 — a garbled fragment is a
                # dead fragment; its shards re-run locally below
                logger.warning("undecodable partial-grid fragment from %s",
                               node, exc_info=True)
        if decoded is None:
            failed.extend(remote_plan[node])
            partial_count += 1
            continue
        header, remote_parts = decoded
        wire_bytes += len(payload)
        parts.extend(remote_parts)
        prov = dict(header.get("provenance") or {})
        prov.setdefault("regions", remote_plan[node])
        prov["wire_bytes"] = len(payload)
        mem_frag = prov.pop("memory", None)
        if isinstance(mem_frag, dict):
            memory_frags.append(mem_frag)
        frag = cluster_mod.fleet_fragment(
            header.get("node", node), {"cluster": prov}
        )
        if frag is not None:
            if isinstance(mem_frag, dict):
                frag["memory"] = mem_frag
            frags.append(frag)
    if failed:
        # degrade ladder rung 2: the coordinator owns every region
        # locally (shared store), so dead fragments re-run here through
        # a fresh admission slot — exact answer, degraded parallelism
        rerun_parts, slot = await _local(sorted(failed))
        parts.extend(rerun_parts)
        my_regions = sorted(set(my_regions) | set(failed))
    out = partial_mod.merge_partials(
        parts, order=order, device_mesh=active_mesh(),
    )
    dist = {
        "fragments": frags,
        "memory_fragments": memory_frags,
        "partial": partial_count,
        "wire_bytes": wire_bytes,
        "regions_local": my_regions,
        "plan": {n: [int(r) for r in rs] for n, rs in plan.items()},
    }
    return out, slot, dist


def init_logging() -> None:
    """file:line + local time + env filter (main.rs:88-94 analog; level from
    the standard logging env var style: HORAEDB_LOG=DEBUG)."""
    import os

    level = os.environ.get("HORAEDB_LOG", "INFO").upper()
    logging.basicConfig(
        level=getattr(logging, level, logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s %(filename)s:%(lineno)d %(message)s",
        stream=sys.stderr,
    )


def build_demo_schema() -> pa.Schema:
    """Hardcoded demo schema (main.rs:178-185)."""
    return pa.schema(
        [
            ("pk1", pa.int64()),
            ("pk2", pa.int64()),
            ("pk3", pa.int64()),
            ("value", pa.int64()),
        ]
    )


# Largest decompressed remote-write payload the server will materialize; a
# hostile leading uvarint must not drive an arbitrary allocation.
MAX_DECOMPRESSED = 256 * 1024 * 1024


def snappy_decompress(buf: bytes) -> bytes:
    """Raw-snappy decompress via pyarrow's codec (no python-snappy in the
    image): the uncompressed length is the stream's leading uvarint."""
    size, shift, i = 0, 0, 0
    while True:
        b = buf[i]
        size |= (b & 0x7F) << shift
        i += 1
        if not (b & 0x80):
            break
        shift += 7
    if size > MAX_DECOMPRESSED:
        raise ValueError(f"decompressed size {size} exceeds limit")
    return bytes(pa.Codec("snappy").decompress(buf, decompressed_size=size))


class ClusterState:
    """This node's cluster identity + routing fabric (horaedb_tpu/cluster):
    the rendezvous router over the peer table, the replica handle when
    role = "replica" (or a standby writer), and the partial-ownership
    flag that turns on write splitting."""

    def __init__(self, config, node_id: str, router, replica=None,
                 standby: bool = False, partial: bool = False,
                 store=None, cluster_root: str = "metrics/cluster",
                 engine_root: str = "metrics",
                 engine_kwargs: "dict | None" = None):
        self.config = config          # cluster.ClusterConfig
        self.node_id = node_id
        self.router = router          # cluster.router.ClusterRouter
        self.replica = replica        # cluster.replica.ReplicaEngine | None
        self.role = config.role
        # a writer-role process that owns no regions yet (serves reads as
        # a replica; /api/v1/cluster/takeover promotes it)
        self.standby = standby
        # a regioned writer owning a strict subset of regions (the
        # assignment map split them): non-owned writes forward per owner
        self.partial = partial
        # takeover needs to reopen engines over the shared store
        self.store = store
        self.cluster_root = cluster_root
        self.engine_root = engine_root
        self.engine_kwargs = dict(engine_kwargs or {})


class ServerState:
    def __init__(self, config: Config, storage, engine: MetricEngine,
                 parser_pool=None, slowlog: "SlowLog | None" = None,
                 admission_controller: "AdmissionController | None" = None,
                 rules=None, telemetry=None, cluster: "ClusterState | None" = None):
        self.config = config
        self.storage = storage       # demo ColumnarStorage (reference parity)
        self.engine = engine         # metric engine (remote-write path)
        self.parser_pool = parser_pool or ParserPool()
        self.slowlog = slowlog       # slow-query flight recorder (or None)
        # bounded query scheduler (server/admission.py): every query
        # handler routes through it (jaxlint J011)
        self.admission = admission_controller or AdmissionController()
        # streaming rule engine (horaedb_tpu/rules), None = disabled
        self.rules = rules
        # self-scrape collector (horaedb_tpu/telemetry), None = disabled
        # (config or the HORAEDB_TELEMETRY=off kill switch)
        self.telemetry = telemetry
        # cluster layer (horaedb_tpu/cluster), None = standalone
        self.cluster = cluster
        self.write_enabled = asyncio.Event()
        self.write_workers: list[asyncio.Task] = []


# ---------------------------------------------------------------------------
# handlers
# ---------------------------------------------------------------------------


async def shield_mutation(coro):
    """Run a state-MUTATING engine/storage call to completion even when
    the client disconnects. `handler_cancellation` exists so abandoned
    QUERIES free their admission slot — but it aborts every handler task,
    and a write/admin mutation cancelled between its internal awaits
    would commit half an operation (e.g. delete_series lands the
    data-table tombstone but not the exemplars one). Shielding keeps the
    mutation atomic: the inner task runs to completion, the cancellation
    re-raises AFTER it settles, and a failure after disconnect is logged
    (nobody is left to receive it)."""
    task = asyncio.ensure_future(coro)
    try:
        return await asyncio.shield(task)
    except asyncio.CancelledError:
        try:
            await task
        except Exception:  # noqa: BLE001 — no caller left to tell
            logger.exception("shielded mutation failed after client "
                             "disconnect")
        raise


async def handle_root(request: web.Request) -> web.Response:
    return web.json_response({"status": "ok", "engine": "horaedb-tpu"})


async def handle_toggle(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    if state.write_enabled.is_set():
        state.write_enabled.clear()
        flag = False
    else:
        state.write_enabled.set()
        flag = True
    return web.json_response({"enable_write": flag})


async def handle_compact(request: web.Request) -> web.Response:
    """Manual compaction. Optional `start`/`end` (epoch ms) scope the pick
    to SSTs overlapping that window (reference /compact is global-only)."""
    state: ServerState = request.app[STATE_KEY]
    rng = None
    if "start" in request.query or "end" in request.query:
        try:
            start = int(request.query.get("start", 0))
            end = int(request.query.get("end", 1 << 62))
        except ValueError:
            return web.json_response(
                {"error": "start/end must be integer epoch ms"}, status=400
            )
        if start > end:
            return web.json_response(
                {"error": f"start ({start}) must be <= end ({end})"}, status=400
            )
        rng = TimeRange(start, end)
    try:
        # the demo root may be a read-only view under cluster mode (its
        # writer is whichever process runs the load generator); the admin
        # op still compacts the METRIC engine below
        if not getattr(state.storage, "read_only", False):
            await shield_mutation(
                state.storage.compact(CompactRequest(time_range=rng))
            )
        await shield_mutation(state.engine.compact(time_range=rng))
    except UnavailableError as e:
        # transient store trouble stays the retryable 503 contract
        return unavailable_response(e)
    except HoraeError as e:
        # ONLY the deployment-shaped refusals are client errors:
        # read-only replica views and disabled schedulers. Anything
        # else (corrupt snapshot, FencedError mid-compaction) is a real
        # internal fault and must keep its 5xx signal for monitoring.
        from horaedb_tpu.common.error import ReplicaReadOnlyError

        if isinstance(e, ReplicaReadOnlyError) \
                or "compaction scheduler disabled" in str(e):
            return web.json_response({"error": str(e)}, status=400)
        raise
    METRICS.inc("horaedb_compactions_triggered_total")
    return web.json_response({
        "compaction": "triggered",
        **({"scope": [rng.start, rng.end]} if rng is not None else {}),
    })


async def handle_split_region(request: web.Request) -> web.Response:
    """Meta-plane split op (RFC :28-76 split rules): halves a region's hash
    range; the daughter owns the upper half for new writes. 400 on a
    non-regioned deployment or an unknown/unsplittable region."""
    from horaedb_tpu.engine.region import RegionedEngine

    state: ServerState = request.app[STATE_KEY]
    if not isinstance(state.engine, RegionedEngine):
        return web.json_response(
            {"error": "not a regioned deployment"}, status=400
        )
    try:
        region = int(request.query["region"])
    except (KeyError, ValueError):
        return web.json_response(
            {"error": "query param ?region=<id> required"}, status=400
        )
    try:
        daughter = await shield_mutation(state.engine.split_region(region))
    except HoraeError as e:
        return web.json_response({"error": str(e)}, status=400)
    METRICS.inc("horaedb_region_splits_total")
    return web.json_response({
        "split": region,
        "daughter": daughter,
        "regions": sorted(state.engine.engines),
    })


async def handle_metrics(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    pool = state.parser_pool.status
    METRICS.set("horaedb_parser_pool_size", pool["size"])
    METRICS.set("horaedb_parser_pool_available", pool["available"])
    # storage/engine gauges: live SSTs and un-merged manifest deltas per
    # table (the backpressure signals, manifest/mod.rs:248-262), buffered
    # ingest rows awaiting flush
    tables: dict = {"demo": state.storage}
    buffered = 0
    for prefix, e in state.engine.sub_engines().items():
        tables.update({
            f"{prefix}metrics": e.metrics_table,
            f"{prefix}series": e.series_table,
            f"{prefix}index": e.index_table,
            f"{prefix}tags": e.tags_table,
            f"{prefix}data": e.data_table,
            f"{prefix}exemplars": e.exemplars_table,
        })
        buffered += e.sample_mgr.buffered_rows
    for name, table in tables.items():
        METRICS.set(
            f'horaedb_ssts_live{{table="{name}"}}', len(table.manifest.all_ssts())
        )
        METRICS.set(
            f'horaedb_manifest_deltas{{table="{name}"}}',
            table.manifest.deltas_num,
        )
    METRICS.set("horaedb_ingest_buffered_rows", buffered)
    # unified pool registry: pull occupancy from the live cache owners
    # right before render, so horaedb_pool_* gauges are scrape-fresh
    GLOBAL_POOLS.refresh()
    # content negotiation: OpenMetrics (with # EOF + trace-id exemplars
    # on the latency histograms) when the scraper asks for it; classic
    # Prometheus text otherwise
    from horaedb_tpu.server.metrics import OPENMETRICS_CONTENT_TYPE

    if OPENMETRICS_CONTENT_TYPE in request.headers.get("Accept", ""):
        return web.Response(
            text=METRICS.render_openmetrics(),
            content_type=OPENMETRICS_CONTENT_TYPE,
        )
    return web.Response(text=METRICS.render(), content_type="text/plain")


async def handle_remote_write(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    body = await request.read()
    # cluster write routing: a replica / standby forwards the RAW body
    # to the owning writer (before any decompression — bytes stay bytes)
    forwarded = await _cluster_forward_write(state, request, body)
    if forwarded is not None:
        return forwarded
    if request.headers.get("Content-Encoding", "").lower() == "snappy":
        try:
            with tracing.span("snappy_decompress", bytes=len(body)):
                body = snappy_decompress(body)
        except Exception:  # noqa: BLE001
            return web.json_response({"error": "bad snappy payload"}, status=400)
    cl = state.cluster
    try:
        with tracing.span("ingest", bytes=len(body)):
            if cl is not None and cl.partial:
                # assignment-split regions: local subset + per-owner
                # forwards (cluster/router.py split_by_owner)
                n, n_local = await shield_mutation(
                    _cluster_split_write(state, body, _tenant_of(request))
                )
            else:
                n = await shield_mutation(state.engine.write_payload(body))
                n_local = n
    except CardinalityLimited as e:
        # series-cardinality partial-accept: existing-series samples WERE
        # accepted and are durable per the normal ack contract; only new
        # series (and their samples) were rejected. 503 + Retry-After so
        # senders back off; the body carries the exact accounting.
        logger.warning("remote write cardinality-limited: %s", e)
        _METER.account(_tenant_of(request),
                       rows_ingested=e.accepted_samples,
                       samples_rejected=e.rejected_samples)
        return unavailable_response(e, extra={
            "partial_accept": True,
            "accepted_samples": e.accepted_samples,
            "rejected_samples": e.rejected_samples,
            "rejected_series": e.rejected_series,
            "cardinality_limit": e.limit,
            "series_estimate": round(e.estimate),
        })
    except UnavailableError as e:
        # overload / store-down shedding: 503 + Retry-After with bounded
        # latency (breaker open fails fast; a stalled flush queue already
        # waited out its deadline) — the sender retries, nothing is lost
        logger.warning("remote write shed (unavailable): %s", e)
        return unavailable_response(e)
    except HoraeError as e:
        # client-shaped errors (malformed wire bytes, missing __name__)
        # stay 4xx
        msg = str(e)
        if "missing __name__" in msg or "malformed" in msg:
            return web.json_response({"error": msg}, status=400)
        logger.exception("remote write failed")
        return web.json_response({"error": msg}, status=500)
    except Exception as e:  # noqa: BLE001
        # internal failures must be 5xx: remote-write senders retry 5xx but
        # permanently DROP the batch on 4xx
        logger.exception("remote write failed")
        return web.json_response({"error": str(e)}, status=500)
    METRICS.inc("horaedb_remote_write_requests_total")
    METRICS.inc("horaedb_remote_write_samples_total", n)
    INGEST_BATCH_SAMPLES.observe(n)
    # per-tenant usage (telemetry/metering.py, the J015 funnel): only
    # LOCALLY-landed rows — a split-forwarded subset is metered by its
    # owning writer under the propagated tenant, never twice
    _METER.account(_tenant_of(request), rows_ingested=n_local)
    return web.json_response({"samples": n}, status=200)


def _raw_table_response(table, limit: int, explain: dict | None = None) -> web.Response:
    """Shared raw-row serialization (samples and exemplars): bounded by
    `limit` with a truncated flag; exemplar label blobs decode to dicts."""
    from horaedb_tpu.engine.types import decode_series_key

    truncated = table.num_rows > limit
    view = table.slice(0, limit)
    body = {
        "rows": view.num_rows,
        "truncated": truncated,
        "tsid": [str(x) for x in view.column("tsid").to_pylist()],
        "ts": view.column("ts").to_pylist(),
        "value": view.column("value").to_pylist(),
    }
    if "labels" in view.schema.names:
        body["labels"] = [
            {
                k.decode(errors="replace"): v.decode(errors="replace")
                for k, v in decode_series_key(blob or b"")
            }
            for blob in view.column("labels").to_pylist()
        ]
    if explain is not None:
        body["explain"] = explain
    return web.json_response(body)


# ---------------------------------------------------------------------------
# query admission plumbing (server/admission.py)
# ---------------------------------------------------------------------------


def _tenant_of(request: web.Request) -> str:
    """Fairness-accounting tenant: the configured header, else "default"."""
    state: ServerState = request.app[STATE_KEY]
    hdr = state.config.metric_engine.query.tenant_header
    return request.headers.get(hdr, "") or "default"


def _meter_scan(request: web.Request, st) -> None:
    """Fold one finished (or deadline-killed / shed — the caller paid for
    the partial scan too) query's byte provenance into the tenant's usage
    ledger (telemetry/metering.py)."""
    if st is None:
        return
    b = st.counts.get("bytes_scanned", 0)
    if b:
        _METER.account(_tenant_of(request), bytes_scanned=b)


def _query_deadline(state: "ServerState", raw_timeout) -> "deadline_ctx.Deadline":
    """End-to-end deadline for one query: Prometheus-style `timeout=`
    override, clamped to [metric_engine.query] max_timeout; absent ->
    default_timeout. Raises ValueError on garbage (the 400 path)."""
    qcfg = state.config.metric_engine.query
    secs = admission.parse_timeout_s(
        raw_timeout, qcfg.default_timeout.seconds, qcfg.max_timeout.seconds
    )
    return deadline_ctx.Deadline(secs)


def _promql_cells(state: "ServerState", expr, n_steps: int) -> int | None:
    """Grid-cell estimate for the admission cost model: steps x the
    matched-series count of every selector in the expression. Index
    lookups only — no scan, no IO."""
    from dataclasses import fields as dc_fields, is_dataclass

    from horaedb_tpu.promql import Selector

    names: list[str] = []
    stack = [expr]
    while stack:
        node = stack.pop()
        if isinstance(node, Selector):
            names.append(node.name)
        elif is_dataclass(node) and not isinstance(node, type):
            for f in dc_fields(node):
                v = getattr(node, f.name)
                if isinstance(v, (list, tuple)):
                    stack.extend(v)
                else:
                    stack.append(v)
    if not names:
        return None
    series = sum(state.engine.series_count(n.encode()) for n in names)
    return max(n_steps, 1) * max(series, 1)


def _progress_payload(st) -> dict | None:
    """Partial-progress provenance for a deadline-killed query's 504
    body: how far the scan got before the budget died (the caller paid
    for these numbers; naming them beats a bare timeout)."""
    if st is None:
        return None
    counts = dict(st.counts)
    return {
        "regions": counts.get("regions_fanout", 0),
        "ssts_selected": counts.get("ssts_selected", 0),
        "ssts_read": counts.get("ssts_read", 0),
        "ssts_bloom_pruned": counts.get("ssts_bloom_pruned", 0),
        "stages_s": {k: round(v, 6) for k, v in st.seconds.items()},
    }


# ---------------------------------------------------------------------------
# query EXPLAIN
# ---------------------------------------------------------------------------

_TRUTHY = ("1", "true", "yes", "on")


def _want_explain(request: web.Request, params: dict | None = None) -> bool:
    """`?explain=1` (query string, or merged PromQL form/JSON params)."""
    v = request.query.get("explain", "")
    if params is not None and not v:
        v = str(params.get("explain", ""))
    return v.lower() in _TRUTHY


def _explain_payload(st, mode: str, admission_verdict: dict | None = None) -> dict:
    """Assemble the plan a finished query leaves behind: what was touched
    (regions, SSTs, bloom prunes), which routes/kernels served it
    (scan path, dispatcher impl, instrumented-kernel envelopes), and where
    the time went (per-lane stage seconds, compile vs steady split, the
    roofline `bound` verdict). Pure dict assembly over the scanstats
    collector — the query already paid for every number in here."""
    att = st.attribution()
    counts = dict(st.counts)
    agg_impls = sorted(
        k[len("agg_impl_"):] for k in counts if k.startswith("agg_impl_")
    )
    if not agg_impls and mode == "downsample":
        # pushdowns that rode the sharded mesh path report via the
        # process-global dispatcher provenance instead of a collector note
        from horaedb_tpu.ops import agg_registry

        last = agg_registry.last_choice()
        if last:
            agg_impls = [last]
    scan_paths = sorted(
        k[len("path_"):] for k in counts if k.startswith("path_")
    )
    # compressed-domain scan provenance (storage/encoding.py +
    # ops/decode.py): which lanes scanned encoded and under which codec,
    # the wire-vs-materialized byte split (compression ratio), what the
    # zone maps / rle run skipping pruned before any decode, and which
    # decode funnel the calibrated dispatcher ran
    enc_lanes = {}
    for k in counts:
        if k.startswith("enclane_") and "=" in k:
            lane, _, codec = k[len("enclane_"):].partition("=")
            enc_lanes[lane] = codec
    encoding = {
        "lanes": enc_lanes,
        "ssts_encoded": counts.get("ssts_encoded", 0),
        "encoded_bytes": counts.get("encoded_bytes", 0),
        "decoded_bytes": counts.get("decoded_bytes", 0),
        "pages_pruned": counts.get("pages_pruned", 0),
        "runs_skipped": counts.get("runs_skipped", 0),
        "decode_impls": sorted(
            k[len("decode_impl_"):] for k in counts
            if k.startswith("decode_impl_")
        ),
    }
    # serving-tier verdict (horaedb_tpu/serving): did the result cache
    # serve this query (hit), was it computed + stored (miss), or was the
    # tier off/bypassed (bypass / None when the query never reached the
    # choke point); and which rollup resolution(s) substituted for raw
    # segment scans.
    if counts.get("serving_cache_hit"):
        cache_verdict = "hit"
    elif counts.get("serving_cache_miss"):
        cache_verdict = "miss"
    elif counts.get("serving_cache_bypass"):
        cache_verdict = "bypass"
    else:
        cache_verdict = None
    rollup_res = sorted(
        k[len("rollup_res_"):] for k in counts if k.startswith("rollup_res_")
    )
    serving_verdict = {
        "cache": cache_verdict,
        "rollup": (
            "none" if not rollup_res
            else rollup_res[0] if len(rollup_res) == 1
            else "mixed"
        ),
        "rollup_resolutions": rollup_res,
        "rollup_segments": counts.get("rollup_segments", 0),
        "rollup_rows_read": counts.get("rollup_rows_read", 0),
        "raw_segments": counts.get("raw_segments", 0),
    }
    # query-batcher verdict (server/batching.py): how many compatible
    # grid queries shared this query's stacked kernel launch (1 = ran
    # solo; None = never reached the batching decision point, e.g. raw
    # mode or a cache hit replay), the padded-buffer waste of that
    # launch, the shape class it coalesced under, and the time spent
    # holding in the coalescing window.
    batch_classes = sorted(
        k[len("batch_class_"):] for k in counts
        if k.startswith("batch_class_")
    )
    batching_verdict = {
        "batched_with": counts.get("batched_with"),
        "pad_waste_pct": counts.get("batch_pad_waste_pct", 0),
        "shape_class": batch_classes[0] if batch_classes else None,
        "window_wait_s": round(st.seconds.get("batch_window", 0.0), 6),
    }
    # the pushdown's folds (ops/aggregate.py fold_sorted): how many, the
    # classes their programs ran as ("<rows>x<series>x<buckets>", rows 0 =
    # the host lane) and the rows they took in against the rows of padding;
    # `pack_order` counts the packed passes before them by the work each
    # took (storage/read.py _packed_downsample_pass)
    fold_verdict = {
        "folds": counts.get("folds", 0),
        "classes": sorted(
            k[len("fold_class_"):] for k in counts if k.startswith("fold_class_")
        ),
        "rows_real": counts.get("fold_rows_real", 0),
        "rows_padded": counts.get("fold_rows_padded", 0),
        "pack_order": {o: counts.get("pack_" + o, 0)
                       for o in ("in_order", "sorted", "dedup")},
    }
    compile_s = st.seconds.get("compile", 0.0)
    total_s = sum(att["lanes_s"].values())
    kernels = []
    for entry in xprof.kernel_entries(st.kernels):
        entry["calls"] = st.kernels.get(entry["kernel"], 0)
        # the full signature map is catalog detail; EXPLAIN keeps the size
        entry.pop("signatures", None)
        kernels.append(entry)
    return {
        "mode": mode,
        "regions": counts.get("regions_fanout", 1),
        "ssts": {
            "selected": counts.get("ssts_selected", 0),
            "read": counts.get("ssts_read", 0),
            "bloom_pruned": counts.get("ssts_bloom_pruned", 0),
            # retention provenance: SSTs wholly past the horizon the
            # selection dropped before any IO (storage.select_ssts)
            "retention_pruned": counts.get("ssts_retention_pruned", 0),
            # partial-result provenance: SSTs a degraded store could not
            # serve (the query answered 503; this names what was missing)
            "unavailable": counts.get("ssts_unavailable", 0),
            # of the reads that pruned row groups by the predicate: served
            # from the min/max lanes kept with the cached footer, or by a
            # walk over the footer's metadata objects (the first read of an
            # SST, or a leaf the lanes cannot decide)
            "footer_lanes": counts.get("footer_lanes", 0),
            "footer_walks": counts.get("footer_walks", 0),
        },
        # tombstone provenance (storage/visibility.py): delete records
        # that masked rows in this scan, and how many rows they masked
        "tombstones_applied": counts.get("tombstones_applied", 0),
        "tombstone_rows_masked": counts.get("tombstone_rows_masked", 0),
        "scan_paths": scan_paths,
        "agg_impl": agg_impls[0] if agg_impls else None,
        "agg_impls": agg_impls,
        "stages_s": {k: round(v, 6) for k, v in st.seconds.items()},
        "lanes_s": att["lanes_s"],
        "bound": att["bound"],
        "compile_s": round(compile_s, 6),
        "steady_s": round(max(0.0, total_s - compile_s), 6),
        # admission verdict (server/admission.py): queued?, queue-wait
        # seconds, estimated device cost, load at admission. None when the
        # query never reached admission (e.g. shed before a slot).
        "admission": admission_verdict,
        "encoding": encoding,
        "serving": serving_verdict,
        "batching": batching_verdict,
        "fold": fold_verdict,
        # memory provenance (common/memtrace.py): the buffer-lineage
        # verdict — bytes allocated/copied per stage, copies vs views,
        # device staging bytes, peak-delta + top sites under deep mode.
        # Pinned schema (memtrace.VERDICT_KEYS); zeros when tracing off.
        "memory": memtrace.verdict(getattr(st, "mem", None)),
        "counts": counts,
        "kernels": kernels,
    }


def _finish_explain(state: "ServerState", st, mode: str,
                    want: bool,
                    admission_verdict: dict | None = None) -> dict | None:
    """Build the plan and attach it to the request's trace root so the
    slow-query flight recorder (and /debug/traces/{id}) carries it even
    when the caller did not ask for ?explain=1. Skipped entirely — zero
    assembly cost on the hot path — when the caller didn't ask AND the
    flight recorder is disabled (nobody would ever read it)."""
    if not want and state.slowlog is None:
        return None
    explain = _explain_payload(st, mode, admission_verdict=admission_verdict)
    # cluster verdict (horaedb_tpu/cluster): who served this and how
    # stale its view may be — the staleness token EXPLAIN carries
    explain["cluster"] = _cluster_verdict(state)
    tracing.add_attr(explain=explain, scanstats=st.as_dict())
    return explain if want else None


async def _promql_params(request: web.Request) -> dict:
    """Merge query-string and form/JSON body params (Prometheus clients
    send either; Grafana's POST mode uses form bodies). Malformed bodies
    raise ValueError so callers answer the Prometheus 400 shape."""
    out = dict(request.query)
    if request.method == "POST":
        if request.content_type == "application/json":
            try:
                body = await request.json()
            except Exception as e:  # noqa: BLE001 — client data
                raise ValueError(f"bad JSON body: {e}") from None
            if not isinstance(body, dict):
                raise ValueError("JSON body must be an object")
            out.update({k: str(v) for k, v in body.items()})
        else:
            body = await request.post()
            out.update({k: v for k, v in body.items() if isinstance(v, str)})
    return out


def _promql_error(e: Exception) -> web.Response:
    return web.json_response(
        {"status": "error", "errorType": "bad_data", "error": str(e)},
        status=400,
    )


async def handle_query_range(request: web.Request) -> web.Response:
    """Prometheus-compatible /api/v1/query_range: PromQL over the engine
    (the subset in horaedb_tpu/promql — *_over_time/aggregations ride the
    device pushdown). The reference has no query language at all."""
    from horaedb_tpu.promql import PromQLError, parse, parse_duration_ms
    from horaedb_tpu.promql.eval import RangeEvaluator, to_prometheus_matrix

    state: ServerState = request.app[STATE_KEY]
    st = None
    try:
        p = await _promql_params(request)
        expr = parse(p["query"])
        start_ms = int(float(p["start"]) * 1000)
        end_ms = int(float(p["end"]) * 1000)
        step_ms = parse_duration_ms(p["step"])
        dl = _query_deadline(state, p.get("timeout"))
        ev = RangeEvaluator(state.engine, start_ms, end_ms, step_ms)
        cells = _promql_cells(state, expr, len(ev.steps))
        # scan_stats outermost so the admission queue wait lands in the
        # collector (stage="queue_wait"); the deadline covers queue wait
        # AND the scan — end-to-end means end-to-end
        with scanstats.scan_stats() as st, \
                deadline_ctx.deadline_scope(dl):
            slot = state.admission.slot(_tenant_of(request), cells=cells)
            async with slot:
                series = await ev.eval(expr)
    except DeadlineExceeded as e:
        _meter_scan(request, st)
        return deadline_response(e, progress=_progress_payload(st))
    except UnavailableError as e:
        _meter_scan(request, st)
        return unavailable_response(e)
    except (PromQLError, HoraeError, KeyError, ValueError) as e:
        # post-scan PromQL errors exist (e.g. many-to-one vector
        # matching rejects AFTER both operands scanned) — the caller
        # paid for those bytes too
        _meter_scan(request, st)
        return _promql_error(e)
    METRICS.inc("horaedb_queries_total")
    _meter_scan(request, st)
    explain = _finish_explain(state, st, "promql_range",
                              _want_explain(request, p),
                              admission_verdict=slot.verdict())
    _attach_rule_provenance(state, explain, _selector_names(expr))
    body = {"status": "success", "data": to_prometheus_matrix(series, ev.steps)}
    if explain is not None:
        body["explain"] = explain
    return web.json_response(body)


async def handle_promql_instant(
    request: web.Request, params: dict
) -> web.Response:
    """Prometheus-compatible instant query (the `query` param form of
    /api/v1/query; requests without `query` fall through to the native
    JSON query API below)."""
    from horaedb_tpu.common.time_ext import now_ms
    from horaedb_tpu.promql import PromQLError, parse
    from horaedb_tpu.promql.eval import (
        LOOKBACK_MS,
        RangeEvaluator,
        to_prometheus_vector,
    )

    state: ServerState = request.app[STATE_KEY]
    st = None
    try:
        expr = parse(params["query"])
        at_ms = int(float(params.get("time", now_ms() / 1000.0)) * 1000)
        dl = _query_deadline(state, params.get("timeout"))
        # instant = a one-step range ending at `time` (window functions need
        # a left context; LOOKBACK covers bare selectors)
        ev = RangeEvaluator(state.engine, at_ms - LOOKBACK_MS, at_ms, LOOKBACK_MS)
        cells = _promql_cells(state, expr, 1)
        with scanstats.scan_stats() as st, \
                deadline_ctx.deadline_scope(dl):
            slot = state.admission.slot(_tenant_of(request), cells=cells)
            async with slot:
                series = await ev.eval(expr)
    except DeadlineExceeded as e:
        _meter_scan(request, st)
        return deadline_response(e, progress=_progress_payload(st))
    except UnavailableError as e:
        _meter_scan(request, st)
        return unavailable_response(e)
    except (PromQLError, HoraeError, ValueError) as e:
        _meter_scan(request, st)  # post-scan eval errors paid for bytes
        return _promql_error(e)
    METRICS.inc("horaedb_queries_total")
    _meter_scan(request, st)
    explain = _finish_explain(state, st, "promql_instant",
                              _want_explain(request, params),
                              admission_verdict=slot.verdict())
    _attach_rule_provenance(state, explain, _selector_names(expr))
    body = {"status": "success", "data": to_prometheus_vector(series, at_ms)}
    if explain is not None:
        body["explain"] = explain
    return web.json_response(body)


async def handle_query(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    # PromQL routing: `query` in the URL, or in a form-encoded POST body
    # (Grafana's POST mode). JSON POST bodies stay on the native API — its
    # own `query` key never existed, so there is no ambiguity.
    if "query" in request.query:
        return await handle_promql_instant(request, dict(request.query))
    if (
        request.method == "POST"
        and request.content_type in (
            "application/x-www-form-urlencoded", "multipart/form-data"
        )
    ):
        form = await request.post()
        if "query" in form:
            params = dict(request.query)
            params.update({k: v for k, v in form.items() if isinstance(v, str)})
            return await handle_promql_instant(request, params)
        return web.json_response(
            {"error": "form body without `query`; use the JSON API"},
            status=400,
        )
    try:
        if request.method == "GET":
            # curl/Grafana-style convenience: scalar params in the query
            # string (metric, start_ms, end_ms, bucket_ms, limit,
            # exemplars); tag filters as every remaining key. Matchers need
            # the JSON POST form.
            qs = dict(request.query)
            if len(request.query) != len(qs):
                # a duplicated key (e.g. &host=a&host=b) would silently drop
                # values; two equality filters on one key can never both
                # match — the caller wants the JSON matcher form
                raise ValueError(
                    "duplicate query parameter; use POST with matchers for "
                    "multiple constraints on one label"
                )
            qs.pop("explain", None)  # EXPLAIN flag, never a tag filter
            q = {
                k: qs.pop(k)
                for k in ("metric", "start_ms", "end_ms", "bucket_ms",
                          "limit", "exemplars", "timeout")
                if k in qs
            }
            if "bucket_ms" in q:
                q["bucket_ms"] = int(q["bucket_ms"])
            if "exemplars" in q:
                q["exemplars"] = q["exemplars"].lower() not in (
                    "0", "false", "no", "off", ""
                )
            q["filters"] = qs
        else:
            q = await request.json()
        if q.get("bucket_ms") is not None and int(q["bucket_ms"]) <= 0:
            raise ValueError("bucket_ms must be > 0")
        matchers = []
        raw_matchers = q.get("matchers", [])
        if isinstance(raw_matchers, dict):
            # convenience form {"host": {"op": "re", "pattern": "web.*"}} —
            # one matcher per key only
            raw_matchers = [
                {"key": k, **spec} for k, spec in raw_matchers.items()
            ]
        for spec in raw_matchers:
            # canonical list form supports several matchers on one label:
            # [{"key": "host", "op": "re", "pattern": "web.*"}, ...]
            matchers.append(
                (spec["key"].encode(), spec["op"], spec["pattern"].encode())
            )
        limit = min(int(q.get("limit", 100_000)), 1_000_000)
        if limit < 0:
            raise ValueError("limit must be >= 0")
        req = QueryRequest(
            metric=q["metric"].encode(),
            start_ms=int(q["start_ms"]),
            end_ms=int(q["end_ms"]),
            filters=[(k.encode(), v.encode()) for k, v in q.get("filters", {}).items()],
            matchers=matchers,
            bucket_ms=q.get("bucket_ms"),
            # +1 so the response can report `truncated` without paying for
            # unbounded materialization
            limit=limit + 1,
        )
    except Exception as e:  # noqa: BLE001
        return web.json_response({"error": f"bad query: {e}"}, status=400)
    try:
        dl = _query_deadline(state, q.get("timeout"))
    except ValueError as e:
        return web.json_response({"error": f"bad query: {e}"}, status=400)
    METRICS.inc("horaedb_queries_total")
    want_explain = _want_explain(request, q)
    mode = (
        "exemplars" if q.get("exemplars")
        else "raw" if req.bucket_ms is None else "downsample"
    )
    # cost-model sizing: only grid-shaped queries are predictable enough
    # to price (buckets x registered series of the metric — index lookup)
    cells = None
    if mode == "downsample":
        n_buckets = -(-(req.end_ms - req.start_ms) // req.bucket_ms)
        cells = int(n_buckets) * max(state.engine.series_count(req.metric), 1)
    tenant = _tenant_of(request)
    # distributed scatter-gather leaf: a coordinator asked THIS node to
    # compute a region-shard subset and answer compact partial grids
    # (cluster/partial.py wire) instead of a merged JSON response
    partial_wire = bool(q.get("partial_grids")) and mode == "downsample"
    if partial_wire and q.get("regions") is not None:
        try:
            req.regions = [int(r) for r in q["regions"]]
        except (TypeError, ValueError):
            return web.json_response(
                {"error": "bad query: regions must be a list of ints"},
                status=400,
            )
    dist = None
    st = None
    try:
        with scanstats.scan_stats() as st, \
                deadline_ctx.deadline_scope(dl):
            if q.get("exemplars"):
                table, slot = await admission.run_query_exemplars(
                    state.admission, state.engine, req, tenant=tenant
                )
            elif partial_wire:
                parts, slot = await admission.run_query_partials(
                    state.admission, state.engine, req, tenant=tenant,
                    cells=cells,
                )
            else:
                plan = (_scatter_plan(state, request, req)
                        if mode == "downsample" else None)
                if plan is not None:
                    out, slot, dist = await _run_distributed(
                        state, req, q, tenant, cells, plan
                    )
                else:
                    out, slot = await admission.run_query(
                        state.admission, state.engine, req, tenant=tenant,
                        cells=cells,
                    )
    except DeadlineExceeded as e:
        # end-to-end budget spent (queued or mid-scan): 504 with the
        # partial-progress provenance of what the scan HAD done
        _meter_scan(request, st)
        extra = (
            {"explain": _explain_payload(st, mode)} if want_explain else None
        )
        return deadline_response(e, progress=_progress_payload(st),
                                 extra=extra)
    except UnavailableError as e:
        # a required SST (or the flush barrier before the scan) hit a
        # down store — or the admission scheduler shed (queue full /
        # stalled / cost gate): typed 503 + Retry-After, with the
        # partial-result provenance of what WAS reached when the caller
        # asked for the plan
        _meter_scan(request, st)
        extra = (
            {"explain": _explain_payload(st, mode)} if want_explain else None
        )
        return unavailable_response(e, extra=extra)
    except HoraeError as e:
        _meter_scan(request, st)  # post-scan errors paid for bytes
        return web.json_response({"error": str(e)}, status=400)
    _meter_scan(request, st)
    explain = _finish_explain(state, st, mode, want_explain,
                              admission_verdict=slot.verdict())
    _attach_rule_provenance(state, explain, [q["metric"]])
    if partial_wire:
        from horaedb_tpu.cluster import WIRE_BYTES
        from horaedb_tpu.cluster.partial import (
            WIRE_CONTENT_TYPE,
            encode_partials,
        )

        cl = state.cluster
        prov = _cluster_verdict(state)
        prov["regions"] = sorted(
            {int(p[0]) for p in parts}
            | set(req.regions if req.regions is not None else ())
        )
        # leaf memory verdict rides the fragment header so the
        # coordinator can graft it into the federated memory verdict
        prov["memory"] = memtrace.verdict(getattr(st, "mem", None))
        payload = encode_partials(
            cl.node_id if cl is not None else "local", parts,
            provenance=prov,
        )
        WIRE_BYTES.labels("partial_grid", "tx").inc(len(payload))
        return web.Response(body=payload, content_type=WIRE_CONTENT_TYPE)
    if dist is not None and explain is not None:
        from horaedb_tpu import cluster as cluster_mod

        cl = state.cluster
        origin = cluster_mod.fleet_fragment(cl.node_id, explain)
        frags = []
        if origin is not None:
            origin["regions"] = [int(r) for r in dist["regions_local"]]
            frags.append(origin)
        explain["fleet"] = cluster_mod.fleet_verdict(
            cl.node_id, frags + dist["fragments"],
            partial=dist["partial"], wire_bytes=dist["wire_bytes"],
        )
        explain["fleet"]["distributed"] = {"plan": dist["plan"]}
        # graft remote leaf memory verdicts into the coordinator's own:
        # scalars add, peaks max — the fleet-wide copy tax of this query
        for mem_frag in dist.get("memory_fragments", ()):
            explain["memory"] = memtrace.verdict_merge(
                explain["memory"], mem_frag
            )
    if q.get("exemplars"):
        if table is None:
            return web.json_response(
                {"series": [], **({"explain": explain} if explain else {})}
            )
        return _raw_table_response(table, limit, explain=explain)
    if out is None:
        return web.json_response(
            {"series": [], **({"explain": explain} if explain else {})}
        )
    if req.bucket_ms is None:
        return _raw_table_response(out, limit, explain=explain)
    tsids, grids = out
    # limit bounds the series dimension of bucketed responses too
    truncated = len(tsids) > limit
    tsids = tsids[:limit]
    mean = grids["mean"][:limit]
    count = grids["count"][:limit]
    body = {
        "tsids": [str(t) for t in tsids],
        "buckets": grids["mean"].shape[1],
        "truncated": truncated,
        "mean": np.where(np.isnan(mean), None, mean).tolist(),
        "count": count.tolist(),
    }
    if explain is not None:
        body["explain"] = explain
    return web.json_response(body)


async def handle_delete_series(request: web.Request) -> web.Response:
    """Prometheus-admin-shaped tombstone delete
    (POST /api/v1/admin/tsdb/delete_series): `match[]` instant selectors
    plus optional `start`/`end` (epoch seconds; default = all time).
    Deletes are visible to queries immediately (scan-time masking via the
    shared visibility helper) and physically applied when compaction
    rewrites the matched SSTs; samples written AFTER the delete survive."""
    from horaedb_tpu.promql import PromQLError, Selector, parse
    from horaedb_tpu.promql.eval import _to_query

    state: ServerState = request.app[STATE_KEY]
    try:
        p = await _promql_params(request)
    except ValueError as e:
        return _promql_error(e)
    # match[] is multi-valued in BOTH carriers (query string and form
    # body) — _promql_params' dict collapse would silently drop all but
    # the last selector, a silent under-delete on a GDPR surface
    match_exprs = list(request.query.getall("match[]", []))
    if request.method == "POST" and request.content_type in (
        "application/x-www-form-urlencoded", "multipart/form-data"
    ):
        form = await request.post()
        match_exprs += [v for v in form.getall("match[]", [])
                        if isinstance(v, str)]
    if not match_exprs and "match[]" in p:
        match_exprs = [p["match[]"]]  # JSON body: single selector
    if not match_exprs:
        return _promql_error(ValueError("match[] selector(s) required"))
    try:
        start_ms = int(float(p["start"]) * 1000) if "start" in p else 0
        # no end = "up to now": rows written after the delete survive by
        # sequence anyway, and an unbounded range would make the
        # tombstone permanently un-GC-able (it would overlap every live
        # SST forever)
        end_ms = (int(float(p["end"]) * 1000) + 1 if "end" in p
                  else now_ms() + 1)
        results = []
        for expr in match_exprs:
            node = parse(expr)
            if not isinstance(node, Selector) or node.range_ms is not None:
                raise PromQLError(
                    f"match[] must be an instant selector: {expr!r}"
                )
            q = _to_query(node, start_ms, end_ms)
            with tracing.span("delete_series", metric=node.name):
                r = await shield_mutation(state.engine.delete_series(
                    q.metric, filters=q.filters, matchers=q.matchers,
                    start_ms=start_ms, end_ms=end_ms,
                ))
            r["match"] = expr
            results.append(r)
    except UnavailableError as e:
        return unavailable_response(e)
    except (PromQLError, HoraeError, KeyError, ValueError) as e:
        return _promql_error(e)
    METRICS.inc("horaedb_delete_series_requests_total")
    return web.json_response({"status": "success", "data": results})


async def handle_metrics_list(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    names = state.engine.metric_names()
    return web.json_response({"metrics": [n.decode(errors="replace") for n in names]})


async def _match_series(state: ServerState, match_exprs: list[str]) -> list[dict]:
    """Resolve Prometheus `match[]` selectors to label maps (discovery
    surface behind /api/v1/series, /labels and /label/:name/values). Goes
    through the engines' public match_series — regex matchers evaluate off
    the event loop and regioned deployments fan out."""
    from horaedb_tpu.promql import PromQLError, Selector, parse
    from horaedb_tpu.promql.eval import _to_query

    out, seen = [], set()
    for expr in match_exprs:
        node = parse(expr)
        if not isinstance(node, Selector) or node.range_ms is not None:
            raise PromQLError(f"match[] must be an instant selector: {expr!r}")
        q = _to_query(node, 0, 1)
        matched = await state.engine.match_series(q.metric, q.filters, q.matchers)
        for t, labs in matched.items():
            if (node.name, t) in seen:
                continue
            seen.add((node.name, t))
            d = {k.decode(errors="replace"): v.decode(errors="replace")
                 for k, v in labs.items()}
            d["__name__"] = node.name
            out.append(d)
    return out


async def handle_series(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    if "match[]" in request.query:
        # Prometheus-shaped series discovery (Grafana variables)
        from horaedb_tpu.promql import PromQLError

        try:
            data = await _match_series(state, request.query.getall("match[]"))
        except (PromQLError, HoraeError) as e:
            return _promql_error(e)
        return web.json_response({"status": "success", "data": data})
    metric = request.query.get("metric", "").encode()
    return web.json_response({"series": state.engine.series(metric)})


async def _all_label_names(
    state: ServerState, match_exprs: list[str] | None
) -> list[str]:
    names: set[str] = {"__name__"}
    if match_exprs:
        for d in await _match_series(state, match_exprs):
            names.update(d.keys())
        return sorted(names)
    # engines' public surface (NOT metric_mgr/index_mgr: RegionedEngine
    # has neither — it answers via fan-out, mirroring match_series)
    names.update(k.decode(errors="replace") for k in state.engine.label_names())
    return sorted(names)


async def handle_labels(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    if "metric" in request.query or "key" in request.query:
        # native surface: values of one key under one metric
        metric = request.query.get("metric", "").encode()
        key = request.query.get("key", "").encode()
        vals = state.engine.label_values(metric, key)
        return web.json_response(
            {"values": [v.decode(errors="replace") for v in vals]}
        )
    # Prometheus-shaped label-NAME listing (optional match[] scope)
    from horaedb_tpu.promql import PromQLError

    try:
        match = (request.query.getall("match[]")
                 if "match[]" in request.query else None)
        data = await _all_label_names(state, match)
    except (PromQLError, HoraeError) as e:
        return _promql_error(e)
    return web.json_response({"status": "success", "data": data})


async def handle_label_values(request: web.Request) -> web.Response:
    """Prometheus /api/v1/label/{name}/values — Grafana's autocomplete
    surface. `__name__` lists metrics; other labels union their values
    across metrics (scoped by match[] when given)."""
    from horaedb_tpu.promql import PromQLError

    state: ServerState = request.app[STATE_KEY]
    name = request.match_info["name"]
    try:
        if "match[]" in request.query:
            rows = await _match_series(state, request.query.getall("match[]"))
            vals = sorted({d[name] for d in rows if name in d})
            return web.json_response({"status": "success", "data": vals})
        if name == "__name__":
            vals = sorted(
                m.decode(errors="replace") for m in state.engine.metric_names()
            )
            return web.json_response({"status": "success", "data": vals})
        out: set[str] = set()
        for metric in state.engine.metric_names():
            for v in state.engine.label_values(metric, name.encode()):
                out.add(v.decode(errors="replace"))
        return web.json_response({"status": "success", "data": sorted(out)})
    except (PromQLError, HoraeError) as e:
        return _promql_error(e)


async def handle_debug_traces(request: web.Request) -> web.Response:
    """Recent traces, newest first (summaries; span trees via /{id}).
    `?limit=N` bounds the count; `?min_ms=X` keeps only traces at least
    that slow — together they serve the operator's "last 10 slow traces"
    pull without scraping the whole ring."""
    try:
        limit = int(request.query.get("limit", 50))
    except ValueError:
        return web.json_response({"error": "limit must be an int"}, status=400)
    min_ms = None
    if "min_ms" in request.query:
        try:
            min_ms = float(request.query["min_ms"])
        except ValueError:
            return web.json_response(
                {"error": "min_ms must be a number"}, status=400
            )
    return web.json_response({
        "sampling": tracing.sampling_enabled(),
        "traces": tracing.recent(limit, min_ms=min_ms),
    })


async def handle_debug_trace(request: web.Request) -> web.Response:
    """One trace's span tree by id (the X-Horaedb-Trace-Id header value)."""
    t = tracing.get(request.match_info["id"])
    if t is None:
        return web.json_response(
            {"error": "unknown trace id (evicted from the ring, or never "
                      "sampled)"},
            status=404,
        )
    return web.json_response(t)


async def handle_debug_kernels(request: web.Request) -> web.Response:
    """Process-wide instrumented-kernel catalog (common/xprof.py): per
    xjit kernel, the compile/retrace history and distinct arg-signatures;
    under `xla`, EVERY backend compile of the process (eager `jnp`
    operations included) and the persistent cache's hits."""
    import jax

    devices = jax.devices()
    return web.json_response({
        "backend": jax.default_backend(),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "totals": xprof.snapshot(),
        "xla": xprof.xla_totals(),
        "kernels": xprof.catalog(),
    })


async def handle_debug_slowlog(request: web.Request) -> web.Response:
    """Slow-query flight recorder contents, slowest first: each entry is
    one recorded request's full span tree + EXPLAIN payload. `?limit=N`
    bounds the response; corrupt spool entries are skipped (logged +
    counted in `corrupt_skipped`), never a 500."""
    state: ServerState = request.app[STATE_KEY]
    if state.slowlog is None:
        return web.json_response({
            "enabled": False, "capacity": 0, "entries": [],
        })
    limit = None
    if "limit" in request.query:
        try:
            limit = int(request.query["limit"])
        except ValueError:
            return web.json_response(
                {"error": "limit must be an int"}, status=400
            )
    entries, corrupt = state.slowlog.entries(limit=limit)
    return web.json_response({
        "enabled": True,
        "capacity": state.slowlog.capacity,
        "min_duration_s": state.slowlog.min_duration_s,
        "corrupt_skipped": corrupt,
        "entries": entries,
    })


async def handle_debug_memory(request: web.Request) -> web.Response:
    """`GET /debug/memory`: the data-plane memory observatory on one
    page — unified pool occupancy (all four byte-budgeted caches through
    the common/bytebudget registry), process RSS, the per-stage copy-tax
    table accumulated since boot, and the memtrace mode. Every number is
    a read-back of state the process already keeps; the handler computes
    nothing new. `device`: the accelerator's own memory statistics, of
    the fullest local device (only the process that holds the chip can
    read them)."""
    pools = GLOBAL_POOLS.refresh()
    return web.json_response({
        "memtrace_mode": memtrace.mode() or "default",
        "rss_bytes": rss_bytes(),
        "device": _device_memory(),
        "pools": pools,
        # since-boot lineage aggregate, sorted by bytes moved: the
        # fleet-independent face of the per-query EXPLAIN verdict
        "copy_tax": memtrace.copy_tax_table(),
    })


def _device_memory() -> dict:
    """`memory_stats()` of the local device with the highest peak (a CPU
    backend reports none: `{}`)."""
    import jax

    worst: dict = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use", -1) >= worst.get("peak_bytes_in_use", -1):
            worst = stats
    return {k: v for k, v in worst.items() if isinstance(v, (int, float))}


async def handle_profile_start(request: web.Request) -> web.Response:
    """`POST /debug/profile/start?dir=<directory>`: open a jax.profiler
    session of this process (the one that holds the chip) writing under
    `dir`. The Python tracer is off: on, a 9 s trace of the serving
    process held 1.5 M host events and its stop blocked for 17 s (PERF.md,
    PR 26); the host's lanes come from the stage funnel's annotations
    (`flush.*`, `compaction.*`, `scan.*`, `ingest.*`, `xjit.*`). One
    session at a time: a second start answers 409."""
    import jax

    out_dir = request.query.get("dir")
    if not out_dir:
        return web.json_response({"error": "dir is required"}, status=400)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    t0 = time.perf_counter()
    try:
        jax.profiler.start_trace(out_dir, profiler_options=options)
    except RuntimeError as e:  # a session is already open
        return web.json_response({"error": str(e)}, status=409)
    return web.json_response({
        "dir": out_dir, "started_unix": time.time(),
        "start_call_s": time.perf_counter() - t0,
    })


async def handle_profile_stop(request: web.Request) -> web.Response:
    """`POST /debug/profile/stop`: close the session and write the trace
    (`<dir>/plugins/profile/<time>/*.xplane.pb`). Collecting blocks for
    seconds (`stop_call_s`), so it runs off the event loop."""
    import jax

    stopped = time.time()
    t0 = time.perf_counter()
    try:
        await asyncio.to_thread(jax.profiler.stop_trace)
    except RuntimeError as e:  # no session open
        return web.json_response({"error": str(e)}, status=409)
    return web.json_response({
        "stopped_unix": stopped, "stop_call_s": time.perf_counter() - t0,
    })


async def handle_buildinfo(request: web.Request) -> web.Response:
    """Minimal Prometheus buildinfo (datasource health checks probe it)."""
    return web.json_response({
        "status": "success",
        "data": {
            "version": "2.45.0", "application": "horaedb-tpu",
            # which rung of the ingest parser chain this process took
            # (ingest/pooled_parser.py): native | protobuf | wire
            "parser_backend": parser_backend(),
        },
    })


async def handle_query_exemplars(request: web.Request) -> web.Response:
    """Prometheus /api/v1/query_exemplars (Grafana's trace-integration
    surface): instant-selector `query` + start/end seconds -> exemplars
    grouped per series with their trace labels."""
    from horaedb_tpu.engine.types import decode_series_key
    from horaedb_tpu.promql import PromQLError, Selector, parse
    from horaedb_tpu.promql.eval import _to_query

    state: ServerState = request.app[STATE_KEY]
    st = None
    try:
        p = await _promql_params(request)
        node = parse(p["query"])
        if not isinstance(node, Selector) or node.range_ms is not None:
            raise PromQLError("query must be an instant vector selector")
        start_ms = int(float(p["start"]) * 1000)
        end_ms = int(float(p["end"]) * 1000)
        dl = _query_deadline(state, p.get("timeout"))
        req = _to_query(node, start_ms, end_ms + 1)
        req.limit = 10_000
        with scanstats.scan_stats() as st, \
                deadline_ctx.deadline_scope(dl):
            table, _slot = await admission.run_query_exemplars(
                state.admission, state.engine, req,
                tenant=_tenant_of(request),
            )
    except DeadlineExceeded as e:
        _meter_scan(request, st)
        return deadline_response(e, progress=_progress_payload(st))
    except UnavailableError as e:
        _meter_scan(request, st)
        return unavailable_response(e)
    except (PromQLError, HoraeError, KeyError, ValueError) as e:
        _meter_scan(request, st)  # post-scan errors paid for bytes
        return _promql_error(e)
    METRICS.inc("horaedb_queries_total")
    _meter_scan(request, st)
    if table is None or table.num_rows == 0:
        return web.json_response({"status": "success", "data": []})
    matched = await state.engine.match_series(req.metric, req.filters, req.matchers)
    by_tsid: dict[int, list] = {}
    tsids = table.column("tsid").to_pylist()
    tss = table.column("ts").to_pylist()
    vals = table.column("value").to_pylist()
    blobs = table.column("labels").to_pylist()
    for t, ts, v, blob in zip(tsids, tss, vals, blobs):
        by_tsid.setdefault(int(t), []).append({
            "labels": {
                k.decode(errors="replace"): val.decode(errors="replace")
                for k, val in decode_series_key(blob or b"")
            },
            "value": str(v),
            "timestamp": ts / 1000.0,
        })
    data = []
    for t, exemplars in sorted(by_tsid.items()):
        labs = matched.get(t, {})
        series_labels = {
            k.decode(errors="replace"): v.decode(errors="replace")
            for k, v in labs.items()
        }
        series_labels["__name__"] = node.name
        data.append({"seriesLabels": series_labels, "exemplars": exemplars})
    return web.json_response({"status": "success", "data": data})


async def handle_metadata(request: web.Request) -> web.Response:
    """Prometheus-shaped /api/v1/metadata: metric family -> [{"type": t}],
    from remote-write METADATA records (advisory, in-memory)."""
    state: ServerState = request.app[STATE_KEY]
    meta = state.engine.metadata()
    return web.json_response({
        "status": "success",
        "data": {
            name.decode(errors="replace"): [{"type": t}]
            for name, t in sorted(meta.items())
        },
    })


# ---------------------------------------------------------------------------
# self-telemetry surface (horaedb_tpu/telemetry)
# ---------------------------------------------------------------------------


async def handle_usage(request: web.Request) -> web.Response:
    """Per-tenant usage summary (telemetry/metering.py, the J015 funnel):
    `?tenant=X` for one tenant (since-boot + `?window=5m` trailing view);
    without `tenant`, every known tenant's since-boot totals. Serving
    this never touches the query path — it reads the in-memory ledger."""
    window_s = None
    raw_window = request.query.get("window")
    if raw_window:
        try:
            # the admission parser is the one float-or-duration reader
            # (and the one that rejects NaN/inf — a NaN window would
            # silently sum nothing). Clamped to the ledger's actual ring
            # horizon (1 h): a wider window CANNOT be answered here —
            # the clamp is visible in the response's `seconds`, and
            # `coverage_seconds` marks any further truncation (short
            # uptime). Longer ranges are a PromQL query over the
            # self-scraped horaedb_tenant_* series.
            from horaedb_tpu.telemetry.metering import UsageMeter

            window_s = admission.parse_timeout_s(
                raw_window, 300.0, UsageMeter.horizon_s()
            )
        except Exception as e:  # noqa: BLE001 — client data
            return web.json_response(
                {"status": "error", "errorType": "bad_data",
                 "error": f"bad window: {e}"},
                status=400,
            )
    tenant = request.query.get("tenant")
    if tenant:
        data = _METER.summary(tenant, window_s=window_s)
    else:
        data = {
            "tenants": [
                _METER.summary(t, window_s=window_s)
                for t in _METER.tenants()
            ],
        }
    return web.json_response({"status": "success", "data": data})


def _telemetry_unavailable() -> web.Response:
    return web.json_response(
        {"status": "error", "errorType": "unavailable",
         "error": "self-telemetry disabled ([metric_engine.telemetry] "
                  "enabled = false, or HORAEDB_TELEMETRY=off)"},
        status=501,
    )


async def handle_telemetry_scrape(request: web.Request) -> web.Response:
    """Force one self-scrape tick NOW (admin/debug; the smoke gate uses
    it instead of waiting out the interval). `?include=<prefix>` echoes
    the written samples whose __name__ starts with the prefix — the
    bit-equality oracle for range-query checks."""
    state: ServerState = request.app[STATE_KEY]
    if state.telemetry is None:
        return _telemetry_unavailable()
    # a forced tick also forces a federation sweep (when configured):
    # the operator probing "is telemetry flowing" means the FLEET view
    summary = await shield_mutation(
        state.telemetry.tick(force_federation=True)
    )
    if summary.get("error"):
        # the background loop retries silently; the FORCED tick is an
        # operator probe, and a probe must not dress a failed write as
        # success (automation keys on the status)
        return web.json_response(
            {"status": "error", "errorType": "internal",
             "error": "self-scrape tick failed (see server log)",
             "data": summary},
            status=503,
        )
    samples = summary.pop("samples_list", [])
    include = request.query.get("include")
    if include:
        summary["matched"] = [
            {"name": n, "labels": dict(k), "value": v}
            for n, k, v in samples if n.startswith(include)
        ]
    return web.json_response({"status": "success", "data": summary})


async def handle_telemetry_snapshot(request: web.Request) -> web.Response:
    """`GET /api/v1/telemetry/snapshot`: the registry's JSON twin of
    /metrics — [[sample name, [[label, value]...], value]...] — what a
    peer's federation sweep pulls through the traced client funnel.
    Served regardless of the local collector (a read-only replica never
    WRITES its own telemetry, but the fleet still scrapes it)."""
    state: ServerState = request.app[STATE_KEY]
    cl = state.cluster
    node = (cl.node_id if cl is not None
            else state.config.metric_engine.telemetry.instance)
    return web.json_response({"status": "success", "data": {
        "node": node,
        "samples": METRICS.federation_snapshot(),
    }})


# ---------------------------------------------------------------------------
# streaming rule engine surface (horaedb_tpu/rules)
# ---------------------------------------------------------------------------


def _selector_names(expr) -> tuple:
    """Metric names a parsed PromQL expression reads (EXPLAIN rule
    provenance for the PromQL handlers) — the shared promql walker."""
    from horaedb_tpu.promql.eval import selector_metrics

    return selector_metrics(expr)


def _rule_provenance(state: "ServerState", metrics) -> dict | None:
    """EXPLAIN provenance for rule-produced series: which of the queried
    metrics are recording-rule outputs, and the producing rule's body —
    so a dashboard reading `cpu:rate5m` can see it is materialized, by
    what, from what."""
    if state.rules is None:
        return None
    hit = sorted(set(metrics) & state.rules.output_metrics())
    if not hit:
        return None
    produced = {}
    for m in hit:
        rule = state.rules.rule_for_metric(m)
        if rule is not None:
            produced[m] = {"rule": rule.name, "expr": rule.expr,
                           "interval_ms": rule.interval_ms}
    return {"rule_produced": produced}


def _attach_rule_provenance(state, explain, metrics) -> None:
    if explain is None:
        return
    prov = _rule_provenance(state, metrics)
    if prov is not None:
        explain["rules"] = prov


def _rules_unavailable() -> web.Response:
    return web.json_response(
        {"status": "error", "errorType": "unavailable",
         "error": "rule engine disabled ([metric_engine.rules] "
                  "enabled = false)"},
        status=501,
    )


async def handle_rules_get(request: web.Request) -> web.Response:
    """Registered rules, Prometheus /api/v1/rules groups shape (one
    implicit group per kind), with live alert state folded in."""
    state: ServerState = request.app[STATE_KEY]
    if state.rules is None:
        return _rules_unavailable()
    recording, alerting = [], []
    # named rule GROUPS (shared interval, ordered in-tick evaluation):
    # each renders as its own Prometheus group; ungrouped recording
    # rules keep the implicit "recording" group
    named_groups: dict[str, list] = {}
    active = {}
    for a in state.rules.alerts():
        active.setdefault(a["labels"]["alertname"], []).append(a)
    for rule in state.rules.list_rules():
        if rule.kind == "recording":
            entry = {
                "type": "recording", "name": rule.name,
                "query": rule.expr, "labels": rule.labels,
                "interval": rule.interval_ms / 1000.0,
            }
            if getattr(rule, "group", ""):
                entry["group_order"] = rule.group_order
                named_groups.setdefault(rule.group, []).append(
                    (rule.group_order, rule.name, entry)
                )
            else:
                recording.append(entry)
        else:
            alerts = active.get(rule.name, [])
            worst = "inactive"
            if any(a["state"] == "firing" for a in alerts):
                worst = "firing"
            elif alerts:
                worst = "pending"
            alerting.append({
                "type": "alerting", "name": rule.name,
                "query": rule.expr, "duration": rule.for_ms / 1000.0,
                "labels": rule.labels, "annotations": rule.annotations,
                "state": worst, "alerts": alerts,
            })
    groups = []
    if recording:
        groups.append({"name": "recording", "rules": recording})
    for g in sorted(named_groups):
        members = [e for _o, _n, e in sorted(named_groups[g],
                                             key=lambda t: t[:2])]
        groups.append({
            "name": g,
            # the group-shared interval (registration enforces equality)
            "interval": members[0]["interval"],
            "rules": members,
        })
    if alerting:
        groups.append({"name": "alerting", "rules": alerting})
    return web.json_response({"status": "success",
                              "data": {"groups": groups}})


async def handle_rules_post(request: web.Request) -> web.Response:
    """Register (or replace, by name) one rule. Body: {"kind":
    "recording"|"alert", "name", "expr", "interval"|"for", "labels",
    "annotations"}. The PUT of the durable record is the registration's
    durability point — a 200 means the rule survives restarts."""
    from horaedb_tpu.promql import PromQLError
    from horaedb_tpu.rules import rule_from_dict

    state: ServerState = request.app[STATE_KEY]
    if state.rules is None:
        return _rules_unavailable()
    try:
        body = await request.json()
    except Exception as e:  # noqa: BLE001 — client data
        return _promql_error(ValueError(f"bad JSON body: {e}"))
    try:
        rule = rule_from_dict(body, now_ms=now_ms())
        # idempotent like the boot path: re-POSTing an UNCHANGED
        # definition (config-sync reconciliation) must not reset the
        # watermark or wipe the alert state machine / transition log
        changed = await shield_mutation(state.rules.ensure_registered(rule))
    except UnavailableError as e:
        return unavailable_response(e)
    except (PromQLError, HoraeError, KeyError, TypeError, ValueError) as e:
        return _promql_error(e)
    METRICS.inc("horaedb_rules_api_registrations_total")
    return web.json_response({
        "status": "success",
        "data": {"kind": rule.kind, "name": rule.name, "expr": rule.expr,
                 "updated": changed},
    })


async def handle_rules_delete(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    if state.rules is None:
        return _rules_unavailable()
    name = request.match_info["name"]
    try:
        known = await shield_mutation(state.rules.delete(name))
    except UnavailableError as e:
        return unavailable_response(e)
    if not known:
        return web.json_response(
            {"status": "error", "errorType": "bad_data",
             "error": f"unknown rule {name!r}"},
            status=404,
        )
    return web.json_response({"status": "success", "data": {"deleted": name}})


async def handle_alerts(request: web.Request) -> web.Response:
    """Active alerts (Prometheus /api/v1/alerts shape). The optional
    `?transitions=<rule>` debug view returns that rule's durable
    transition-log tail (the exactly-once record the runbooks and the
    chaos oracle read)."""
    state: ServerState = request.app[STATE_KEY]
    if state.rules is None:
        return _rules_unavailable()
    name = request.query.get("transitions")
    if name:
        return web.json_response({
            "status": "success",
            "data": {"rule": name,
                     "transitions": state.rules.transitions(name)},
        })
    return web.json_response({
        "status": "success", "data": {"alerts": state.rules.alerts()},
    })


async def handle_rules_tick(request: web.Request) -> web.Response:
    """Force one evaluator tick NOW (admin/debug; the smoke gate and
    stuck-pending runbooks use it instead of waiting out the interval).
    Serialized with the background loop by the engine's tick lock."""
    state: ServerState = request.app[STATE_KEY]
    if state.rules is None:
        return _rules_unavailable()
    try:
        summary = await shield_mutation(state.rules.tick())
    except UnavailableError as e:
        return unavailable_response(e)
    return web.json_response({"status": "success", "data": summary})


# ---------------------------------------------------------------------------
# cluster surface (horaedb_tpu/cluster)
# ---------------------------------------------------------------------------


def _cluster_regions_view(state: "ServerState") -> dict:
    """{region_id: {"owned", "epoch"}} for the status payload — works for
    a single engine, a regioned engine, and a replica facade alike."""
    eng = state.engine
    engines = getattr(eng, "engines", None)
    if engines is None:
        return {"0": {
            "owned": not getattr(eng, "read_only", False),
            "epoch": eng.manifest_epoch(),
        }}
    return {
        str(i): {"owned": not sub.read_only, "epoch": sub.manifest_epoch()}
        for i, sub in sorted(engines.items())
    }


_BREAKER_STATES = {0: "closed", 1: "half_open", 2: "open"}


def _load_view() -> dict:
    """This node's load in one dict, read ENTIRELY from the metric
    registry (no reach into admission/resilient internals — the metrics
    are the stable contract): admission inflight/queued, object-store
    breaker states, shed totals by reason. Rides the cluster status
    payload, so peers' probe loops carry every node's load to every
    /debug/cluster page within one probe interval."""
    view: dict = {"inflight": 0, "queued": 0, "breakers": {}, "sheds": {}}
    for family, _type, _sample, key, value in METRICS.snapshot_samples():
        if family == "horaedb_query_inflight":
            view["inflight"] = int(value)
        elif family == "horaedb_query_queued":
            view["queued"] = int(value)
        elif family == "horaedb_objstore_breaker_state":
            store = dict(key).get("store", "?")
            view["breakers"][store] = _BREAKER_STATES.get(
                int(value), str(value)
            )
        elif family == "horaedb_query_shed_total" and value:
            view["sheds"][dict(key).get("reason", "?")] = value
    return view


async def handle_cluster_status(request: web.Request) -> web.Response:
    """`/api/v1/cluster/status`: this node's role, per-region ownership +
    manifest epochs, the staleness token (replicas), the assignment-map
    view, and peer health — the router's probe target AND the operator's
    catch-up check (writer epoch == replica epoch means caught up)."""
    state: ServerState = request.app[STATE_KEY]
    cl = state.cluster
    if cl is None:
        return web.json_response({"status": "success", "data": {
            "enabled": False, "role": "standalone",
            "manifest_epoch": state.engine.manifest_epoch(),
        }})
    data = {
        "enabled": True,
        "role": cl.role,
        "node": cl.node_id,
        "standby": cl.standby,
        "partial": cl.partial,
        "manifest_epoch": state.engine.manifest_epoch(),
        "regions": _cluster_regions_view(state),
        "peers": cl.router.peer_status(),
        "load": _load_view(),
    }
    if cl.replica is not None:
        st = cl.replica.staleness()
        data["manifest_epoch"] = st["manifest_epoch"]
        data["staleness_ms"] = st["staleness_ms"]
        data["stale"] = (
            st["staleness_ms"] / 1000.0
            > cl.config.max_staleness.seconds
        )
    asg = cl.router.assignment
    if asg is not None:
        data["assignment"] = {
            "version": asg.version,
            "regions": {str(r): n for r, n in sorted(asg.regions.items())},
        }
    return web.json_response({"status": "success", "data": data})


async def handle_cluster_refresh(request: web.Request) -> web.Response:
    """Force one watch probe NOW (admin/debug; smoke gates and tests use
    it instead of waiting out the watch interval). On a replica this
    swaps in any fresh snapshots; on a partial writer it refreshes the
    non-owned (read-only) region views. Either way one peer-probe round
    runs first, so a peer that was down at boot (and got marked
    unhealthy by the initial probe) rejoins the routable set without
    waiting out the probe interval."""
    state: ServerState = request.app[STATE_KEY]
    cl = state.cluster
    if cl is None:
        return web.json_response(
            {"status": "error", "errorType": "unavailable",
             "error": "cluster layer disabled ([metric_engine.cluster])"},
            status=501,
        )
    if cl.router.peers:
        try:
            await cl.router.probe_once()
        except Exception:  # noqa: BLE001 — health converges on the loop
            logger.warning("forced peer probe failed", exc_info=True)
    if cl.replica is not None:
        try:
            outcome = await shield_mutation(cl.replica.watch_once())
        except Exception as e:  # noqa: BLE001 — faulted store
            return unavailable_response(UnavailableError(
                f"refresh probe failed: {e}"
            ))
        return web.json_response({"status": "success", "data": {
            "outcome": outcome, **cl.replica.staleness(),
        }})
    engines = getattr(state.engine, "engines", None)
    refreshed = []
    if engines is not None:
        for rid, sub in sorted(engines.items()):
            if sub.read_only:
                await shield_mutation(state.engine.refresh_region(rid))
                refreshed.append(rid)
    return web.json_response({"status": "success", "data": {
        "outcome": "refreshed" if refreshed else "noop",
        "regions": refreshed,
        "manifest_epoch": state.engine.manifest_epoch(),
    }})


async def handle_cluster_takeover(request: web.Request) -> web.Response:
    """Writer takeover (`?region=all` or `?region=<id>`): rewrite the
    assignment map to name THIS node the owner, then reopen the region
    as a writer — the fresh epoch-fence acquisition deposes the lapsed
    writer regardless of what it believes (storage/fence.py). The
    operator runbook for a dead writer (docs/operations.md "Scale-out");
    background rule/telemetry loops resume on the next boot."""
    from horaedb_tpu.cluster import TAKEOVERS
    from horaedb_tpu.cluster import assignment as asg_mod

    state: ServerState = request.app[STATE_KEY]
    cl = state.cluster
    if cl is None or cl.role != "writer":
        return web.json_response(
            {"error": "takeover requires cluster role = writer"}, status=400
        )
    raw = request.query.get("region", "all")
    asg = cl.router.assignment or await asg_mod.load_assignment(
        cl.store, cl.cluster_root
    )
    # the regions this deployment actually has: the engine's live set,
    # plus anything the assignment map names (a split elsewhere)
    engines = getattr(state.engine, "engines", None)
    known = set(asg.regions) | (set(engines) if engines is not None
                                else {0})
    if raw == "all":
        targets = sorted(known - set(asg.regions_of(cl.node_id)))
    else:
        try:
            targets = [int(raw)]
        except ValueError:
            return web.json_response(
                {"error": "?region= must be an int or 'all'"}, status=400
            )
        unknown = [r for r in targets if r not in known]
        if unknown:
            # never commit an assignment version (a permanent audit-log
            # record) for a region that does not exist
            return web.json_response(
                {"error": f"unknown region(s) {unknown}; known: "
                          f"{sorted(known)}"},
                status=400,
            )
    taken = []
    for rid in targets:
        def mutate(regions, rid=rid):
            regions[int(rid)] = cl.node_id
            return regions

        asg = await shield_mutation(asg_mod.propose_assignment(
            cl.store, cl.cluster_root, cl.node_id, mutate
        ))
        engines = getattr(state.engine, "engines", None)
        if engines is not None and rid in engines:
            if engines[rid].read_only:
                await shield_mutation(
                    state.engine.promote_region(rid, cl.node_id)
                )
        elif cl.replica is not None or cl.standby:
            # single-engine standby: swap the replica facade for a real
            # writer engine (the open's fence acquisition deposes)
            new_engine = await shield_mutation(MetricEngine.open(
                cl.engine_root, cl.store,
                **{**cl.engine_kwargs, "fence_node_id": cl.node_id},
            ))
            old = state.engine
            state.engine = new_engine
            if cl.replica is not None:
                await cl.replica.close()
            else:
                await old.close()
            cl.replica = None
            cl.standby = False
        TAKEOVERS.inc()
        taken.append(rid)
    cl.router.set_assignment(asg)
    if getattr(state.engine, "engines", None) is not None:
        cl.partial = any(
            sub.read_only for sub in state.engine.engines.values()
        )
    return web.json_response({"status": "success", "data": {
        "taken": taken,
        "assignment_version": asg.version,
        "regions": _cluster_regions_view(state),
        # rule evaluation / self-telemetry were sized for the boot-time
        # role; a restart picks them up under the new ownership
        "restart_recommended": bool(taken) and (state.rules is None),
    }})


async def handle_debug_cluster(request: web.Request) -> web.Response:
    """`GET /debug/cluster`: the fleet on one page — this node's role,
    epoch, staleness/watch posture and load, plus every peer as the
    router sees it (health, probe-reported role/epoch/staleness/load)
    and the telemetry-federation posture. Everything here is already
    in memory (registry reads + the router's probe cache): rendering
    the page costs no cluster traffic."""
    state: ServerState = request.app[STATE_KEY]
    cl = state.cluster
    self_view: dict = {
        "node": (cl.node_id if cl is not None
                 else state.config.metric_engine.telemetry.instance),
        "role": cl.role if cl is not None else "standalone",
        "manifest_epoch": state.engine.manifest_epoch(),
        "load": _load_view(),
    }
    if cl is not None:
        self_view["standby"] = cl.standby
        self_view["partial"] = cl.partial
        if cl.replica is not None:
            self_view["replica"] = cl.replica.watch_stats()
    federation = (state.telemetry.federation_status()
                  if state.telemetry is not None else {"enabled": False})
    data = {
        "enabled": cl is not None,
        "self": self_view,
        "peers": cl.router.peer_detail() if cl is not None else {},
        "federation": federation,
    }
    if cl is not None and cl.router.assignment is not None:
        asg = cl.router.assignment
        data["assignment"] = {
            "version": asg.version,
            "regions": {str(r): n
                        for r, n in sorted(asg.regions.items())},
        }
    return web.json_response({"status": "success", "data": data})


# ---------------------------------------------------------------------------
# self-write load generator (main.rs:187-233)
# ---------------------------------------------------------------------------


async def bench_write_worker(state: ServerState, worker_id: int) -> None:
    interval = state.config.test.write_interval.seconds
    rng = np.random.default_rng(worker_id)
    schema = build_demo_schema()
    while True:
        await state.write_enabled.wait()
        t = now_ms()
        batch = pa.RecordBatch.from_pydict(
            {
                "pk1": rng.integers(0, 1000, 1000),
                "pk2": rng.integers(0, 1000, 1000),
                "pk3": rng.integers(0, 1000, 1000),
                "value": rng.integers(0, 1_000_000, 1000),
            },
            schema=schema,
        )
        try:
            await state.storage.write(
                WriteRequest(batch, TimeRange(t, t + 1), enable_check=True)
            )
            METRICS.inc("horaedb_bench_writes_total")
        except Exception:  # noqa: BLE001
            logger.exception("bench write failed")
        await asyncio.sleep(interval)


LOOP_LAG_SECONDS = METRICS.histogram(
    "horaedb_loop_lag_seconds",
    help="How late the event loop woke a 20 ms timer. The sum over a "
         "window is the time the loop ran late, which is what every "
         "request queued on it waited.",
    buckets=(0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0),
)
LOOP_LAG_PERIOD_S = 0.02


async def loop_lag_heartbeat() -> None:
    """Started with the app, cancelled at its clean-up: whatever holds the
    loop (a synchronous merge inside a coroutine, a long callback) shows
    as the lateness of this task's wake-ups."""
    loop = asyncio.get_running_loop()
    while True:
        due = loop.time() + LOOP_LAG_PERIOD_S
        await asyncio.sleep(LOOP_LAG_PERIOD_S)
        LOOP_LAG_SECONDS.observe(max(0.0, loop.time() - due))


# ---------------------------------------------------------------------------
# bootstrap
# ---------------------------------------------------------------------------


async def build_app(config: Config, store=None) -> web.Application:
    """`store`: optional pre-built ObjectStore overriding the config's
    store selection — the chaos gate (tools/chaos_smoke.py) boots the
    real server over a ChaosStore this way. Callers injecting a store
    own its resilience wrapping; config-built stores are always wrapped
    in a ResilientStore here, so every component (engine flush,
    manifest, fence, compaction, scan reads) inherits the retry/breaker
    policy."""
    from concurrent.futures import ThreadPoolExecutor

    config.validate()
    # memory observatory mode ([metric_engine.memory] memtrace, default
    # from HORAEDB_MEMTRACE — the config never clobbers an env override)
    memtrace.configure(config.metric_engine.memory.memtrace)
    store_cfg = config.metric_engine.storage.object_store
    # imported at boot so horaedb_agg_impl_total renders on /metrics even
    # before the first aggregate dispatch
    from horaedb_tpu.ops import agg_registry

    # same contract for the horaedb_jit_* families (lazy by module
    # layering; forced here so scrapers see the zero state from boot)
    xprof.register_metrics()

    res = store_cfg.resilience
    if store is not None:
        pass  # injected store: caller owns wrapping (see docstring)
    elif store_cfg.type.lower() == "s3like":
        from horaedb_tpu.objstore.s3 import S3LikeStore

        store = ResilientStore(
            S3LikeStore(store_cfg.to_s3_config()),
            retry=res.retry, breaker=res.breaker, name="s3like",
        )
    else:
        store = ResilientStore(
            LocalStore(store_cfg.data_dir),
            retry=res.retry, breaker=res.breaker, name="local",
        )
        # aggregation + decode calibration caches live under the data root
        # (an S3 deployment keeps the tmpdir default — the caches are
        # per-BOX measurement, not shared state)
        agg_registry.configure_cache_dir(store_cfg.data_dir)
        from horaedb_tpu.ops import decode as decode_ops

        decode_ops.configure_cache_dir(store_cfg.data_dir)
    segment_ms = config.test.segment_duration.as_millis()
    # ThreadConfig sizes the dedicated executor for CPU-heavy SST work —
    # the analog of the reference's named multi-thread runtimes
    # (main.rs:102-119): heavy compaction encodes no longer compete with
    # ingest for the event loop's default pool.
    # every pool's threads take their Python names at the OS level as they
    # start (scanstats.name_thread): a new thread is born with its
    # creator's name, and the profiler cannot tell lines of one name apart
    sst_executor = ThreadPoolExecutor(
        max_workers=config.metric_engine.threads.sst_thread_num,
        thread_name_prefix="sst", initializer=scanstats.name_thread,
    )
    manifest_executor = ThreadPoolExecutor(
        max_workers=config.metric_engine.threads.manifest_thread_num,
        thread_name_prefix="manifest", initializer=scanstats.name_thread,
    )
    cluster_cfg = config.metric_engine.cluster
    replica_role = cluster_cfg.enabled and cluster_cfg.role == "replica"
    # The demo root has no epoch fence: in ANY cluster topology (writer +
    # standby included) a second process running its merger/compaction/GC
    # would be an unfenced concurrent mutator on the shared bucket. It
    # opens writable only when this process actually drives it (the
    # self-write load generator, single-process by config validation).
    demo_read_only = cluster_cfg.enabled and (
        replica_role or not config.test.enable_write
    )
    storage = await ObjectBasedStorage.try_new(
        root="demo",
        store=store,
        arrow_schema=build_demo_schema(),
        num_primary_keys=3,
        segment_duration_ms=segment_ms,
        config=config.metric_engine.storage.time_merge_storage,
        sst_executor=sst_executor,
        manifest_executor=manifest_executor,
        read_only=demo_read_only,
    )
    # one shared parser pool: the /metrics pool telemetry must reflect the
    # pool the engine's ingest actually borrows from
    pool = ParserPool()
    parser_backend()  # resolve (and log) the parser chain's rung at start
    engine_kwargs = dict(
        segment_duration_ms=segment_ms,
        config=config.metric_engine.storage.time_merge_storage,
        sst_executor=sst_executor,
        manifest_executor=manifest_executor,
        ingest_buffer_rows=config.metric_engine.ingest_buffer_rows,
        # overlapped ingest->flush pipeline sizing ([metric_engine.ingest])
        flush_workers=config.metric_engine.ingest.flush_workers,
        flush_queue_max=config.metric_engine.ingest.flush_queue_max,
        flush_stall_deadline_s=config.metric_engine.ingest.stall_deadline.seconds,
        # dirty-traffic knobs: retention horizon ([metric_engine.retention])
        # and the series-cardinality limit ([metric_engine.limits])
        retention_period_ms=config.metric_engine.retention.period_ms(),
        max_series=config.metric_engine.limits.max_series,
        # serving tier ([metric_engine.serving]): rollups + result cache,
        # bit-exact vs HORAEDB_SERVING=off
        serving=config.metric_engine.serving,
        parser_pool=pool,
    )
    if config.metric_engine.node_id:
        # multi-process shared store: claim per-region write ownership
        engine_kwargs["fence_node_id"] = config.metric_engine.node_id
    num_regions = config.metric_engine.num_regions
    granularity = config.metric_engine.region_granularity
    cluster_state: "ClusterState | None" = None
    if cluster_cfg.enabled:
        from horaedb_tpu.cluster import assignment as asg_mod
        from horaedb_tpu.cluster.replica import ReplicaEngine
        from horaedb_tpu.cluster.router import ClusterRouter

        node_id = config.metric_engine.node_id
        router = ClusterRouter(cluster_cfg, node_id)
        cluster_root = "metrics/cluster"
        replica_kwargs = {
            k: v for k, v in engine_kwargs.items()
            if k not in ("fence_node_id",)
        }
        if replica_role:
            replica = await ReplicaEngine.open(
                "metrics", store,
                num_regions=num_regions, granularity=granularity,
                watch_interval_s=cluster_cfg.watch_interval.seconds,
                watch_backoff_cap_s=cluster_cfg.watch_backoff_cap.seconds,
                engine_kwargs=replica_kwargs,
                # a racing boot waits for the writer's store layout
                open_retries=40, open_retry_delay_s=0.5,
            )
            engine = replica
            try:
                router.set_assignment(
                    await asg_mod.load_assignment(store, cluster_root)
                )
            except Exception:  # noqa: BLE001 — routing converges on probes
                logger.warning("assignment map unreadable at replica boot")
            cluster_state = ClusterState(
                cluster_cfg, node_id, router, replica=replica,
                store=store, cluster_root=cluster_root,
                engine_kwargs=replica_kwargs,
            )
        else:
            # writer: claim regions per the assignment map (never steals;
            # takeover is the explicit /api/v1/cluster/takeover op).
            # Unowned regions claim to SELF — first writer to boot owns
            # them; a later writer finds them taken and serves as a
            # standby. Rendezvous-splitting regions across several LIVE
            # writers is a deliberate operator action (the assignment
            # API's writer_nodes bootstrap / per-region takeover), never
            # an inference from the peer table: a configured-but-down
            # peer must not be handed regions nobody can write.
            region_ids = list(range(num_regions))
            asg = await asg_mod.claim_regions(
                store, cluster_root, node_id, region_ids, [node_id],
            )
            owned = set(asg.regions_of(node_id))
            router.set_assignment(asg)
            standby = False
            replica = None
            if num_regions > 1:
                from horaedb_tpu.engine.region import RegionedEngine

                engine = await RegionedEngine.open(
                    "metrics", store, num_regions,
                    granularity=granularity,
                    writable_regions=(None if owned == set(region_ids)
                                      else owned),
                    **engine_kwargs,
                )
            elif 0 in owned:
                engine = await MetricEngine.open(
                    "metrics", store, **engine_kwargs,
                )
            else:
                # standby writer: another writer owns the region — serve
                # reads as a replica until takeover promotes this node
                standby = True
                replica = await ReplicaEngine.open(
                    "metrics", store,
                    num_regions=num_regions, granularity=granularity,
                    watch_interval_s=cluster_cfg.watch_interval.seconds,
                    watch_backoff_cap_s=cluster_cfg.watch_backoff_cap.seconds,
                    engine_kwargs=replica_kwargs,
                    open_retries=40, open_retry_delay_s=0.5,
                )
                engine = replica
            cluster_state = ClusterState(
                cluster_cfg, node_id, router, replica=replica,
                standby=standby,
                partial=(num_regions > 1 and owned != set(region_ids)),
                store=store, cluster_root=cluster_root,
                engine_kwargs=replica_kwargs,
            )
    elif num_regions > 1:
        from horaedb_tpu.engine.region import RegionedEngine

        engine = await RegionedEngine.open(
            "metrics", store, num_regions,
            granularity=granularity,
            **engine_kwargs,
        )
    else:
        engine = await MetricEngine.open("metrics", store, **engine_kwargs)
    engine_read_only = bool(getattr(engine, "read_only", False))
    slow = None
    if config.slowlog.capacity > 0:
        import os as _os

        # the spool is per-box diagnostic state, like the agg-calib cache:
        # it lives under the LOCAL data dir even for S3 deployments
        slow = SlowLog(
            _os.path.join(store_cfg.data_dir, "slowlog"),
            capacity=config.slowlog.capacity,
            min_duration_s=config.slowlog.min_duration.seconds,
        )
    qcfg = config.metric_engine.query
    rcfg = config.metric_engine.rules
    tcfg = config.metric_engine.telemetry
    # rule evaluations run as a distinct weighted-fair tenant; its LOW
    # default share means a rule storm queues behind dashboards, never
    # ahead of them (an explicit tenant_weights entry wins). The
    # self-scrape `_system` tenant gets the same treatment.
    weights = dict(qcfg.tenant_weights)
    weights.setdefault(rcfg.tenant, rcfg.tenant_weight)
    weights.setdefault(tcfg.tenant, tcfg.tenant_weight)
    adm = AdmissionController(
        max_concurrent=qcfg.max_concurrent,
        max_per_tenant=qcfg.max_per_tenant,
        queue_max=qcfg.queue_max,
        queue_deadline_s=qcfg.queue_deadline.seconds,
        max_cost_s=qcfg.max_cost_s,
        weights=weights,
    )
    # query batcher ([metric_engine.query.batching], server/batching.py):
    # process-global like the serving caches — the planner rides the
    # engine's cold downsample path, so configuring it here covers every
    # read surface (native JSON, PromQL, rules, regioned fan-out)
    from horaedb_tpu.server import batching as batching_mod

    batching_mod.GLOBAL_BATCHER.configure(qcfg.batching)
    from horaedb_tpu import telemetry as telemetry_mod

    rules_engine = None
    if rcfg.enabled and engine_read_only:
        # rules materialize output through the ingest path and checkpoint
        # fenced state — writer-only work; replicas serve the rule OUTPUT
        # series like any other data with bounded staleness
        logger.info("rule engine disabled on a read-only replica")
    elif rcfg.enabled:
        from horaedb_tpu.rules import rule_from_dict
        from horaedb_tpu.rules.engine import RuleEngine

        rules_engine = await RuleEngine.open(
            engine, store, root="metrics/rules",
            # single-writer discipline rides the engine's fence when one
            # is configured (regioned deployments fence per region root;
            # the rule store then relies on deployment discipline)
            fence=getattr(engine, "_fence", None),
            admission=adm, tenant=rcfg.tenant,
        )
        # config-declared rules: asserted idempotently (an unchanged
        # definition keeps its watermark / alert states across restarts).
        # SLO burn-rate templates (telemetry/slo.py) expand into the same
        # idempotent path — an unchanged [[metric_engine.slo]] block
        # keeps its rules' watermarks and alert states.
        declared = (
            list(rcfg.recording) + list(rcfg.alerting)
            + telemetry_mod.expand_slos(config.metric_engine.slo)
        )
        for entry in declared:
            await rules_engine.ensure_registered(
                rule_from_dict(entry, now_ms=now_ms())
            )
    collector = None
    if telemetry_mod.telemetry_enabled(tcfg.enabled) and engine_read_only:
        logger.info("self-telemetry collector disabled on a read-only "
                    "replica (its writes belong to the writer)")
    elif telemetry_mod.telemetry_enabled(tcfg.enabled):
        collector = telemetry_mod.SelfScrapeCollector(
            engine,
            tenant=tcfg.tenant,
            max_series=tcfg.max_series,
            exclude=tuple(tcfg.exclude),
            retention_ms=tcfg.retention_ms(),
            instance=tcfg.instance,
            # fleet federation: pull peers' snapshots through the cluster
            # router's traced client funnel (no cluster layer, no fleet)
            federation=tcfg.federation,
            router=(cluster_state.router
                    if cluster_state is not None else None),
        )
        if tcfg.federation.enabled and cluster_state is None:
            logger.warning(
                "[metric_engine.telemetry.federation] enabled without the "
                "cluster layer; there are no peers to scrape"
            )
    state = ServerState(config, storage, engine, parser_pool=pool,
                        slowlog=slow, admission_controller=adm,
                        rules=rules_engine, telemetry=collector,
                        cluster=cluster_state)
    if config.test.enable_write:
        state.write_enabled.set()
    state.write_workers.append(
        asyncio.create_task(loop_lag_heartbeat(), name="loop-lag")
    )
    for i in range(config.test.write_worker_num):
        state.write_workers.append(
            asyncio.create_task(bench_write_worker(state, i), name=f"bench-write-{i}")
        )
    if config.metric_engine.ingest_buffer_rows > 0 and not engine_read_only:
        # periodic flush bounds the buffered-ingest data-loss window
        interval = config.metric_engine.ingest_flush_interval.seconds

        async def flush_loop():
            while True:
                await asyncio.sleep(interval)
                try:
                    with tracing.trace("periodic_ingest_flush"):
                        await engine.flush()
                except Exception:  # noqa: BLE001 — keep flushing; writes retry
                    logger.exception("periodic ingest flush failed")

        state.write_workers.append(
            asyncio.create_task(flush_loop(), name="ingest-flush")
        )
    if rules_engine is not None:
        # the evaluator tick loop: dirty-set driven, so a quiet tick
        # costs ~nothing; failures log and retry next interval (the
        # dirty sets only clear on success, so nothing is lost)
        rules_interval = rcfg.eval_interval.seconds

        async def rules_loop():
            while True:
                await asyncio.sleep(rules_interval)
                try:
                    await rules_engine.tick()
                except Exception:  # noqa: BLE001 — keep ticking
                    logger.exception("rule evaluator tick failed")

        state.write_workers.append(
            asyncio.create_task(rules_loop(), name="rule-evaluator")
        )
    if collector is not None:
        # the self-scrape loop: the registry becomes first-class series
        # on this interval; tick failures log and retry (the collector
        # is stateless between ticks beyond its series budget)
        scrape_interval = tcfg.scrape_interval.seconds

        async def telemetry_loop():
            while True:
                await asyncio.sleep(scrape_interval)
                try:
                    await collector.tick()
                except Exception:  # noqa: BLE001 — keep scraping
                    logger.exception("self-scrape tick failed")

        state.write_workers.append(
            asyncio.create_task(telemetry_loop(), name="telemetry-scrape")
        )

    if cluster_state is not None:
        # background cluster fabric: the replica watch/swap loop and the
        # peer health probes (both tasks die with their owners' close)
        if cluster_state.replica is not None:
            cluster_state.replica.start_watch()
        cluster_state.router.start_probes()

    tracing.configure(
        sample=config.tracing.sample,
        slow_s=config.tracing.slow_threshold.seconds,
        ring=config.tracing.ring_capacity,
    )
    app = web.Application(
        client_max_size=64 * 1024 * 1024,
        middlewares=[observability_middleware, cluster_middleware],
    )
    app[STATE_KEY] = state
    app.add_routes(
        [
            web.get("/", handle_root),
            web.get("/toggle", handle_toggle),
            web.get("/compact", handle_compact),
            web.post("/admin/split_region", handle_split_region),
            web.get("/metrics", handle_metrics),
            web.post("/api/v1/write", handle_remote_write),
            web.post("/api/v1/query", handle_query),
            web.get("/api/v1/query", handle_query),
            web.get("/api/v1/query_range", handle_query_range),
            web.post("/api/v1/query_range", handle_query_range),
            web.get("/api/v1/query_exemplars", handle_query_exemplars),
            web.post("/api/v1/query_exemplars", handle_query_exemplars),
            web.get("/api/v1/labels", handle_labels),
            web.get("/api/v1/label/{name}/values", handle_label_values),
            web.get("/api/v1/metrics", handle_metrics_list),
            web.get("/api/v1/series", handle_series),
            web.get("/api/v1/metadata", handle_metadata),
            web.get("/api/v1/rules", handle_rules_get),
            web.post("/api/v1/rules", handle_rules_post),
            web.delete("/api/v1/rules/{name}", handle_rules_delete),
            web.get("/api/v1/alerts", handle_alerts),
            web.post("/api/v1/rules/tick", handle_rules_tick),
            web.get("/api/v1/usage", handle_usage),
            web.get("/api/v1/cluster/status", handle_cluster_status),
            web.post("/api/v1/cluster/refresh", handle_cluster_refresh),
            web.post("/api/v1/cluster/takeover", handle_cluster_takeover),
            web.post("/api/v1/telemetry/scrape", handle_telemetry_scrape),
            web.get("/api/v1/telemetry/snapshot", handle_telemetry_snapshot),
            web.post("/api/v1/admin/tsdb/delete_series", handle_delete_series),
            web.get("/api/v1/status/buildinfo", handle_buildinfo),
            web.get("/debug/traces", handle_debug_traces),
            web.get("/debug/traces/{id}", handle_debug_trace),
            web.get("/debug/kernels", handle_debug_kernels),
            web.get("/debug/slowlog", handle_debug_slowlog),
            web.get("/debug/memory", handle_debug_memory),
            web.post("/debug/profile/start", handle_profile_start),
            web.post("/debug/profile/stop", handle_profile_stop),
            web.get("/debug/cluster", handle_debug_cluster),
        ]
    )

    async def on_cleanup(app):
        for t in state.write_workers:
            t.cancel()
        # wait for in-flight writes before closing storage under them
        await asyncio.gather(*state.write_workers, return_exceptions=True)
        if state.rules is not None:
            await state.rules.close()
        if state.cluster is not None:
            await state.cluster.router.close()
        await state.storage.close()
        await state.engine.close()
        closer = getattr(store, "close", None)
        if closer is not None:  # S3LikeStore owns an HTTP session
            await closer()

    app.on_cleanup.append(on_cleanup)
    return app


def main() -> None:
    init_logging()
    # Escape hatch for CPU-only deployments and CI: pick the jax platform
    # BEFORE the backend initializes (JAX_PLATFORMS does the same).
    import os

    platform = os.environ.get("HORAEDB_JAX_PLATFORM")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    cache_dir = compile_cache.enable()
    ap = argparse.ArgumentParser(description="horaedb-tpu server")
    ap.add_argument("--config", help="toml config path")
    args = ap.parse_args()
    config = Config.from_file(args.config) if args.config else Config()
    logger.info("starting horaedb-tpu server on 127.0.0.1:%d", config.port)
    logger.info("compile cache: %s", cache_dir)

    async def run():
        # the loop's thread under a name of its own on the profiler's
        # timeline (every other Python thread that never names itself is
        # `python3` there, and lines of one name are not told apart)
        scanstats.name_thread("horaedb-loop")
        from concurrent.futures import ThreadPoolExecutor

        # asyncio's own default pool, its threads named as they start
        asyncio.get_running_loop().set_default_executor(ThreadPoolExecutor(
            thread_name_prefix="asyncio", initializer=scanstats.name_thread))
        app = await build_app(config)
        # handler_cancellation: a client disconnect raises CancelledError
        # into the handler, so an abandoned query frees its admission
        # slot and stops scanning instead of finishing work nobody reads
        # (counted in horaedb_query_shed_total{reason="client_disconnect"})
        runner = web.AppRunner(app, handler_cancellation=True)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", config.port)
        await site.start()
        await asyncio.Event().wait()  # serve forever

    asyncio.run(run())


if __name__ == "__main__":
    main()
