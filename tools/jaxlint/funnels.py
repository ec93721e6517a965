"""Per-file funnel-boundary rules J008-J017: every subsystem with ONE
sanctioned choke point (flush executor, ResilientStore, visibility
helper, admission scheduler, decode funnel, serving tier, invalidation
subscribers, metering, query batcher, cluster meta plane) gets a rule
that flags the second path. Moved verbatim from the single-file
linter; docs/static-analysis.md has per-rule rationale."""

from __future__ import annotations

import ast
import re

from tools.jaxlint.base import Finding, arg_identifiers, dotted

# J008: the append hot path (ingest decode + the engine write layers)
# must not reach blocking flush work directly — parquet encodes and
# object-store puts belong behind the flush executor
# (engine/flush_executor.py) and the storage layer it drives.
J008_MODULES = (
    "horaedb_tpu/ingest/",
    "horaedb_tpu/engine/",
)
J008_EXEMPT = ("horaedb_tpu/engine/flush_executor.py",)

# J009: the resilience boundary (objstore/resilient.py). Concrete store
# constructors outside objstore/ must be immediate arguments of a
# ResilientStore(...) call. tests/ and benchmarks/tools harnesses are out
# of scope — they deliberately build raw stores to inject faults.
J009_MODULES = ("horaedb_tpu/",)
J009_EXEMPT = ("horaedb_tpu/objstore/",)

# J011: the query-admission boundary (server/admission.py). Server-layer
# code must reach the engine's query surface only through the admission
# helpers; the owner-name heuristic (`engine`/`_engine` receiver) matches
# this codebase's handler idiom (`state.engine.query(...)`) without
# flagging unrelated `.query()` methods on other objects.
J011_MODULES = ("horaedb_tpu/server/",)
J011_EXEMPT = ("horaedb_tpu/server/admission.py",)
QUERY_ENTRY_ATTRS = {"query", "query_exemplars"}
ENGINE_RECEIVERS = {"engine", "_engine"}

# J010: tombstone/retention filtering is ONE shared helper
# (storage/visibility.py, funneled through ParquetReader.read_sst); any
# other engine code touching the visibility state's row-filtering fields
# is an ad-hoc reader filter waiting to diverge. The manifest package is
# the record STORE (load/persist/GC) and is exempt.
J010_MODULES = ("horaedb_tpu/",)
J010_EXEMPT = (
    "horaedb_tpu/storage/visibility.py",
    "horaedb_tpu/storage/manifest/",
)
VISIBILITY_FIELDS = {"tombstones", "retention_floor_ms"}

# J012: the encoded-lane decode funnel (storage/encoding.py host codecs,
# ops/decode.py device kernels) and the one reader that drives it
# (storage/read.py's encoded path). Everything else in engine code must
# not decode encoded buffers by hand.
J012_MODULES = ("horaedb_tpu/",)
J012_EXEMPT = (
    "horaedb_tpu/storage/encoding.py",
    "horaedb_tpu/ops/decode.py",
    "horaedb_tpu/storage/read.py",
)
# the funnel's own decode entry points (dotted-name tail match)
DECODE_FUNNEL_FUNCS = {
    "decode_lane", "decode_blob", "decode_page_device", "unpack_bits",
    "unzigzag",
}
# decode-shaped primitives that, applied to an encoded buffer, are an
# ad-hoc decode path (tail match; `.accumulate` covers ufunc scans like
# np.bitwise_xor.accumulate)
DECODE_SHAPED_TAILS = {"cumsum", "unpackbits", "associative_scan", "accumulate"}
_ENC_NAME_RE = re.compile(r"(^|_)enc(oded)?(_|$)|encoded|^payload$")

# J013: the serving-tier funnel (horaedb_tpu/serving + storage/rollup.py).
# READ side: cache lookups / rollup planning belong at the planner choke
# point (engine/data.py) and in the tier's own modules. WRITE side: cache
# mutation belongs to the invalidation funnel — the storage write commit,
# the compaction commit, the tombstone path (all in storage/storage.py /
# compaction/executor.py) and the manifest's record store. The reader
# (storage/read.py) is below the tier and calls neither side.
J013_MODULES = ("horaedb_tpu/",)
J013_READ_EXEMPT = (
    "horaedb_tpu/serving/",
    "horaedb_tpu/engine/data.py",
    "horaedb_tpu/storage/rollup.py",
)
J013_WRITE_EXEMPT = (
    "horaedb_tpu/serving/",
    "horaedb_tpu/storage/storage.py",
    "horaedb_tpu/storage/compaction/executor.py",
    "horaedb_tpu/storage/manifest/",
    "horaedb_tpu/storage/rollup.py",
    # the replica's snapshot swap IS its flush/delete commit — the swap
    # routes through serving_invalidate with the mutation's time range
    "horaedb_tpu/cluster/replica.py",
)
SERVING_READ_FUNCS = {
    "serving_get", "serving_single_flight", "plan_rollups", "read_rollup",
}
SERVING_WRITE_FUNCS = {
    "serving_put", "serving_invalidate", "evict_rollup",
}

# J014: the invalidation funnel's CONSUMER set. serving_subscribe /
# serving_unsubscribe (serving/cache.py) hand out a synchronous callback
# inside every mutation commit; the audited consumers are the cache
# itself (serving/) and the rule evaluator (rules/ — the streaming rule
# engine's dirty sets). Anything else subscribing is a second standing-
# query engine growing outside the one whose exactness is tested.
J014_MODULES = ("horaedb_tpu/",)
J014_EXEMPT = (
    "horaedb_tpu/serving/",
    "horaedb_tpu/rules/",
)
FUNNEL_SUBSCRIBE_FUNCS = {"serving_subscribe", "serving_unsubscribe"}

# J015: the per-tenant usage funnel (telemetry/metering.py). Tenant
# accounting registered anywhere else forks the ledger.
J015_MODULES = ("horaedb_tpu/",)
J015_EXEMPT = ("horaedb_tpu/telemetry/",)
METRIC_REGISTER_VERBS = {"counter", "gauge", "histogram"}
TENANT_FAMILY_PREFIX = "horaedb_tenant_"

# J016: the stacked-execution funnel (server/batching.py pads/stacks the
# coalesced query lanes; ops/aggregate.py hosts the sanctioned stacked
# kernels). Stack/pad-shaped calls over batched-query-lane names anywhere
# else are a second stacking path (same heuristic class as J012's
# encoded-buffer prong: primitive tail + argument naming idiom).
J016_MODULES = ("horaedb_tpu/",)
J016_EXEMPT = (
    "horaedb_tpu/server/batching.py",
    "horaedb_tpu/ops/aggregate.py",
)
STACK_SHAPED_TAILS = {
    "stack", "vstack", "hstack", "dstack", "column_stack", "pad",
}
_BATCH_LANE_RE = re.compile(
    r"(^|_)(stacked?|padded|batch(ed)?|grids?|lanes?)(_|$)"
)

# J017: the cluster funnel (horaedb_tpu/cluster). Prong 1: manifest
# snapshot views belong to the manifest package + the replica funnel.
# Prong 2: assignment records mutate only through assignment.py's
# fenced CAS (put_if_absent-arbitrated versions).
J017_MODULES = ("horaedb_tpu/",)
J017_VIEW_EXEMPT = (
    "horaedb_tpu/storage/manifest/",
    "horaedb_tpu/cluster/replica.py",
)
J017_ASSIGN_EXEMPT = ("horaedb_tpu/cluster/assignment.py",)
MANIFEST_VIEW_FUNCS = {"read_snapshot", "read_folded_view"}
STORE_MUTATION_TAILS = {"put", "put_if_absent", "put_stream", "delete"}
_ASSIGNMENT_NAME_RE = re.compile(
    r"cluster/assignment|assignment_path|assignment_dir|ASSIGNMENT_DIR"
)

# J022: the traced cluster-client funnel (cluster/router.traced_request).
# Every outbound cluster-tier HTTP hop — write forwards, split-write
# fan-out, read offload, hedged failover, status probes, federation
# scrapes — goes through the ONE funnel that injects the cross-node
# trace headers, grafts the peer's shipped-back span subtree, and feeds
# peer-health/probe metrics. A second client path ships invisible hops.
J022_MODULES = ("horaedb_tpu/cluster/", "horaedb_tpu/server/")
J022_EXEMPT = ("horaedb_tpu/cluster/router.py",)
HTTP_VERB_TAILS = {
    "get", "post", "put", "delete", "head", "options", "patch",
    "request", "ws_connect",
}
SESSION_RECEIVERS = {"session", "_session", "client_session",
                     "http_session"}

# J023: the partial-grid funnel (cluster/partial.py). The scatter-gather
# wire codec and the coordinator merge are the load-bearing half of the
# distributed bit-exactness promise: ONE encode/decode pair so every
# fragment ships the same dtype-preserving LE layout, ONE merge with the
# fixed canonical-region fold order. A second encoder or an ad-hoc
# in-place fold (np.add.at / np.minimum.at / np.maximum.at on grids) in
# server/cluster code silently reorders float addition and the
# distributed answer stops matching single-node bit-for-bit.
J023_MODULES = ("horaedb_tpu/cluster/", "horaedb_tpu/server/")
J023_EXEMPT = ("horaedb_tpu/cluster/partial.py",)
PARTIAL_GRID_FUNNEL_DEFS = {
    "encode_partials", "decode_partials", "merge_partials", "merge_grids",
}
GRID_FOLD_UFUNC_HEADS = {"add", "minimum", "maximum"}

# J024: the memtrace funnel (common/memtrace.py). The data-plane modules
# account every buffer hand-off — copies vs views per stage — through
# the tracked_* helpers; a raw `pa.concat_tables` / `.combine_chunks()`
# / `np.concatenate` / `np.ascontiguousarray` / lane `.copy()` in scope
# is an invisible copy the EXPLAIN memory verdict, the copy-tax table,
# and the mem-smoke regression gate all silently miss. jnp.concatenate
# (traced device math) is NOT a host copy and stays out of scope.
J024_MODULES = (
    "horaedb_tpu/storage/read.py",
    "horaedb_tpu/storage/rollup.py",
    "horaedb_tpu/serving/",
    "horaedb_tpu/engine/data.py",
    "horaedb_tpu/cluster/partial.py",
    "horaedb_tpu/ingest/",
    "horaedb_tpu/parallel/mesh.py",
)
J024_EXEMPT = ("horaedb_tpu/common/memtrace.py",)
MEMTRACE_CONCAT_TAILS = {"concat_tables", "combine_chunks"}
MEMTRACE_NUMPY_CALLS = {"np.concatenate", "np.ascontiguousarray",
                        "numpy.concatenate", "numpy.ascontiguousarray"}
# zero-arg `.copy()` receivers that look like data-plane lanes; scoped
# to lane-ish names so dict/config `.copy()` bookkeeping stays quiet
_LANE_NAME_RE = re.compile(
    r"(^|_)(ts|tsid|sid|val(ue)?s?|mask|lane|lanes|grid|grids|arr|"
    r"cols?|table|tables|buf)(_|$|\d*$)"
)

# J025: the column-block contract (common/colblock.py). The zero-copy
# spine hands column blocks BY REFERENCE across the data plane; a fresh
# numpy array materialized from a block's lanes (`np.array`/`np.asarray`
# /`np.frombuffer`/`np.copy` over a `.lane(...)` accessor or a
# block-named buffer) outside colblock.py's sanctioned APIs is a
# re-materialization the lineage ledger files nowhere — the copy-tax
# verdict reads "view"/"reuse" while real bytes moved. colblock.as_lane
# / ColBlock.copy_lane / the memtrace tracked_* helpers are the
# sanctioned ways to coerce or duplicate a lane.
J025_MODULES = J024_MODULES + (
    "horaedb_tpu/storage/storage.py",
    "horaedb_tpu/parallel/scan.py",
)
J025_EXEMPT = (
    "horaedb_tpu/common/colblock.py",
    "horaedb_tpu/common/memtrace.py",
)
BLOCK_MATERIALIZE_CALLS = {
    "np.array", "np.asarray", "np.frombuffer", "np.copy",
    "numpy.array", "numpy.asarray", "numpy.frombuffer", "numpy.copy",
}
BLOCK_LANE_ATTRS = {"lane", "lanes", "writable_lane"}
_BLOCK_NAME_RE = re.compile(r"(^|_)(col_?block|blocks?)(_|$|\d*$)")
# colblock's own constructors/coercers + the memtrace helpers sanction
# every call nested inside them (the J024 wrapped-subtree technique)
COLBLOCK_SANCTIONED_TAILS = {
    "ColBlock", "GrowableColBlock", "ArrowLanes", "aligned_empty",
    "as_lane", "adopt_spare", "wrap", "copy_lane", "to_device",
    "to_arrow_batch",
}

RAW_STORE_CTORS = {"MemStore", "LocalStore", "S3LikeStore"}
STORE_BOUNDARY_WRAPPERS = {"ResilientStore", "ChaosStore"}
PARQUET_ENCODE_CALLS = {
    "pq.ParquetWriter", "pq.write_table", "pq.write_to_dataset",
    "pyarrow.parquet.ParquetWriter", "pyarrow.parquet.write_table",
    "parquet.ParquetWriter", "parquet.write_table",
}
OBJSTORE_PUT_VERBS = {"put", "put_stream", "put_if_absent"}


def check_append_hot_path(tree: ast.Module, findings: list[Finding]) -> None:
    """J008, append-hot modules only: direct parquet-encode calls and
    direct object-store put verbs. The storage layer (`storage.write`)
    is the sanctioned durability path — it runs on the flush executor's
    workers with encode offloaded to the SST pool; a call site here
    would drag that work back onto the append path. Control-plane writes
    (region descriptors, index sidecars) carry reasoned suppressions."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fd = dotted(node.func)
        if fd in PARQUET_ENCODE_CALLS:
            findings.append(Finding(
                node.lineno, "J008",
                f"parquet encode `{fd}(...)` reachable from the append hot "
                "path — flush encode belongs behind the flush executor "
                "(engine/flush_executor.py) via the storage layer",
            ))
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in OBJSTORE_PUT_VERBS
        ):
            findings.append(Finding(
                node.lineno, "J008",
                f"direct object-store `.{node.func.attr}()` reachable from "
                "the append hot path — route durability through the "
                "storage layer / flush executor, or suppress with the "
                "control-plane justification",
            ))


def check_store_boundary(tree: ast.Module, findings: list[Finding]) -> None:
    """J009: concrete ObjectStore constructors outside objstore/ that are
    not immediate arguments of a ResilientStore(...) (or ChaosStore(...)
    — the chaos harness wraps before resilience does). One pass collects
    the wrapped argument nodes; a second flags naked constructions."""
    wrapped: set[ast.AST] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fd = dotted(node.func)
        if fd and fd.rsplit(".", 1)[-1] in STORE_BOUNDARY_WRAPPERS:
            wrapped.update(node.args)
            wrapped.update(kw.value for kw in node.keywords)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or node in wrapped:
            continue
        fd = dotted(node.func)
        if fd and fd.rsplit(".", 1)[-1] in RAW_STORE_CTORS:
            findings.append(Finding(
                node.lineno, "J009",
                f"concrete object store `{fd}(...)` constructed outside "
                "objstore/ without the ResilientStore boundary — the "
                "receiver gets single-naked-attempt semantics (no retry/"
                "backoff, deadlines, breaker, or horaedb_objstore_* "
                "attribution); wrap it in objstore/resilient.ResilientStore "
                "at the construction site or suppress with the reason",
            ))


def check_admission_boundary(tree: ast.Module, findings: list[Finding]) -> None:
    """J011: `<...>.engine.query(...)` / `.query_exemplars(...)` in server
    code outside server/admission.py. The receiver must be named
    `engine`/`_engine` (directly or as the last attribute before the
    verb) — the handler idiom this tree uses — so `registry.query(...)`
    on unrelated objects never trips the rule."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr in QUERY_ENTRY_ATTRS):
            continue
        owner = f.value
        owner_name = None
        if isinstance(owner, ast.Attribute):
            owner_name = owner.attr
        elif isinstance(owner, ast.Name):
            owner_name = owner.id
        if owner_name in ENGINE_RECEIVERS:
            findings.append(Finding(
                node.lineno, "J011",
                f"direct engine `.{f.attr}(...)` in server code bypasses "
                "the admission scheduler (no concurrency cap, queue/stall "
                "backpressure, end-to-end deadline, tenant fairness, or "
                "shed metrics); route through server/admission.run_query"
                "/run_query_exemplars, or suppress with the reason",
            ))


def check_decode_funnel(tree: ast.Module, findings: list[Finding]) -> None:
    """J012, two prongs: (1) calls of the funnel's decode primitives
    outside the funnel; (2) decode-shaped ops (cumsum/unpackbits/
    associative_scan/ufunc .accumulate) whose arguments name an encoded
    buffer (`*_enc`, `enc_*`, `*encoded*`, `payload`) — the naming idiom
    of every encoded-buffer variable in this tree, same heuristic class
    as J011's `engine` receiver match."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fd = dotted(node.func)
        tail = fd.rsplit(".", 1)[-1] if fd else None
        if tail in DECODE_FUNNEL_FUNCS:
            findings.append(Finding(
                node.lineno, "J012",
                f"`{tail}(...)` called outside the sanctioned decode "
                "funnel (storage/encoding.py / ops/decode.py / the "
                "encoded reader in storage/read.py) — ad-hoc decode paths "
                "diverge from the funnel's bit-exactness contract and "
                "skip the calibrated host/device dispatcher; route "
                "through the reader, or suppress with the reason",
            ))
        elif tail in DECODE_SHAPED_TAILS and any(
            _ENC_NAME_RE.search(name) for name in arg_identifiers(node)
        ):
            findings.append(Finding(
                node.lineno, "J012",
                f"decode-shaped `{tail}(...)` over an encoded buffer "
                "outside the sanctioned funnel — hand-rolled prefix-sum/"
                "unpack of encoded lanes belongs in storage/encoding.py "
                "(host) or ops/decode.py (device kernels); suppress with "
                "the reason for harnesses measuring the funnel itself",
            ))


def check_serving_funnel(
    tree: ast.Module, findings: list[Finding],
    check_reads: bool, check_writes: bool,
) -> None:
    """J013: serving-tier read primitives outside the planner choke point,
    or mutation primitives outside the invalidation funnel (dotted-name
    tail match, the J011/J012 heuristic class)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fd = dotted(node.func)
        tail = fd.rsplit(".", 1)[-1] if fd else None
        if check_reads and tail in SERVING_READ_FUNCS:
            findings.append(Finding(
                node.lineno, "J013",
                f"serving-tier read `{tail}(...)` outside the planner "
                "choke point (engine/data.py's query methods) — a second "
                "lookup path can serve results the invalidation funnel "
                "already declared stale; route through the choke point, "
                "or suppress with the reason",
            ))
        elif check_writes and tail in SERVING_WRITE_FUNCS:
            findings.append(Finding(
                node.lineno, "J013",
                f"serving-tier mutation `{tail}(...)` outside the "
                "invalidation funnel (storage write commit / compaction "
                "commit / tombstone path / reader eviction hooks) — cache "
                "state must only change with the commit that justifies "
                "it; route through the funnel, or suppress with the "
                "reason",
            ))


def check_stacking_funnel(tree: ast.Module,
                          findings: list[Finding]) -> None:
    """J016: stack/pad-shaped primitives over query result lanes outside
    the batcher and the sanctioned stacked kernels. A call fires when its
    dotted tail is a stacking/padding primitive AND any argument
    identifier names a batched query lane (`stacked_*`, `padded_*`,
    `batch_*`, `*_grids`, `*_lanes` — the naming idiom of every stacked
    buffer in this tree, the J011/J012 heuristic class)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fd = dotted(node.func)
        tail = fd.rsplit(".", 1)[-1] if fd else None
        if tail in STACK_SHAPED_TAILS and any(
            _BATCH_LANE_RE.search(name) for name in arg_identifiers(node)
        ):
            findings.append(Finding(
                node.lineno, "J016",
                f"stacking/padding `{tail}(...)` over a query result lane "
                "outside the query batcher (server/batching.py) / the "
                "sanctioned stacked kernels (ops/aggregate.py) — a second "
                "stacked-execution path dodges the batcher's power-of-two "
                "shape classes (retraces escape the shared compiled "
                "shapes), its pad-waste accounting, and the bit-exact "
                "demux contract; route through the batcher, or suppress "
                "with the reason for harnesses measuring the stacked "
                "lane itself",
            ))


def check_cluster_funnel(
    tree: ast.Module, findings: list[Finding],
    check_views: bool, check_assign: bool,
) -> None:
    """J017: manifest-view consumption outside the replica funnel, and
    assignment-record mutation outside the fenced CAS API (dotted-tail +
    argument-naming heuristics, the J012/J016 class)."""
    def _arg_names_and_strings(node: ast.Call):
        for name in arg_identifiers(node):
            yield name
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                    yield sub.value
                elif isinstance(sub, ast.JoinedStr):
                    for v in sub.values:
                        if isinstance(v, ast.Constant) and isinstance(v.value, str):
                            yield v.value

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fd = dotted(node.func)
        tail = fd.rsplit(".", 1)[-1] if fd else None
        if check_views and tail in MANIFEST_VIEW_FUNCS:
            findings.append(Finding(
                node.lineno, "J017",
                f"manifest view `{tail}(...)` consumed outside the "
                "manifest package / the cluster replica funnel "
                "(cluster/replica.py) — a second snapshot consumer is a "
                "second replication path with no staleness token, swap "
                "invalidation, or watch backoff; open the storage "
                "read-only (read_only=True) or go through ReplicaEngine, "
                "or suppress with the reason",
            ))
        elif check_assign and tail in STORE_MUTATION_TAILS and any(
            _ASSIGNMENT_NAME_RE.search(s)
            for s in _arg_names_and_strings(node)
        ):
            findings.append(Finding(
                node.lineno, "J017",
                f"assignment-record mutation `{tail}(...)` outside the "
                "fenced CAS API (cluster/assignment.py) — an unversioned "
                "write forks the meta plane and can reroute writes to a "
                "deposed owner; use propose_assignment/claim_regions/"
                "takeover_region, or suppress with the reason",
            ))


def check_funnel_subscribers(tree: ast.Module,
                             findings: list[Finding]) -> None:
    """J014: the invalidation funnel's consumer set is pinned — only the
    cache (serving/) and the rule evaluator (rules/) may subscribe. A
    third subscriber is a standing-query engine growing outside the one
    whose dirty-set exactness is chaos-tested."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fd = dotted(node.func)
        tail = fd.rsplit(".", 1)[-1] if fd else None
        if tail in FUNNEL_SUBSCRIBE_FUNCS:
            findings.append(Finding(
                node.lineno, "J014",
                f"invalidation-funnel subscription `{tail}(...)` outside "
                "the audited consumer set (serving/cache.py internals and "
                "the rule evaluator, horaedb_tpu/rules) — mutation-commit "
                "callbacks are a standing-query surface; consume the rule "
                "engine's dirty sets instead, or suppress with the reason",
            ))


def check_metering_funnel(tree: ast.Module, findings: list[Finding]) -> None:
    """J015: per-tenant accounting goes through telemetry/metering.py —
    three prongs: (1) a metric family registered under the reserved
    `horaedb_tenant_*` namespace; (2) a family registered with a
    `tenant` labelname; (3) a legacy string-API name literal embedding a
    `tenant="..."` label."""
    def _str_const(node):
        return node.value if (isinstance(node, ast.Constant)
                              and isinstance(node.value, str)) else None

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not isinstance(f, ast.Attribute):
            continue
        name_arg = None
        if node.args:
            name_arg = _str_const(node.args[0])
        for kw in node.keywords:
            if kw.arg == "name" and name_arg is None:
                name_arg = _str_const(kw.value)
        if f.attr in METRIC_REGISTER_VERBS:
            if name_arg and name_arg.startswith(TENANT_FAMILY_PREFIX):
                findings.append(Finding(
                    node.lineno, "J015",
                    f"metric family {name_arg!r} registered outside the "
                    "metering funnel (horaedb_tpu/telemetry/) — the "
                    "horaedb_tenant_* namespace is the usage ledger's; "
                    "account through telemetry.metering.GLOBAL_METER, or "
                    "suppress with the reason",
                ))
                continue
            for kw in node.keywords:
                if kw.arg != "labelnames":
                    continue
                if isinstance(kw.value, (ast.Tuple, ast.List)) and any(
                    _str_const(e) == "tenant" for e in kw.value.elts
                ):
                    findings.append(Finding(
                        node.lineno, "J015",
                        "metric family registered with a `tenant` "
                        "labelname outside the metering funnel — ad-hoc "
                        "per-tenant series fork the usage ledger; route "
                        "the accounting through telemetry.metering."
                        "GLOBAL_METER, or suppress with the reason",
                    ))
        elif f.attr in ("inc", "set") and node.args:
            legacy = _str_const(node.args[0])
            if legacy and "tenant=\"" in legacy:
                findings.append(Finding(
                    node.lineno, "J015",
                    f"legacy metric name {legacy!r} embeds a tenant "
                    "label outside the metering funnel; route through "
                    "telemetry.metering.GLOBAL_METER, or suppress with "
                    "the reason",
                ))


def check_traced_client_funnel(tree: ast.Module,
                               findings: list[Finding]) -> None:
    """J022, two prongs: (1) an `aiohttp.ClientSession` constructed in
    cluster/server code outside the router (the funnel owns the ONE
    outbound session); (2) an HTTP verb called on a session-named
    receiver (`session`/`_session`/`client_session`/`http_session` —
    the naming idiom of every client session in this tree, the J011
    receiver-match heuristic class)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fd = dotted(node.func)
        tail = fd.rsplit(".", 1)[-1] if fd else None
        if tail == "ClientSession":
            findings.append(Finding(
                node.lineno, "J022",
                f"HTTP client session `{fd}(...)` constructed outside the "
                "traced cluster-client funnel (cluster/router."
                "traced_request) — a second outbound session ships hops "
                "with no X-Horaedb-Trace-Id injection, no span grafting, "
                "and no peer-health/probe metrics; route the call through "
                "the router funnel, or suppress with the reason",
            ))
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr in HTTP_VERB_TAILS):
            continue
        owner = f.value
        owner_name = None
        if isinstance(owner, ast.Attribute):
            owner_name = owner.attr
        elif isinstance(owner, ast.Name):
            owner_name = owner.id
        if owner_name in SESSION_RECEIVERS:
            findings.append(Finding(
                node.lineno, "J022",
                f"outbound HTTP `.{f.attr}(...)` on a client session "
                "outside the traced cluster-client funnel — the hop is "
                "invisible to cross-node tracing (no trace-header "
                "injection, no shipped-back span graft) and to the "
                "peer-health view; route through cluster/router."
                "traced_request, or suppress with the reason",
            ))


def check_partial_grid_funnel(tree: ast.Module,
                              findings: list[Finding]) -> None:
    """J023, two prongs: (1) a function DEFINITION reusing a partial-grid
    funnel name (`encode_partials`/`decode_partials`/`merge_partials`/
    `merge_grids`) outside cluster/partial.py — a shadow codec or merge
    forks the wire format / fold order; calling the funnel is fine.
    (2) an in-place ufunc grid fold (`np.add.at`, `np.minimum.at`,
    `np.maximum.at`) in cluster/server code — that is merge math, and
    merge math outside the funnel loses the canonical-region fold order
    the bit-exactness property test pins down."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in PARTIAL_GRID_FUNNEL_DEFS:
                findings.append(Finding(
                    node.lineno, "J023",
                    f"partial-grid funnel name `{node.name}` redefined "
                    "outside cluster/partial.py — a second wire codec or "
                    "merge forks the fragment format and the canonical "
                    "fold order behind the distributed bit-exactness "
                    "guarantee; import it from cluster/partial.py, or "
                    "suppress with the reason",
                ))
            continue
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "at"):
            continue
        owner = f.value
        if (isinstance(owner, ast.Attribute)
                and owner.attr in GRID_FOLD_UFUNC_HEADS):
            findings.append(Finding(
                node.lineno, "J023",
                f"in-place ufunc fold `{dotted(node.func)}(...)` in "
                "cluster/server code — partial-grid merge math belongs "
                "in cluster/partial.merge_grids, where the fold runs in "
                "the fixed canonical-region order that keeps the "
                "distributed answer bit-exact vs single-node; call the "
                "funnel, or suppress with the reason",
            ))


def check_memtrace_funnel(tree: ast.Module,
                          findings: list[Finding]) -> None:
    """J024, three prongs over the data-plane modules: (1) a raw
    `...concat_tables(...)` / `....combine_chunks()` arrow copy; (2) a
    raw `np.concatenate` / `np.ascontiguousarray` host-lane copy (exact
    numpy head — `jnp.concatenate` is traced device math, not a host
    buffer move); (3) a zero-arg `.copy()` on a lane-named receiver
    (`ts`/`vals`/`mask`/`grids`/...). Each belongs behind the
    common/memtrace tracked_* helpers so the bytes land in the per-query
    memory verdict and the copy-tax accounting; calls already wrapped by
    a memtrace helper in the same expression are sanctioned."""
    # sanctioned: any call nested inside a memtrace.tracked_*/track(...)
    # call expression — collect those subtree nodes first
    wrapped: set = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if name and ("memtrace." in name or name.startswith("tracked_")
                     or name in ("track", "memtrace")):
            for sub in ast.walk(node):
                wrapped.add(id(sub))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or id(node) in wrapped:
            continue
        f = node.func
        if not isinstance(f, ast.Attribute):
            continue
        name = dotted(f) or ""
        if f.attr in MEMTRACE_CONCAT_TAILS:
            findings.append(Finding(
                node.lineno, "J024",
                f"raw `.{f.attr}(...)` in a data-plane module — this "
                "arrow copy is invisible to the memory observatory "
                "(EXPLAIN memory verdict, horaedb_mem_* families, the "
                "mem-smoke copy-count gate); route it through "
                "memtrace.tracked_combine / tracked_concat_tables, or "
                "suppress with the reason",
            ))
        elif name in MEMTRACE_NUMPY_CALLS:
            findings.append(Finding(
                node.lineno, "J024",
                f"raw `{name}(...)` in a data-plane module — a host-lane "
                "copy the memory observatory cannot see; route it "
                "through memtrace.tracked_concat / tracked_contiguous "
                "(same array out, bytes accounted), or suppress with "
                "the reason",
            ))
        elif (f.attr == "copy" and not node.args and not node.keywords
                and isinstance(f.value, ast.Name)
                and _LANE_NAME_RE.search(f.value.id)):
            findings.append(Finding(
                node.lineno, "J024",
                f"lane `.copy()` on `{f.value.id}` in a data-plane "
                "module — an unaccounted buffer duplication; use "
                "memtrace.tracked_copy(arr, stage), or suppress with "
                "the reason",
            ))


def check_colblock_contract(tree: ast.Module,
                            findings: list[Finding]) -> None:
    """J025, over the zero-copy data-plane modules: a fresh numpy array
    (`np.array`/`np.asarray`/`np.frombuffer`/`np.copy`) materialized
    from a column block's data — either a `.lane(...)`-accessor argument
    or a block-named buffer — outside colblock.py's sanctioned APIs.
    Such a call silently re-materializes bytes the block already holds
    contiguous and aligned, and the lineage ledger never sees the copy.
    Calls nested inside colblock constructors/coercers or memtrace
    tracked_* helpers are sanctioned (the J024 wrapped-subtree
    technique)."""
    wrapped: set = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func) or ""
        tail = name.rsplit(".", 1)[-1]
        if ("colblock." in name or "memtrace." in name
                or name.startswith("tracked_")
                or tail in COLBLOCK_SANCTIONED_TAILS):
            for sub in ast.walk(node):
                wrapped.add(id(sub))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or id(node) in wrapped:
            continue
        name = dotted(node.func) or ""
        if name not in BLOCK_MATERIALIZE_CALLS:
            continue
        hit = None
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(arg):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in BLOCK_LANE_ATTRS):
                    hit = f"a `.{sub.func.attr}(...)` accessor"
                    break
            if hit:
                break
        if hit is None and any(
            _BLOCK_NAME_RE.search(n) for n in arg_identifiers(node)
        ):
            hit = "a block-named buffer"
        if hit:
            findings.append(Finding(
                node.lineno, "J025",
                f"fresh numpy array `{name}(...)` materialized from "
                f"{hit} — the column block already holds those bytes "
                "contiguous and 64-byte aligned, and this duplication is "
                "invisible to the lineage ledger (the copy-tax verdict "
                "still reads view/reuse); consume the lane by reference, "
                "coerce through colblock.as_lane, duplicate through "
                "ColBlock.copy_lane / memtrace.tracked_copy, or suppress "
                "with the reason",
            ))


def check_visibility_boundary(tree: ast.Module,
                              findings: list[Finding]) -> None:
    """J010: attribute access on the visibility state's row-filtering
    fields (`.tombstones`, `.retention_floor_ms`) outside the shared
    helper. Keyword construction (`Visibility(tombstones=...)`) and the
    manifest's accessor methods (`all_tombstones()`) are deliberately NOT
    flagged — building/storing the state is fine; CONSUMING it for row
    filtering belongs in storage/visibility.apply_visibility alone."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in VISIBILITY_FIELDS:
            findings.append(Finding(
                node.lineno, "J010",
                f"`.{node.attr}` consumed outside storage/visibility.py — "
                "tombstone/retention row filtering must go through the "
                "shared apply_visibility helper (one funnel for every "
                "scan route, the downsample pushdown, and compaction), "
                "or deletes diverge between readers; suppress with the "
                "reason for harness introspection",
            ))
