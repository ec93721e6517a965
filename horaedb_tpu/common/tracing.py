"""Lightweight request tracing: contextvar-propagated span trees.

The engine's perf story spans three lanes (object-store IO/decode,
host<->device transfer, XLA kernel) and VERDICT r02 proved attribution
cannot be an afterthought ("assumed kernel-bound, measured 95%
transfer-bound"). scanstats answers "which lane, per stage, inside one
scan"; this module answers "which request, which layer, end to end" —
every HTTP request (and any internal operation that opts in) becomes a
trace: a tree of named spans with wall-clock durations and attributes,
kept in a bounded in-memory ring served at /debug/traces.

Design constraints:
- zero overhead when sampling is off: `span()` is one contextvar get;
- contextvar propagation: spans opened in `asyncio` child tasks and in
  `asyncio.to_thread` workers attach to the caller's trace (both copy
  the context at spawn);
- no deps beyond the stdlib (storage/ and ingest/ import this).

Usage:

    with tracing.trace("query", metric="cpu") as t:      # root span
        with tracing.span("scan", segment=3):
            ...
    t.trace_id  # echoed to clients as X-Horaedb-Trace-Id

Knobs (env, overridable via configure()):
    HORAEDB_TRACE_SAMPLE   sample rate in [0,1]; 0 disables (default 1)
    HORAEDB_TRACE_SLOW_S   slow-trace WARNING threshold (default 1.0)
    HORAEDB_TRACE_RING     recent-trace ring capacity (default 256)
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import random
import re
import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from contextvars import ContextVar

logger = logging.getLogger(__name__)

# Cross-node propagation headers (cluster observability plane). They live
# HERE — not in server/main.py or cluster/router.py — because both the
# HTTP tier and the router funnel need them and this module is the only
# stdlib-clean common ground (router importing server would cycle).
TRACE_HEADER = "X-Horaedb-Trace-Id"
PARENT_SPAN_HEADER = "X-Horaedb-Parent-Span"
SPANS_HEADER = "X-Horaedb-Trace-Spans"

# Serialized-subtree ship budget: the callee returns its span list in a
# response header, and aiohttp's client rejects header fields over ~8190
# bytes — blowing that budget would fail the FORWARDED REQUEST to report
# on it. Stay well under, degrading detail instead (export_spans).
SHIP_BUDGET_BYTES = 4096

_TRACE_ID_RE = re.compile(r"^[0-9a-f]{8,64}$")


def valid_trace_id(s) -> bool:
    """Is `s` shaped like one of our trace ids? Remote peers are trusted
    cluster members, but the id lands in filenames (slowlog spool) and
    log lines — refuse anything that isn't plain bounded hex."""
    return isinstance(s, str) and _TRACE_ID_RE.match(s) is not None


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def env_defaults() -> tuple[float, float, int]:
    """(sample, slow_s, ring) from the HORAEDB_TRACE_* env vars, falling
    back to the compiled defaults. The server's TracingConfig seeds its
    field defaults from this, so the env knobs stay live when the config
    file has no [tracing] section (explicit config values win)."""
    return (
        min(1.0, max(0.0, _env_float("HORAEDB_TRACE_SAMPLE", 1.0))),
        _env_float("HORAEDB_TRACE_SLOW_S", 1.0),
        max(1, int(_env_float("HORAEDB_TRACE_RING", 256))),
    )


_sample_rate, _slow_s, _ring_cap = env_defaults()


class Span:
    __slots__ = ("span_id", "parent_id", "name", "start_ms", "duration_s",
                 "attrs")

    def __init__(self, span_id: int, parent_id: int | None, name: str,
                 attrs: dict):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_ms = time.time() * 1000.0
        self.duration_s: float | None = None  # None while open
        self.attrs = attrs

    def as_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "name": self.name,
            "start_ms": round(self.start_ms, 3),
            "duration_s": (round(self.duration_s, 6)
                           if self.duration_s is not None else None),
            # copy (one level deep for add_stage's nested dict): a span of
            # a still-running background task may mutate attrs while the
            # serialized dict is being JSON-encoded
            "attrs": {
                k: (dict(v) if isinstance(v, dict) else v)
                for k, v in list(self.attrs.items())
            },
        }


class Trace:
    """One request's span set. Spans append from any task/thread of the
    request (list.append is atomic under the GIL; span identity is never
    shared across appenders)."""

    __slots__ = ("trace_id", "spans", "_ids")

    def __init__(self, trace_id: str):
        self.trace_id = trace_id
        self.spans: list[Span] = []
        self._ids = itertools.count(1)

    def new_span(self, parent_id: int | None, name: str, attrs: dict) -> Span:
        sp = Span(next(self._ids), parent_id, name, attrs)
        self.spans.append(sp)
        return sp

    @property
    def root(self) -> Span | None:
        return self.spans[0] if self.spans else None

    def as_dict(self) -> dict:
        """Span tree: children nested under their parent. Iterates ONE
        snapshot of the span list: a background task spawned inside the
        request (e.g. an ingest flush) may still be appending spans after
        the trace landed in the ring, and two live iterations could see
        different lengths (KeyError on the second). Parents are created
        before their children, so any snapshot is self-consistent."""
        spans = list(self.spans)
        nodes = {s.span_id: dict(s.as_dict(), children=[]) for s in spans}
        roots = []
        for s in spans:
            node = nodes[s.span_id]
            parent = nodes.get(s.parent_id) if s.parent_id else None
            (parent["children"] if parent else roots).append(node)
        root = self.root
        return {
            "trace_id": self.trace_id,
            "name": root.name if root else "",
            "start_ms": root.start_ms if root else 0.0,
            "duration_s": root.duration_s if root else None,
            "spans": len(self.spans),
            "root": roots[0] if roots else None,
        }


# (trace, current span) of the running context; None outside any trace
_ACTIVE: ContextVar[tuple[Trace, Span] | None] = ContextVar(
    "horaedb_trace", default=None
)

_ring_lock = threading.Lock()
_stage_lock = threading.Lock()
_ring: "OrderedDict[str, Trace]" = OrderedDict()


def configure(sample: float | None = None, slow_s: float | None = None,
              ring: int | None = None) -> None:
    """Override the env-derived knobs (server config, tests)."""
    global _sample_rate, _slow_s, _ring_cap
    if sample is not None:
        _sample_rate = min(1.0, max(0.0, float(sample)))
    if slow_s is not None:
        _slow_s = float(slow_s)
    if ring is not None:
        _ring_cap = max(1, int(ring))
        with _ring_lock:
            while len(_ring) > _ring_cap:
                _ring.popitem(last=False)


def sampling_enabled() -> bool:
    return _sample_rate > 0.0


def _sampled() -> bool:
    if _sample_rate >= 1.0:
        return True
    if _sample_rate <= 0.0:
        return False
    return random.random() < _sample_rate


@contextmanager
def trace(name: str, *, remote_id: str | None = None,
          remote_parent: int | None = None, **attrs):
    """Root span context: starts a new trace (subject to sampling) and
    registers it in the recent-trace ring on exit. Yields the Trace, or
    None when this request is not sampled. Nested calls degrade to a
    child span of the enclosing trace.

    `remote_id` adopts a trace id minted by a peer (a forwarded request's
    X-Horaedb-Trace-Id) instead of minting one: the sampling decision was
    the ORIGIN's — it only sent headers because it sampled — so adoption
    bypasses the local sampler; an unsampled origin sends nothing and the
    callee falls through to its own sampling. A malformed id is ignored
    (normal local trace). `remote_parent` records the origin-side span id
    this request hangs under, so the shipped-back subtree is attributable
    even when read raw."""
    cur = _ACTIVE.get()
    if cur is not None:
        with span(name, **attrs):
            yield cur[0]
        return
    if remote_id is not None and valid_trace_id(remote_id):
        t = Trace(remote_id)
        if remote_parent is not None:
            attrs = dict(attrs, remote_parent=remote_parent)
    elif not _sampled():
        yield None
        return
    else:
        t = Trace(os.urandom(8).hex())
    root = t.new_span(None, name, attrs)
    token = _ACTIVE.set((t, root))
    t0 = time.perf_counter()
    try:
        yield t
    finally:
        root.duration_s = time.perf_counter() - t0
        _ACTIVE.reset(token)
        _finish(t)


def _finish(t: Trace) -> None:
    with _ring_lock:
        _ring[t.trace_id] = t
        while len(_ring) > _ring_cap:
            _ring.popitem(last=False)
    root = t.root
    if root is not None and root.duration_s is not None \
            and root.duration_s >= _slow_s:
        logger.warning(
            "slow trace %s: %s took %.3fs (%d spans; threshold %.3fs) "
            "GET /debug/traces/%s for the span tree",
            t.trace_id, root.name, root.duration_s, len(t.spans), _slow_s,
            t.trace_id,
        )


@contextmanager
def span(name: str, **attrs):
    """Child span of the active trace; a no-op (one contextvar get) when
    no trace is active. Yields the Span or None."""
    cur = _ACTIVE.get()
    if cur is None:
        yield None
        return
    t, parent = cur
    sp = t.new_span(parent.span_id, name, attrs)
    token = _ACTIVE.set((t, sp))
    t0 = time.perf_counter()
    try:
        yield sp
    finally:
        sp.duration_s = time.perf_counter() - t0
        _ACTIVE.reset(token)


def current_trace_id() -> str | None:
    cur = _ACTIVE.get()
    return cur[0].trace_id if cur is not None else None


def add_attr(**kw) -> None:
    """Attach attributes to the current span (no-op outside a trace)."""
    cur = _ACTIVE.get()
    if cur is not None:
        cur[1].attrs.update(kw)


def add_stage(stage: str, seconds: float) -> None:
    """Fold one scanstats stage timing into the current span (accumulated
    under a 'stages' attr — per-chunk stages would flood the tree as
    individual spans)."""
    cur = _ACTIVE.get()
    if cur is None:
        return
    # a query's segments fold on worker threads, several at once, under
    # the one span their coroutines share
    with _stage_lock:
        stages = cur[1].attrs.setdefault("stages", {})
        stages[stage] = round(stages.get(stage, 0.0) + seconds, 6)


def recent(limit: int = 50, min_ms: float | None = None) -> list[dict]:
    """Most-recent-first trace summaries (no span bodies). `min_ms` keeps
    only traces at least that slow — the "last 10 slow traces" operator
    pull — applied BEFORE `limit`, so the newest `limit` traces ABOVE the
    threshold come back, not however many slow ones survive inside the
    newest `limit`."""
    with _ring_lock:
        traces = list(_ring.values())
    if min_ms is not None:
        traces = [
            t for t in traces
            if t.root is not None and t.root.duration_s is not None
            and t.root.duration_s * 1000.0 >= min_ms
        ]
    out = []
    for t in reversed(traces[-limit:] if limit else traces):
        root = t.root
        out.append({
            "trace_id": t.trace_id,
            "name": root.name if root else "",
            "start_ms": root.start_ms if root else 0.0,
            "duration_s": (round(root.duration_s, 6)
                           if root and root.duration_s is not None else None),
            "spans": len(t.spans),
        })
    return out


def get(trace_id: str) -> dict | None:
    with _ring_lock:
        t = _ring.get(trace_id)
    return t.as_dict() if t is not None else None


def reset() -> None:
    """Clear the ring (tests)."""
    with _ring_lock:
        _ring.clear()


# -- cross-node stitching ----------------------------------------------------
# The callee EXPORTS its finished span list (flat, compact JSON) in the
# response's SPANS_HEADER; the origin GRAFTS it under the router funnel's
# client span. Flat-with-parent-ids beats a nested tree on the wire: the
# graft is one pass, and a record whose parent got truncated away still
# attaches (to the anchor span) instead of orphaning.

# root attrs that must NOT ride the ship header: the EXPLAIN payload and
# scanstats already travel in the response BODY (the federated-EXPLAIN
# fragment); duplicating them here would blow the budget on every query
_NOSHIP_ATTRS = frozenset({"explain", "scanstats"})


def current_span_id() -> int | None:
    """Span id of the running context's current span (the funnel puts it
    in PARENT_SPAN_HEADER so the callee can name its origin anchor)."""
    cur = _ACTIVE.get()
    return cur[1].span_id if cur is not None else None


def export_spans(t: Trace, budget: int = SHIP_BUDGET_BYTES) -> str:
    """Serialize a finished trace's span list for the SPANS_HEADER,
    degrading under `budget` instead of failing the response: full
    records -> records without attrs -> one root summary carrying a
    `truncated_spans` count. Always returns header-safe ASCII JSON."""
    spans = list(t.spans)

    def enc(recs) -> str:
        return json.dumps(recs, separators=(",", ":"), ensure_ascii=True,
                          default=str)

    def record(s: Span, with_attrs: bool) -> dict:
        rec = {
            "id": s.span_id,
            "parent": s.parent_id,
            "name": s.name,
            "start_ms": round(s.start_ms, 3),
            "duration_s": round(s.duration_s or 0.0, 6),
        }
        if with_attrs and s.attrs:
            attrs = {k: v for k, v in list(s.attrs.items())
                     if k not in _NOSHIP_ATTRS}
            if attrs:
                rec["attrs"] = attrs
        return rec

    for with_attrs in (True, False):
        try:
            out = enc([record(s, with_attrs) for s in spans])
        except (TypeError, ValueError):
            continue  # a non-JSON attr value: retry without attrs
        if len(out) <= budget:
            return out
    root = t.root
    return enc([{
        "id": root.span_id if root else 1,
        "parent": None,
        "name": root.name if root else "",
        "start_ms": round(root.start_ms, 3) if root else 0.0,
        "duration_s": round(root.duration_s or 0.0, 6) if root else 0.0,
        "attrs": {"truncated_spans": len(spans)},
    }])


def graft_remote(payload, node: str) -> int:
    """Attach a peer's exported span list under the CURRENT span, re-ided
    from the local trace's counter and labeled `node=<peer>`. A record
    whose parent is unknown (truncated ship, malformed entry) anchors to
    the current span — the stitched tree has no orphans by construction.
    Returns spans grafted; 0 (never a raise) on any malformed payload —
    a peer's bad header must not fail the origin's request."""
    cur = _ACTIVE.get()
    if cur is None or not payload:
        return 0
    if isinstance(payload, (str, bytes)):
        try:
            payload = json.loads(payload)
        except (ValueError, UnicodeDecodeError):
            return 0
    if not isinstance(payload, list):
        return 0
    t, anchor = cur
    idmap: dict[int, int] = {}
    grafted = 0
    for rec in payload:
        if not isinstance(rec, dict):
            continue
        attrs = rec.get("attrs")
        attrs = dict(attrs) if isinstance(attrs, dict) else {}
        attrs["node"] = node
        rparent = rec.get("parent")
        parent = (idmap.get(rparent, anchor.span_id)
                  if isinstance(rparent, int) else anchor.span_id)
        sp = t.new_span(parent, str(rec.get("name", "?")), attrs)
        try:
            sp.start_ms = float(rec.get("start_ms", sp.start_ms))
            sp.duration_s = float(rec.get("duration_s", 0.0))
        except (TypeError, ValueError):
            sp.duration_s = 0.0
        rid = rec.get("id")
        if isinstance(rid, int):
            idmap[rid] = sp.span_id
        grafted += 1
    return grafted
