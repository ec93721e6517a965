"""Pooled parser front-end (reference: pooled_parser.rs:38-73).

`decode` uses a fresh arena; `decode_async` borrows one of POOL_SIZE pooled
native arenas (auto-returned), so steady-state ingest allocates nothing per
request — the deadpool pattern of the reference.
"""

from __future__ import annotations

import asyncio
import logging

import numpy as np

from horaedb_tpu.common import colblock, memtrace, tracing
from horaedb_tpu.ingest.types import ParsedWriteRequest
from horaedb_tpu.server.metrics import GLOBAL_METRICS
from horaedb_tpu.storage import scanstats

logger = logging.getLogger(__name__)

POOL_SIZE = 64


class DecodeArena:
    """Per-parser scratch buffers reused across requests.

    Steady-state ingest parses the same payload SHAPE every scrape
    interval, but each parse_light still paid fresh numpy allocations for
    the id-lane copies (~90 ns/sample parse budget, ROOFLINE §7). A
    pooled parser owns one arena; `take` hands out views into buffers
    that grow geometrically and never shrink, so after warmup a request
    allocates nothing. Lanes come off the column-block allocator
    (common/colblock.py aligned_empty), so the 64-byte alignment
    contract holds from wire decode through the memtable arena to device
    staging — no downstream layer ever repacks a parse lane. Returned
    views follow the pool's borrow
    discipline: valid only until the owning parser's next parse —
    callers that hold lanes past the borrow (exemplar persistence) copy
    them out first.

    `allocations`/`takes` are test hooks: the allocation-count assertion
    (tests) pins the steady state at zero new buffers per request."""

    __slots__ = ("_bufs", "allocations", "takes")

    def __init__(self) -> None:
        self._bufs: dict[str, np.ndarray] = {}
        self.allocations = 0
        self.takes = 0

    def take(self, tag: str, n: int, dtype) -> np.ndarray:
        self.takes += 1
        dt = np.dtype(dtype)
        buf = self._bufs.get(tag)
        if buf is None or len(buf) < n or buf.dtype != dt:
            cap = max(int(n), 256)
            if buf is not None and buf.dtype == dt:
                cap = max(cap, 2 * len(buf))
            buf = colblock.aligned_empty(cap, dt)
            self._bufs[tag] = buf
            self.allocations += 1
            memtrace.track_bytes(buf.nbytes, "parse", "alloc")
        else:
            # steady state: a pooled buffer reissued, zero fresh bytes
            memtrace.track_bytes(int(n) * dt.itemsize, "parse", "reuse")
        return buf[:n]

PARSE_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_ingest_parse_seconds",
    help="Remote-write wire decode time (the ingest parse lane), including "
         "any worker-thread handoff for large payloads.",
)
POOL_WAIT_SECONDS = GLOBAL_METRICS.histogram(
    "horaedb_ingest_pool_wait_seconds",
    help="Time spent waiting for a parser arena; sustained non-zero tail "
         "means POOL_SIZE is the ingest bottleneck.",
)


# the ingest front's stages in the one stage funnel (storage/scanstats.py):
# `ingest.parse` is work; `ingest.pool_wait` matches the trace reduction's
# WAITS pattern, so waiting for an arena never reads as work
STAGES = scanstats.Family(
    "ingest", {"parse": PARSE_SECONDS, "pool_wait": POOL_WAIT_SECONDS}.__getitem__
)

_BACKEND: str | None = None


def parser_backend() -> str:
    """Which rung of the backend chain this process parses with, resolved
    once and logged once: `native` (C++ parser) -> `protobuf` (protobuf
    runtime PyParser) -> `wire` (hand-rolled pure-Python WireParser: no
    native code, no protoc codegen; lacks the hash lanes, so the engine
    takes its slow path). The server exposes it on
    /api/v1/status/buildinfo."""
    global _BACKEND
    if _BACKEND is None:
        from horaedb_tpu.ingest import native

        if native.load() is not None:
            _BACKEND = "native"
        else:
            try:
                import horaedb_tpu.ingest.py_parser  # noqa: F401

                _BACKEND = "protobuf"
            except ImportError:
                _BACKEND = "wire"
        log = logger.info if _BACKEND == "native" else logger.warning
        log("remote-write parser backend: %s", _BACKEND)
    return _BACKEND


def _new_backend():
    """One parser of the chosen backend. The native one gets a DecodeArena
    so pooled parses reuse their scratch lane buffers."""
    backend = parser_backend()
    if backend == "native":
        from horaedb_tpu.ingest import native

        p = native.NativeParser()
        p.arena = DecodeArena()
        return p
    if backend == "protobuf":
        from horaedb_tpu.ingest.py_parser import PyParser

        return PyParser()
    from horaedb_tpu.ingest.wire_parser import WireParser

    return WireParser()


class ParserPool:
    """Bounded pool of parser arenas (deadpool analog, POOL_SIZE=64)."""

    def __init__(self, size: int = POOL_SIZE):
        self._size = size
        self._sem = asyncio.Semaphore(size)
        self._free: list = []
        self._in_use = 0
        self._waiting = 0

    async def decode(self, payload: bytes) -> ParsedWriteRequest:
        async with self.borrow() as parser:
            # native parse releases no GIL-bound state we await on; run in a
            # thread so large payloads don't stall the event loop
            with tracing.span("parse", bytes=len(payload)), \
                    STAGES.stage("parse"):
                return await asyncio.to_thread(
                    STAGES.on_worker, "parse", parser.parse, payload)

    def borrow(self):
        """Async context manager lending a parser backend for multi-call use
        (parse_light + accum-add must run on one arena before its next
        parse). The borrowed parser returns to the pool on exit unless the
        body was cancelled mid-parse."""
        return _Borrow(self)

    @property
    def status(self) -> dict:
        """Pool telemetry (reference: pool_stats bin)."""
        return {
            "size": self._size,
            "available": self._size - self._in_use,
            "waiting": self._waiting,
        }


class _Borrow:
    def __init__(self, pool: ParserPool):
        self._pool = pool
        self._parser = None

    async def __aenter__(self):
        pool = self._pool
        pool._waiting += 1
        try:
            with STAGES.stage("pool_wait"):
                await pool._sem.acquire()
        finally:
            pool._waiting -= 1
        pool._in_use += 1
        self._parser = pool._free.pop() if pool._free else _new_backend()
        return self._parser

    async def __aexit__(self, exc_type, exc, tb):
        pool = self._pool
        if self._parser is not None and exc_type is not asyncio.CancelledError:
            pool._free.append(self._parser)
        self._parser = None
        pool._in_use -= 1
        pool._sem.release()
        return False


_DEFAULT_POOL = None


class PooledParser:
    """API mirror of the reference PooledParser."""

    @staticmethod
    def decode(payload: bytes) -> ParsedWriteRequest:
        """One-shot decode with a fresh parser (pooled_parser.rs `decode`)."""
        return _new_backend().parse(payload)

    @staticmethod
    async def decode_async(payload: bytes) -> ParsedWriteRequest:
        """Pooled decode (pooled_parser.rs `decode_async`)."""
        global _DEFAULT_POOL
        if _DEFAULT_POOL is None:
            _DEFAULT_POOL = ParserPool()
        return await _DEFAULT_POOL.decode(payload)
