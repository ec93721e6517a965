"""ResilientStore: the hardened boundary around every object-store call.

In the HoraeDB v2 design the shared object store IS the distributed data
plane (PAPER §0) — which makes every naked `store.get()`/`put()` a
single point of failure for a flush, a compaction, or a query scan. This
module wraps any ObjectStore with the fault-tolerance contract the rest
of the tree builds on:

- **Classified retries.** Every attempt's failure runs through the error
  taxonomy (common/error.py): `retryable` faults retry with capped
  exponential backoff and FULL jitter (sleep ~ U(0, min(cap, base*2^n)),
  the AWS-recommended variant — synchronized retry storms from many
  clients decorrelate); `persistent` and `fatal` faults surface
  immediately. Semantic results (NotFound, PreconditionFailed) are part
  of the store contract, not failures — they pass through untouched and
  count as successes.
- **Per-attempt deadlines.** Each attempt runs under
  `asyncio.wait_for(op, op_deadline)`: a black-holed endpoint costs a
  bounded timeout, not a hung flush worker. Ops issued on behalf of a
  request additionally respect the request's end-to-end deadline
  (common/deadline.py): each attempt is capped at the remaining budget
  and the ladder stops — `DeadlineExceeded`, the HTTP 504 — once the
  budget cannot cover another attempt, so retries/backoff never outlive
  the query that asked. Background work (no deadline installed) keeps
  the configured ladder unchanged.
- **A circuit breaker per store.** `failure_threshold` consecutive
  gave-ups open the breaker; while open every call fails fast with
  `UnavailableError` (carrying a Retry-After hint) instead of burning a
  full retry ladder against a dead backend. After `open_s` the breaker
  half-opens and admits one probe; success closes it, failure re-opens.
- **Observability.** `horaedb_objstore_attempts_total{op,result}`,
  `horaedb_objstore_retries_total{op}`, `horaedb_objstore_gave_up_total
  {op}`, and `horaedb_objstore_breaker_state{store}` render on /metrics,
  and every retry backoff is a span (`objstore_retry`) on the active
  trace, so a retry storm is visible in /debug/traces with the op, the
  attempt number, and the error that caused it.

`put_stream` is deliberately NOT retried per-attempt: its chunk iterator
is consumed by the first attempt, and buffering it would defeat the
streaming memory bound. It still gets the breaker, the classification,
and the metrics; replay of failed streams belongs to the layer that owns
the bytes (the flush executor's park/replay machinery).

Deployment shape: the server wraps its store once at boot
(server/main.py), so engine flush, manifest, fence, compaction, and scan
reads all inherit the policy without knowing it exists. jaxlint J009
enforces the boundary: concrete stores are constructed inside objstore/
or handed straight to a ResilientStore.
"""

from __future__ import annotations

import asyncio
import random
import time
from dataclasses import dataclass, field

from horaedb_tpu.common import deadline as deadline_ctx
from horaedb_tpu.common import tracing
from horaedb_tpu.common.error import (
    DeadlineExceeded,
    HoraeError,
    UnavailableError,
    classify,
)
from horaedb_tpu.common.time_ext import ReadableDuration
from horaedb_tpu.objstore import ObjectMeta, ObjectStore
from horaedb_tpu.server.metrics import GLOBAL_METRICS

OBJSTORE_ATTEMPTS = GLOBAL_METRICS.counter(
    "horaedb_objstore_attempts_total",
    help="Object-store attempts through the resilience layer, by verb and "
         "outcome (ok | retryable | persistent | fatal | breaker_open).",
    labelnames=("op", "result"),
)
OBJSTORE_RETRIES = GLOBAL_METRICS.counter(
    "horaedb_objstore_retries_total",
    help="Backoff retries issued after a retryable object-store failure.",
    labelnames=("op",),
)
OBJSTORE_GAVE_UP = GLOBAL_METRICS.counter(
    "horaedb_objstore_gave_up_total",
    help="Object-store ops that exhausted their retry budget (the failure "
         "surfaced to the caller as UnavailableError).",
    labelnames=("op",),
)
OBJSTORE_BREAKER_STATE = GLOBAL_METRICS.gauge(
    "horaedb_objstore_breaker_state",
    help="Circuit breaker state per store: 0 closed, 1 half-open, 2 open.",
    labelnames=("store",),
)

OPS = ("put", "put_if_absent", "put_stream", "get", "list", "delete", "head")


@dataclass
class RetryPolicy:
    """Retry/backoff/deadline knobs ([metric_engine.storage.object_store.
    resilience] in the server config)."""

    max_attempts: int = 4
    backoff_base: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.millis(50)
    )
    backoff_cap: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(2)
    )
    # per-ATTEMPT deadline: a black-holed endpoint costs this much, not a
    # hung worker (the S3 client's own timeouts usually fire first; this
    # is the backstop for stores without native timeouts)
    op_deadline: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(30)
    )


@dataclass
class BreakerPolicy:
    """Circuit-breaker knobs (same config table as RetryPolicy)."""

    # consecutive gave-up ops (full retry ladders, not single attempts)
    # that open the breaker; 0 disables the breaker entirely
    failure_threshold: int = 5
    # how long the breaker stays open before half-opening one probe
    open_for: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.secs(10)
    )


class CircuitBreaker:
    """Per-store breaker: closed -> (threshold gave-ups) -> open ->
    (open_for elapsed) -> half-open probe -> closed | open.

    Event-loop-confined like the rest of the store plumbing — no locks.
    `clock` is injectable so tests drive state transitions without
    sleeping."""

    CLOSED, HALF_OPEN, OPEN = "closed", "half_open", "open"

    def __init__(self, policy: BreakerPolicy, name: str = "objstore",
                 clock=time.monotonic):
        self._policy = policy
        self._clock = clock
        self._name = name
        self._failures = 0
        self._opened_at: float | None = None
        self._probing = False
        self._gauge = OBJSTORE_BREAKER_STATE.labels(name)
        self._gauge.set(0)

    @property
    def state(self) -> str:
        if self._opened_at is None:
            return self.CLOSED
        if self._clock() - self._opened_at >= self._policy.open_for.seconds:
            return self.HALF_OPEN
        return self.OPEN

    def _set_gauge(self) -> None:
        self._gauge.set(
            {self.CLOSED: 0, self.HALF_OPEN: 1, self.OPEN: 2}[self.state]
        )

    def retry_after_s(self) -> float:
        if self._opened_at is None:
            return 0.0
        return max(
            0.0,
            self._policy.open_for.seconds - (self._clock() - self._opened_at),
        )

    def admit(self) -> bool:
        """May an op proceed? OPEN rejects; HALF_OPEN admits one probe at
        a time (concurrent callers fail fast while the probe is out)."""
        st = self.state
        if st == self.CLOSED:
            return True
        if st == self.HALF_OPEN and not self._probing:
            self._probing = True
            self._set_gauge()
            return True
        self._set_gauge()
        return False

    def on_success(self) -> None:
        self._failures = 0
        self._opened_at = None
        self._probing = False
        self._set_gauge()

    def on_gave_up(self) -> None:
        """One op exhausted its whole retry ladder (or a half-open probe
        failed): count toward — or re-arm — the open state."""
        self._probing = False
        if self._policy.failure_threshold <= 0:
            return  # breaker disabled
        self._failures += 1
        if self._opened_at is not None or (
            self._failures >= self._policy.failure_threshold
        ):
            self._opened_at = self._clock()
        self._set_gauge()

    def on_probe_aborted(self) -> None:
        """An admitted op ended without a verdict (cancelled mid-flight):
        release the half-open probe slot WITHOUT moving state, so the
        next caller can probe — a leaked slot would lock the breaker
        open forever."""
        self._probing = False
        self._set_gauge()

    def force_open(self) -> None:
        """Trip the breaker now (admin/test hook; smoke gates use it to
        prove the 503 shedding path without a dead backend)."""
        self._failures = max(self._failures, self._policy.failure_threshold)
        self._opened_at = self._clock()
        self._set_gauge()

    def reset(self) -> None:
        self.on_success()


class ResilientStore(ObjectStore):
    """ObjectStore wrapper implementing the module-docstring contract.

    `rng` is injectable (tests pin jitter); `clock` feeds the breaker."""

    def __init__(
        self,
        inner: ObjectStore,
        retry: RetryPolicy | None = None,
        breaker: BreakerPolicy | None = None,
        name: str = "objstore",
        rng: random.Random | None = None,
        clock=time.monotonic,
    ) -> None:
        self._inner = inner
        self._retry = retry or RetryPolicy()
        self.breaker = CircuitBreaker(breaker or BreakerPolicy(), name=name,
                                      clock=clock)
        self._rng = rng or random.Random()
        self._name = name
        # pre-register every (op, result=ok) child so /metrics shows the
        # families' zero state from boot (the PR2 convention)
        for op in OPS:
            OBJSTORE_ATTEMPTS.labels(op, "ok")
            OBJSTORE_RETRIES.labels(op)
            OBJSTORE_GAVE_UP.labels(op)

    @property
    def inner(self) -> ObjectStore:
        return self._inner

    # -- the retry core ------------------------------------------------------

    def _backoff_s(self, attempt: int) -> float:
        """Capped exponential with FULL jitter: U(0, min(cap, base*2^n))."""
        cap = self._retry.backoff_cap.seconds
        base = self._retry.backoff_base.seconds
        return self._rng.uniform(0.0, min(cap, base * (2 ** attempt)))

    def _check_admit(self, op: str) -> None:
        if not self.breaker.admit():
            OBJSTORE_ATTEMPTS.labels(op, "breaker_open").inc()
            retry_after = self.breaker.retry_after_s()
            raise UnavailableError(
                f"object store unavailable (circuit breaker open, "
                f"store={self._name}, op={op}); failing fast",
                retry_after_s=retry_after,
            )

    async def _call(self, op: str, fn, *args):
        """One resilient op: admit -> bounded attempts -> classified
        surface. `fn` is the inner-store coroutine function.

        Every admitted call reaches exactly one breaker verdict —
        on_success (returned, semantic result, or a deterministic
        rejection that proves the backend is up), on_gave_up (budget
        exhausted), or on_probe_aborted (cancelled mid-flight). A leaked
        half-open probe slot would lock the breaker open forever."""
        self._check_admit(op)
        try:
            return await self._attempt_loop(op, fn, args)
        except asyncio.CancelledError:
            self.breaker.on_probe_aborted()
            raise
        except DeadlineExceeded:
            # the CALLER's budget died mid-ladder: no availability verdict
            # either way — release a half-open probe slot without moving
            # breaker state (same contract as a cancellation)
            self.breaker.on_probe_aborted()
            raise

    def _raise_budget_spent(self, op: str, attempt: int,
                            last: BaseException | None) -> None:
        """The query deadline (common/deadline.py) cannot cover another
        attempt: stop the ladder NOW, typed. An op issued on behalf of a
        request must never outlive the request — a black-holed store
        under a 1 s query deadline costs ~1 s, not the full ladder."""
        d = deadline_ctx.current()
        raise DeadlineExceeded(
            f"{op} abandoned after {attempt} attempt(s): query deadline "
            f"exceeded (store={self._name})",
            cause=last,
            budget_s=d.budget_s if d else None,
            elapsed_s=d.elapsed_s() if d else None,
            at=f"objstore_{op}",
        )

    async def _attempt_loop(self, op: str, fn, args):
        deadline = self._retry.op_deadline.seconds
        attempts = max(1, self._retry.max_attempts)
        last: BaseException | None = None
        for attempt in range(attempts):
            # per-attempt timeout = min(op_deadline, the driving query's
            # remaining budget); background work (no deadline contextvar)
            # keeps the configured op_deadline unchanged
            rem = deadline_ctx.remaining_s()
            timeout = deadline
            if rem is not None:
                if rem <= 0.0:
                    self._raise_budget_spent(op, attempt, last)
                timeout = min(deadline, rem)
            try:
                result = await asyncio.wait_for(fn(*args), timeout=timeout)
            except HoraeError as e:
                from horaedb_tpu.objstore import NotFound, PreconditionFailed

                if isinstance(e, (NotFound, PreconditionFailed)):
                    # semantic contract results, not faults
                    OBJSTORE_ATTEMPTS.labels(op, "ok").inc()
                    self.breaker.on_success()
                    raise
                last = e
            except Exception as e:  # noqa: BLE001 — classified below
                # (CancelledError is BaseException: handled by _call)
                last = e
            else:
                OBJSTORE_ATTEMPTS.labels(op, "ok").inc()
                self.breaker.on_success()
                return result
            cls = classify(last)
            OBJSTORE_ATTEMPTS.labels(op, cls).inc()
            if cls in ("fatal", "persistent"):
                # deterministic / process-level: surface now. The backend
                # RESPONDED, so availability-wise this is a success — it
                # must not poison the breaker, and above all it must
                # release a half-open probe slot (a 4xx during recovery
                # would otherwise brick the breaker open forever)
                self.breaker.on_success()
                raise last
            if attempt + 1 < attempts:
                # retrying (or even just backing off) past the caller's
                # remaining budget is work nobody will read: stop typed
                rem = deadline_ctx.remaining_s()
                if rem is not None and rem <= 0.0:
                    self._raise_budget_spent(op, attempt + 1, last)
                OBJSTORE_RETRIES.labels(op).inc()
                backoff = self._backoff_s(attempt)
                if rem is not None:
                    backoff = min(backoff, max(rem, 0.0))
                # the retry is a SPAN wrapping its backoff sleep, so a slow
                # traced request shows exactly where its latency went
                with tracing.span(
                    "objstore_retry", op=op, attempt=attempt + 1,
                    backoff_ms=round(backoff * 1000, 1),
                    error=str(last)[:200],
                ):
                    if backoff > 0:
                        await asyncio.sleep(backoff)
        OBJSTORE_GAVE_UP.labels(op).inc()
        self.breaker.on_gave_up()
        raise UnavailableError(
            f"{op} gave up after {attempts} attempts (store={self._name})",
            cause=last,
            retry_after_s=self.breaker.retry_after_s() or None,
        )

    # -- the five verbs (+ conditional put + stream) -------------------------

    async def put(self, path: str, data: bytes) -> None:
        await self._call("put", self._inner.put, path, data)

    async def put_if_absent(self, path: str, data: bytes) -> None:
        # Retrying a conditional put is safe in this tree: the inner stores
        # answer synchronously (no lost-ack window), and a retry that finds
        # its own previous attempt's object raises PreconditionFailed —
        # which for every caller (epoch fencing) means "lost the race",
        # the correct conservative answer.
        await self._call("put_if_absent", self._inner.put_if_absent, path, data)

    async def get(self, path: str) -> bytes:
        return await self._call("get", self._inner.get, path)

    async def get_if_changed(self, path: str, etag):
        """Conditional GET rides the `get` verb's retry/breaker/metrics
        (it IS a get, economized); an "unchanged" answer counts as a
        success like the other semantic results."""
        return await self._call("get", self._inner.get_if_changed, path, etag)

    async def list(self, prefix: str) -> list[ObjectMeta]:
        return await self._call("list", self._inner.list, prefix)

    async def delete(self, path: str) -> None:
        await self._call("delete", self._inner.delete, path)

    async def delete_many(self, paths: list[str]) -> list[BaseException | None]:
        """One guarded call for the batch (breaker, deadline, metrics as
        one `delete`); what a single path raised comes back as its value
        and is not retried: the callers' deletes are best-effort."""
        return await self._call("delete", self._inner.delete_many, paths)

    async def head(self, path: str) -> ObjectMeta:
        return await self._call("head", self._inner.head, path)

    async def put_stream(self, path: str, chunks) -> int:
        """Breaker + classification + metrics, but NO per-attempt retry:
        the chunk iterator is consumed by the first attempt (see module
        docstring). No wait_for either — a large stream legitimately
        outlives the per-attempt deadline; the inner transport owns its
        own IO timeouts."""
        self._check_admit("put_stream")
        try:
            n = await self._inner.put_stream(path, chunks)
        except asyncio.CancelledError:
            self.breaker.on_probe_aborted()  # no verdict: free the slot
            raise
        except Exception as e:  # noqa: BLE001 — classified below
            cls = classify(e)
            OBJSTORE_ATTEMPTS.labels("put_stream", cls).inc()
            if cls == "retryable":
                OBJSTORE_GAVE_UP.labels("put_stream").inc()
                self.breaker.on_gave_up()
                raise UnavailableError(
                    f"put_stream failed (store={self._name})", cause=e,
                    retry_after_s=self.breaker.retry_after_s() or None,
                )
            # deterministic/fatal: the backend responded — availability-
            # wise a success (and the half-open probe slot must free)
            self.breaker.on_success()
            raise
        OBJSTORE_ATTEMPTS.labels("put_stream", "ok").inc()
        self.breaker.on_success()
        return n

    # -- pass-throughs -------------------------------------------------------

    async def verify_conditional_puts(self, prefix: str) -> None:
        await self._inner.verify_conditional_puts(prefix)

    def local_path(self, path: str) -> str | None:
        return self._inner.local_path(path)

    async def close(self) -> None:
        closer = getattr(self._inner, "close", None)
        if closer is not None:
            await closer()
