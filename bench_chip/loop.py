"""The client's loops and the arithmetic on what they recorded.

A closed loop: n workers, each sending its next request when the last is
answered. An open loop: one stream of requests, the i-th due at
start + i / rate, sent by a bounded pool of senders; a request waits for
a free sender, and its latency runs from when it was due, so that a
server that backs up cannot hide its delay. `run_loops` runs several of
either under one barrier and one window.

One process, one thread a worker or sender (the threads wait on sockets;
what one computes between being free to send and sending is measured as
`turnaround`). A request under /api/v1/write is a `write`, any other a
`query`: the summary gives each class's numbers under its own prefix."""

from __future__ import annotations

import functools
import statistics
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator

WRITE_PATH = "/api/v1/write"
CLASSES = ("write", "query")


@dataclass
class Request:
    method: str
    path: str
    body: bytes | None = None
    headers: dict | None = None
    meta: object = None  # what the reference needs to know of this request
    units: int = 1       # samples (a write) or 1 (a query)


@dataclass
class Record:
    worker: int
    sent: float          # perf_counter at the send; in an open loop, when it was due
    done: float          # perf_counter at the answer's last byte
    turnaround: float    # from when the sender was free to send this request to the send
    status: int          # 0: no answer (connection failed or timed out)
    body: bytes
    meta: object
    units: int
    sent_bytes: int = 0
    error: str = ""
    cls: str = "query"   # request_class of its path
    late: float | None = None  # open loop: the actual send less when it was due


@dataclass
class Window:
    start: float
    close: float                      # start + seconds
    records: list[Record] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.close - self.start


def request_class(path: str) -> str:
    route = path.split("?", 1)[0]
    return "write" if route == WRITE_PATH or route.startswith(WRITE_PATH + "/") else "query"


def _send(conn, req: Request) -> tuple[int, bytes, str, float]:
    """(status, body, error, perf_counter at the answer's last byte)."""
    status, body, error = 0, b"", ""
    try:
        status, body = conn.request(req.method, req.path, req.body, req.headers)
    except ConnectionError as e:
        error = str(e)
    return status, body, error, time.perf_counter()


@dataclass
class Closed:
    """`workers` closed loops: worker w sends the requests of `requests(w)`,
    each when the last is answered, at most `per_worker` of them."""
    workers: int
    requests: Callable[[int], Iterator[Request]]
    per_worker: int | None = None

    def jobs(self, first: int) -> list[Callable]:
        its = [self.requests(w) for w in range(self.workers)]
        return [functools.partial(self._work, first + w, its[w]) for w in range(self.workers)]

    def _work(self, wid: int, it: Iterator[Request], conn, bounds: dict, out: list) -> None:
        close, last_done, n = bounds["close"], bounds["start"], 0
        while self.per_worker is None or n < self.per_worker:
            req = next(it, None)
            if req is None:
                break
            sent = time.perf_counter()
            if close is not None and sent >= close:
                break
            status, body, error, done = _send(conn, req)
            out.append(Record(wid, sent, done, sent - last_done, status, body, req.meta, req.units,
                              len(req.body or b""), error, request_class(req.path)))
            last_done = done
            n += 1


@dataclass
class Open:
    """One stream at `rate` requests a second from `senders` threads: the
    i-th request is due at start + i / rate; a sender takes the next one
    when it is free, waits for its due time if it is early, and sends it
    at once if it is late. Every request due before the close is sent."""
    rate: float
    senders: int
    requests: Iterable[Request]

    def jobs(self, first: int) -> list[Callable]:
        it, lock, taken = iter(self.requests), threading.Lock(), [0]

        def take() -> tuple[int, Request | None]:
            with lock:  # the stream's order is the schedule's
                i, taken[0] = taken[0], taken[0] + 1
                return i, next(it, None)
        return [functools.partial(self._work, first + s, take) for s in range(self.senders)]

    def _work(self, wid: int, take: Callable, conn, bounds: dict, out: list) -> None:
        start, close, free = bounds["start"], bounds["close"], bounds["start"]
        while True:
            i, req = take()
            due = start + i / self.rate
            if req is None or (close is not None and due >= close):
                return
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent = time.perf_counter()
            status, body, error, done = _send(conn, req)
            out.append(Record(wid, due, done, sent - max(due, free), status, body, req.meta,
                              req.units, len(req.body or b""), error, request_class(req.path),
                              late=sent - due))
            free = done


def run_loops(server, loops: list[Closed | Open], seconds: float | None,
              timeout: float) -> Window:
    """Run every loop under one barrier: the window opens when every
    thread of every loop is ready, and closes `seconds` later (None: when
    each closed worker has sent `per_worker` and each open stream is
    spent). A request sent, or due, before the close is waited for."""
    bounds = {}

    def open_window() -> None:  # runs once, when every thread is ready
        bounds["start"] = time.perf_counter()
        bounds["close"] = None if seconds is None else bounds["start"] + seconds

    jobs: list[Callable] = []
    for lp in loops:
        jobs += lp.jobs(len(jobs))
    barrier = threading.Barrier(len(jobs) + 1, action=open_window)
    out: list[list[Record]] = [[] for _ in jobs]
    raised: list[BaseException] = []

    def work(j: int) -> None:
        conn = server.conn(timeout)
        barrier.wait()
        try:
            jobs[j](conn, bounds, out[j])
        except Exception as e:  # noqa: BLE001 — raised again below, in the caller
            raised.append(e)
        finally:
            conn.close()

    threads = [threading.Thread(target=work, args=(j,), name=f"worker-{j}")
               for j in range(len(jobs))]
    for t in threads:
        t.start()
    barrier.wait()
    for t in threads:
        t.join()
    end = time.perf_counter()
    if raised:
        raise raised[0]
    win = Window(bounds["start"], bounds["close"] if seconds is not None else end)
    for recs in out:
        win.records.extend(recs)
    win.records.sort(key=lambda r: r.sent)
    return win


def closed_loop(server, workers: int, requests: Callable[[int], Iterator[Request]],
                seconds: float | None, timeout: float,
                per_worker: int | None = None) -> Window:
    """Run `workers` closed loops for `seconds` (or `per_worker` requests
    each). A request sent before the close is waited for."""
    return run_loops(server, [Closed(workers, requests, per_worker)], seconds, timeout)


def open_loop(server, rate: float, senders: int, requests: Iterable[Request],
              seconds: float | None, timeout: float) -> Window:
    """Send `requests` at `rate` a second from `senders` threads for
    `seconds` (None: until the stream is spent)."""
    return run_loops(server, [Open(rate, senders, requests)], seconds, timeout)


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q of the
    sample at or below it."""
    s = sorted(values)
    rank = max(1, -(-len(s) * q // 1))  # ceil
    return s[int(rank) - 1]


def ok(r: Record) -> bool:
    return r.status == 200


def share(r: Record, t0: float | None, t1: float | None) -> float:
    """The share of a request's time that lies in [t0, t1] (1 with no span)."""
    if t0 is None:
        return 1.0
    inside = min(r.done, t1) - max(r.sent, t0)
    return max(inside, 0.0) / max(r.done - r.sent, 1e-9)


def latency(win: Window, recs: list[Record]) -> dict:
    """Rate, unit rate, p50, p95, max and turnaround of `recs`, which are
    not empty. A request that failed or was never answered takes the worst
    latency among them."""
    lat = [r.done - r.sent for r in recs]
    worst = max(lat)
    lat_all = [(l if ok(r) else worst) for r, l in zip(recs, lat)]
    in_time = [r for r in recs if ok(r) and r.done <= win.close]
    return {"rate": len(in_time) / win.seconds,
            "unit_rate": sum(r.units for r in in_time) / win.seconds,
            "p50_ms": 1000 * percentile(lat_all, 0.50),
            "p95_ms": 1000 * percentile(lat_all, 0.95),
            "max_ms": 1000 * worst,
            "turnaround_ms": 1000 * statistics.fmean(r.turnaround for r in recs)}


def summary(win: Window, timed: str | None = None) -> dict:
    """What every end-to-end metric and the `client` readers are taken
    from. The counts are over every request; `<class>_*` over each class
    the window holds (`<class>_late_ms`: the p95 of how late an open
    loop's sends fell behind their schedule); the unprefixed latencies and
    rates over the class `timed` names, or over every request where it
    names none, which a window of two classes may not do."""
    recs = win.records
    in_time = [r for r in recs if ok(r) and r.done <= win.close]
    out = {
        "attempted": len(recs),
        "failed": sum(1 for r in recs if not ok(r)),
        "completed_in_window": len(in_time),
        "units_in_window": sum(r.units for r in in_time),
        "units_ok": sum(r.units for r in recs if ok(r)),
        "window_s": win.seconds,
    }
    if timed is not None and timed not in CLASSES:
        raise ValueError(f'the traffic file\'s "timed" is {timed!r}: "write" or "query"')
    by_class = {c: [r for r in recs if r.cls == c] for c in CLASSES}
    by_class = {c: mine for c, mine in by_class.items() if mine}
    if timed is None and len(by_class) > 1:
        raise ValueError('the window holds writes and queries: the traffic file has to name '
                         'the class its end-to-end numbers time, "timed": "write" or "query"')
    for c, mine in by_class.items():
        out.update({f"{c}_{k}": v for k, v in latency(win, mine).items()})
        late = [r.late for r in mine if r.late is not None]
        if late:
            out[f"{c}_late_ms"] = 1000 * percentile(late, 0.95)
    if timed is not None and recs and timed not in by_class:
        raise ValueError(f'the traffic file times {timed!r} and the window holds none')
    if recs:
        out.update(latency(win, by_class[timed] if timed is not None else recs))
    return out
