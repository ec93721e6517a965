"""The closed loop: n workers, each sending its next request when the
last is answered, and the arithmetic on what they recorded.

One process, one thread a worker (the threads wait on sockets; what they
compute between two sends is measured as `turnaround`)."""

from __future__ import annotations

import statistics
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator


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
    sent: float          # perf_counter at the send
    done: float          # perf_counter at the answer's last byte
    turnaround: float    # from the last answer's last byte to this send
    status: int          # 0: no answer (connection failed or timed out)
    body: bytes
    meta: object
    units: int
    sent_bytes: int = 0
    error: str = ""


@dataclass
class Window:
    start: float
    close: float                      # start + seconds
    records: list[Record] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.close - self.start


def closed_loop(server, workers: int, requests: Callable[[int], Iterator[Request]],
                seconds: float | None, timeout: float,
                per_worker: int | None = None) -> Window:
    """Run `workers` closed loops for `seconds` (or `per_worker` requests
    each). A request sent before the close is waited for."""
    bounds = {}

    def open_window() -> None:  # runs once, when every worker is ready
        bounds["start"] = time.perf_counter()
        bounds["close"] = None if seconds is None else bounds["start"] + seconds

    barrier = threading.Barrier(workers + 1, action=open_window)
    out: list[list[Record]] = [[] for _ in range(workers)]
    its = [requests(w) for w in range(workers)]
    raised: list[BaseException] = []

    def work(w: int) -> None:
        conn = server.conn(timeout)
        it = its[w]
        barrier.wait()
        close = bounds["close"]
        last_done = bounds["start"]
        n = 0
        try:
            while per_worker is None or n < per_worker:
                req = next(it, None)
                if req is None:
                    break
                sent = time.perf_counter()
                if close is not None and sent >= close:
                    break
                status, body, error = 0, b"", ""
                try:
                    status, body = conn.request(req.method, req.path, req.body, req.headers)
                except ConnectionError as e:
                    error = str(e)
                done = time.perf_counter()
                out[w].append(Record(w, sent, done, sent - last_done, status, body,
                                     req.meta, req.units, len(req.body or b""), error))
                last_done = done
                n += 1
        except Exception as e:  # noqa: BLE001 — raised again below, in the caller
            raised.append(e)
        finally:
            conn.close()

    threads = [threading.Thread(target=work, args=(w,), name=f"worker-{w}")
               for w in range(workers)]
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


def summary(win: Window) -> dict:
    """What every end-to-end metric and the `client` readers are taken
    from. A request that failed or was never answered counts in `failed`
    and takes the window's worst latency."""
    recs = win.records
    lat = [r.done - r.sent for r in recs]
    worst = max(lat, default=0.0)
    lat_all = [(l if ok(r) else worst) for r, l in zip(recs, lat)]
    in_time = [r for r in recs if ok(r) and r.done <= win.close]
    out = {
        "attempted": len(recs),
        "failed": sum(1 for r in recs if not ok(r)),
        "completed_in_window": len(in_time),
        "units_in_window": sum(r.units for r in in_time),
        "units_ok": sum(r.units for r in recs if ok(r)),
        "window_s": win.seconds,
    }
    if recs:
        out["rate"] = len(in_time) / win.seconds
        out["unit_rate"] = out["units_in_window"] / win.seconds
        out["p50_ms"] = 1000 * percentile(lat_all, 0.50)
        out["p95_ms"] = 1000 * percentile(lat_all, 0.95)
        out["max_ms"] = 1000 * worst
        out["turnaround_ms"] = 1000 * statistics.fmean(r.turnaround for r in recs)
    return out
