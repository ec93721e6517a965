"""The client's request classes, the open loop and the run's clean-up.

A stdlib HTTP server in this process answers /api/v1/write and
/api/v1/query_range after set delays, and some requests with a 500: the
summary gives each class its own numbers, a one-class window the same
fields as the summary before classes existed (its copy below is the
reference), the open loop times a request from when it was due, the span
sampling keeps every root name, and a run leaves no process and no
thread behind."""

import json
import os
import socket
import statistics
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from bench_chip import child, loop, run
from bench_chip.readers import client, span_self

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
TINY_FLEET = ["--set", "targets=2", "--set-traffic", "warm_seconds=1"]


def summary_before_classes(win: loop.Window) -> dict:
    """loop.summary as it was before request classes, kept as the reference."""
    recs = win.records
    lat = [r.done - r.sent for r in recs]
    worst = max(lat, default=0.0)
    lat_all = [(l if loop.ok(r) else worst) for r, l in zip(recs, lat)]
    in_time = [r for r in recs if loop.ok(r) and r.done <= win.close]
    out = {
        "attempted": len(recs),
        "failed": sum(1 for r in recs if not loop.ok(r)),
        "completed_in_window": len(in_time),
        "units_in_window": sum(r.units for r in in_time),
        "units_ok": sum(r.units for r in recs if loop.ok(r)),
        "window_s": win.seconds,
    }
    if recs:
        out["rate"] = len(in_time) / win.seconds
        out["unit_rate"] = out["units_in_window"] / win.seconds
        out["p50_ms"] = 1000 * loop.percentile(lat_all, 0.50)
        out["p95_ms"] = 1000 * loop.percentile(lat_all, 0.95)
        out["max_ms"] = 1000 * worst
        out["turnaround_ms"] = 1000 * statistics.fmean(r.turnaround for r in recs)
    return out


class Behaviour:
    """What the fake server does: a delay a class, a 500 every `fail_every`
    requests of a class, and one stall on the n-th request of all."""

    def __init__(self, write_s=0.002, query_s=0.010, fail_every=None, stall_at=None, stall_s=0.0):
        self.delay = {"write": write_s, "query": query_s}
        self.fail_every = fail_every or {}
        self.stall_at, self.stall_s = stall_at, stall_s
        self.seen = {"write": 0, "query": 0, "all": 0}
        self.lock = threading.Lock()

    def answer(self, path: str) -> int:
        cls = loop.request_class(path)
        with self.lock:
            self.seen[cls] += 1
            self.seen["all"] += 1
            n, n_all = self.seen[cls], self.seen["all"]
        time.sleep(self.delay[cls] + (self.stall_s if n_all == self.stall_at else 0.0))
        every = self.fail_every.get(cls)
        return 500 if every and n % every == 0 else 200


@pytest.fixture
def fake():
    behaviour = Behaviour()

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def setup(self):
            super().setup()
            self.request.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

        def log_message(self, *args):
            pass

        def reply(self):
            length = int(self.headers.get("Content-Length") or 0)
            if length:
                self.rfile.read(length)
            status = behaviour.answer(self.path)
            body = b'{"status": "success"}'
            self.send_response(status)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        do_GET = do_POST = reply

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    httpd.daemon_threads = True
    serving = threading.Thread(target=httpd.serve_forever, daemon=True)
    serving.start()

    class Server:
        behaviour_ = behaviour

        def conn(self, timeout):
            return child.Conn(httpd.server_address[1], timeout)

    yield Server()
    httpd.shutdown()
    httpd.server_close()
    serving.join(10)


def writes(w=0):
    return (loop.Request("POST", "/api/v1/write", b"x" * 100, units=2000) for _ in iter(int, 1))


def queries(w=0):
    return (loop.Request("GET", "/api/v1/query_range?query=up&start=1&end=2&step=1")
            for _ in iter(int, 1))


def rec(cls, sent, latency, status=200, units=1, turnaround=0.001):
    return loop.Record(0, sent, sent + latency, turnaround, status, b"", None, units, cls=cls)


def test_request_class_is_taken_from_the_path():
    assert loop.request_class("/api/v1/write") == "write"
    assert loop.request_class("/api/v1/write?x=1") == "write"
    assert loop.request_class("/api/v1/query_range?query=up") == "query"
    assert loop.request_class("/api/v1/writes") == "query"


def test_each_class_has_its_own_percentiles_and_worst_latency():
    """Hand-made records: a failed query takes the worst query latency,
    not the window's worst, which a write holds."""
    win = loop.Window(0.0, 10.0)
    win.records = ([rec("write", 0.1 * i, 0.010 + 0.001 * i, units=2000) for i in range(20)]
                   + [rec("write", 3.0, 5.0, units=2000)]                 # the window's worst
                   + [rec("query", 0.2 * i, 0.100 + 0.010 * i) for i in range(10)]
                   + [rec("query", 4.0, 0.050, status=500)])              # failed: the worst query
    win.records.sort(key=lambda r: r.sent)
    s = loop.summary(win, "write")
    w_lat = sorted([0.010 + 0.001 * i for i in range(20)] + [5.0])
    assert s["write_p50_ms"] == pytest.approx(1000 * w_lat[10])
    assert s["write_p95_ms"] == pytest.approx(1000 * w_lat[19])
    assert s["write_max_ms"] == pytest.approx(5000.0)
    q_worst = 0.100 + 0.010 * 9
    q_lat = sorted([0.100 + 0.010 * i for i in range(10)] + [q_worst])
    assert s["query_p50_ms"] == pytest.approx(1000 * q_lat[5])
    assert s["query_p95_ms"] == pytest.approx(1000 * q_worst)
    assert s["query_max_ms"] == pytest.approx(1000 * q_worst)
    assert s["query_rate"] == pytest.approx(10 / 10.0) and s["write_rate"] == pytest.approx(21 / 10.0)
    assert s["write_unit_rate"] == pytest.approx(21 * 2000 / 10.0)
    # the counts are over both classes; the unprefixed fields are the timed class's
    assert (s["attempted"], s["failed"], s["completed_in_window"]) == (32, 1, 31)
    assert s["units_ok"] == 21 * 2000 + 10
    for k in ("p50_ms", "p95_ms", "max_ms", "rate", "unit_rate", "turnaround_ms"):
        assert s[k] == s["write_" + k]
    assert loop.summary(win, "query")["p95_ms"] == s["query_p95_ms"]


def test_two_classes_measured_together(fake):
    fake.behaviour_.fail_every = {"query": 7, "write": 11}
    fake.behaviour_.delay = {"write": 0.002, "query": 0.030}
    win = loop.run_loops(fake, [loop.Closed(2, queries), loop.Open(100.0, 4, writes())], 1.0, 10.0)
    s = loop.summary(win, "write")
    ws = [r for r in win.records if r.cls == "write"]
    qs = [r for r in win.records if r.cls == "query"]
    assert len(ws) == 100 and len(qs) > 20
    assert s["attempted"] == len(ws) + len(qs)
    assert s["failed"] == sum(1 for r in win.records if r.status == 500) > 0
    assert {r.status for r in ws} == {200, 500} and {r.status for r in qs} == {200, 500}
    # each class's numbers are the one-class summary of its records alone
    for cls, mine in (("write", ws), ("query", qs)):
        alone = loop.Window(win.start, win.close, mine)
        ref = summary_before_classes(alone)
        for k in ("p50_ms", "p95_ms", "max_ms", "rate", "unit_rate", "turnaround_ms"):
            assert s[f"{cls}_{k}"] == ref[k], (cls, k)
    assert s["write_p50_ms"] < 25.0 < s["query_p50_ms"]
    assert "write_late_ms" in s and "query_late_ms" not in s
    # one barrier: both classes inside one window
    assert min(r.sent for r in win.records) >= win.start and max(r.sent for r in ws) < win.close
    assert max(r.sent for r in qs) < win.close


@pytest.mark.parametrize("make", [queries, writes])
def test_one_class_gives_the_fields_of_before_bit_for_bit(fake, make):
    fake.behaviour_.fail_every = {"query": 5, "write": 5}
    win = loop.closed_loop(fake, 3, make, 0.6, 10.0)
    before, now = summary_before_classes(win), loop.summary(win)
    assert before["failed"] > 0
    for k, v in before.items():
        assert now[k] == v, k
    cls = loop.request_class(make().__next__().path)
    for k in ("p50_ms", "p95_ms", "max_ms", "rate", "unit_rate", "turnaround_ms"):
        assert now[f"{cls}_{k}"] == before[k]


def test_the_write_cells_p95_reads_per_layer_from_its_writes(fake):
    """write_ack_p95_ms is end to end in the query cell alone; in
    rwfleet.catchup the same number reads per layer, from the write class."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "rwfleet.catchup" not in e2e["write_ack_p95_ms"]["workloads"]
    m = next(m for m in bench["per_layer"] if m["name"] == "client.ack_p95_ms.write")
    assert m["workloads"] == ["rwfleet.catchup"] and m["moves"] == "write_ack_p50_ms"
    with open(os.path.join(HERE, "layer_metrics", m["name"] + ".json"), encoding="utf-8") as f:
        spec = json.load(f)
    win = loop.closed_loop(fake, 3, writes, 0.6, 10.0)
    s = loop.summary(win)
    assert client.read(spec, {"summary": s}) == s["p95_ms"] == summary_before_classes(win)["p95_ms"]


def test_two_classes_without_timed_fail():
    win = loop.Window(0.0, 1.0, [rec("write", 0.1, 0.01), rec("query", 0.2, 0.02)])
    with pytest.raises(ValueError, match='"timed"'):
        loop.summary(win)
    with pytest.raises(ValueError, match='"timed"'):
        loop.summary(win, "writes")
    only_queries = loop.Window(0.0, 1.0, [rec("query", 0.2, 0.02)])
    with pytest.raises(ValueError, match="holds none"):
        loop.summary(only_queries, "write")
    assert loop.summary(loop.Window(0.0, 1.0), "write")["attempted"] == 0


def test_open_loop_keeps_its_rate_and_times_from_the_schedule(fake):
    """One sender, and the server stalls 0.4 s on the 10th request: the
    ones due meanwhile go late, and their latency counts the wait."""
    fake.behaviour_.stall_at, fake.behaviour_.stall_s = 10, 0.4
    win = loop.open_loop(fake, 50.0, 1, writes(), 1.0, 10.0)
    recs = win.records
    assert len(recs) == 50  # every request due before the close, and no other
    assert [r.sent for r in recs] == [win.start + i / 50.0 for i in range(50)]
    after = recs[10]  # due 0.02 s after the stalled one, sent when it answered
    assert after.late > 0.3 and after.done - after.sent > 0.3
    assert all(r.late >= 0 for r in recs) and recs[0].late < 0.05
    s = loop.summary(win)
    assert s["write_late_ms"] > 200.0 and s["p95_ms"] > 300.0
    assert s["rate"] == pytest.approx(50.0, rel=0.1)


def test_open_loop_on_time_where_senders_suffice(fake):
    win = loop.open_loop(fake, 100.0, 4, writes(), 0.5, 10.0)
    s = loop.summary(win)
    assert s["attempted"] == 50 and s["failed"] == 0
    assert s["write_late_ms"] < 20.0


class ListedServer:
    """/debug/traces as the ring lists it: 40 writes for each query, newest first."""

    def __init__(self, queries: int):
        self.listed = []
        for q in range(queries):
            self.listed += [{"trace_id": f"w{q}-{i}", "name": "POST /api/v1/write"} for i in range(40)]
            self.listed.append({"trace_id": f"q{q}", "name": "GET /api/v1/query_range"})
        self.asked = []

    def get_json(self, path, **params):
        self.asked.append((path, params))
        if path == "/debug/traces":
            return {"traces": self.listed}
        tid = path.rsplit("/", 1)[1]
        name = next(t["name"] for t in self.listed if t["trace_id"] == tid)
        return {"trace_id": tid, "root": {"name": name, "start_ms": 0.0, "duration_s": 0.2,
                                          "attrs": {"stages": {"io_decode": 0.05}}, "children": []}}


def test_span_trees_keep_each_root_name():
    server = ListedServer(queries=6)
    trees = run.span_trees(server)
    assert server.asked[0] == ("/debug/traces", {"limit": 0})  # the whole ring
    names = [t["root"]["name"] for t in trees]
    assert names.count("POST /api/v1/write") == run.SPAN_SAMPLE
    assert names.count("GET /api/v1/query_range") == 6
    with open(os.path.join(HERE, "layer_metrics", "promql.self_ms.json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert span_self.read(spec, {"trees": trees}) == pytest.approx(150.0)
    # the newest SPAN_SAMPLE trees alone would have held one query at most
    assert sum(1 for t in server.listed[:run.SPAN_SAMPLE] if t["name"].startswith("GET")) <= 1


def alive_in_group(pgid: int) -> list[int]:
    """Pids of live (not zombie) processes in process group `pgid`."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat", encoding="utf-8") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if fields[0] != "Z" and int(fields[2]) == pgid:
            out.append(int(pid))
    return out


def test_a_rehearsal_leaves_no_process_and_no_thread(tmp_path, monkeypatch):
    started = []
    real = child.Server.start

    def start(self, *a, **kw):
        real(self, *a, **kw)
        started.append(self.proc.pid)
    monkeypatch.setattr(child.Server, "start", start)
    before = set(threading.enumerate())
    result, why = run.run_cell(["--workload", "rwfleet.catchup", "--seed", str(2**31 + 4242),
                                "--seconds", "2", "--trace", "0", "--out", str(tmp_path / "run"),
                                *TINY_FLEET])
    assert result is not None and "platform 'cpu'" in why, why
    assert result["correct"] is True
    assert len(started) == 2  # the window's child and the one the check restarts
    for pid in started:
        assert alive_in_group(pid) == [], pid
    assert run.stray_threads(before) == []


def test_a_thread_left_alive_fails_the_run(tmp_path, monkeypatch):
    release = threading.Event()

    def leaves_a_thread(args, server, out_dir):
        threading.Thread(target=release.wait, name="left-behind").start()
        return {"device": {"platform": "tpu", "count": 1}}, 1
    monkeypatch.setattr(run, "run", leaves_a_thread)
    try:
        result, why = run.run_cell(["--workload", "rwfleet.catchup", "--seed", "1", "--seconds", "1",
                                    "--trace", "0", "--out", str(tmp_path / "run")])
        assert result is None and "left-behind" in why and "still alive" in why
    finally:
        release.set()


def test_a_server_whose_benchmark_died_ends_itself(tmp_path):
    """The benchmark is SIGKILLed with its server up: the server's own
    watch ends the server's session within seconds."""
    script = ("import os, sys; sys.path.insert(0, %r)\n"
              "from bench_chip.child import Server\n"
              "s = Server(%r, 300.0); s.start()\n"
              "print(s.proc.pid, flush=True); os._exit(0)\n") % (ROOT, str(tmp_path))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    pid = int(r.stdout.split()[-1])
    end = time.monotonic() + 15.0
    while alive_in_group(pid) and time.monotonic() < end:
        time.sleep(0.2)
    assert alive_in_group(pid) == []
