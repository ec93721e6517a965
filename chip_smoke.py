#!/usr/bin/env python3
"""Bring-up proof: a remote-write fleet and its PromQL served from one chip.

One parent (this process, which never imports JAX) and one child that owns
the chip: `python -m horaedb_tpu.server.main --config <toml>`, the normal
entry point, with docs/example.toml's settings as shipped apart from port
and data directory. The parent

1. builds the C++ remote-write parser from the committed source;
2. generates a TSBS-devops-shaped fleet from --seed (--hosts hosts x the 10
   cpu_* fields, one sample per series every 10 s for --hours) and sends it
   as remote-write requests in time order to /api/v1/write;
3. asks three PromQL queries with ?explain=1 and compares each answer with
   a plain numpy float64 computation on the generated arrays; then the same
   of one more series whose samples (1e300, 1e-300) no f32 exponent holds;
4. calls /compact, waits for it, and repeats the downsample;
5. reads /debug/kernels and /metrics and requires the chip to have done
   the work;
6. stops the child.

Every phase prints one JSON object; the LAST line of stdout is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`
on success. Any failed phase, a child that dies, or a platform other than
`tpu` ends the script non-zero with `"ok": false`.

    python chip_smoke.py                      # one chip, default size
    JAX_PLATFORMS=cpu python chip_smoke.py --hosts 20 --hours 0.5
                                              # rehearsal: every phase
                                              # passes, then exit != 0
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.parse

import numpy as np
import pyarrow as pa

ROOT = os.path.dirname(os.path.abspath(__file__))

# TSBS devops `cpu` measurement: its 10 fields, one Prometheus metric each
CPU_FIELDS = (
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest",
    "usage_guest_nice",
)
# TSBS devops host tags (hostname comes first; choices as in TSBS)
REGIONS = ("us-east-1", "us-west-1", "us-west-2", "eu-west-1", "eu-central-1",
           "ap-southeast-1", "ap-southeast-2", "ap-northeast-1", "sa-east-1")
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCHES = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
ENVIRONMENTS = ("production", "staging", "test")

SCRAPE_MS = 10_000
STEP_S = 300
BASE_MS = 1_767_225_600_000  # 2026-01-01T00:00:00Z: aligned to any segment
# tests/test_promql.py holds the pushdown grid to the raw path at this
# tolerance; max and last value are selections and must be exact
MEAN_RTOL, MEAN_ATOL = 1e-9, 1e-12
# the first query of each shape pays its cold compiles inside the longest
# deadline the server allows (docs/example.toml max_timeout)
FIRST_TIMEOUT_S = 300
# the whole run's own limit: it fails itself before the driver's 1,200 s
BUDGET_S = 1100
AGG_KERNELS = {
    "downsample", "stacked_downsample", "block_sum_count", "block_min_max",
    "scatter_fused", "lane_sum_count", "grouped_stats", "segment_last_value",
    "sharded_downsample", "multisegment_downsample",
}
MERGE_KERNELS = {
    "packed_merge", "sort_perm", "scan_kernel", "index_merge_mask",
    "index_merge_filter", "sample_sort_merge",
}


class Failed(Exception):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def emit(phase: str, t0: float, ok: bool = True, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": ok,
                      "seconds": round(time.perf_counter() - t0, 3), **fields}),
          flush=True)


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------


def make_fleet(seed: int, hosts: int, rounds: int):
    """Labels per series (host-major: a host's 10 fields are scraped
    together) and values[field, host, round] as float64: TSBS's clamped
    random walk in [0, 100]."""
    rng = np.random.default_rng(seed)
    host_tags = []
    for h in range(hosts):
        region = REGIONS[rng.integers(len(REGIONS))]
        host_tags.append({
            "hostname": f"host_{h}",
            "region": region,
            "datacenter": f"{region}{'abc'[rng.integers(3)]}",
            "rack": str(rng.integers(100)),
            "os": OSES[rng.integers(len(OSES))],
            "arch": ARCHES[rng.integers(len(ARCHES))],
            "team": TEAMS[rng.integers(len(TEAMS))],
            "service": str(rng.integers(20)),
            "service_version": str(rng.integers(2)),
            "service_environment": ENVIRONMENTS[rng.integers(len(ENVIRONMENTS))],
        })
    values = np.empty((len(CPU_FIELDS), hosts, rounds), dtype=np.float64)
    x = rng.uniform(0.0, 100.0, size=(len(CPU_FIELDS), hosts))
    for r in range(rounds):
        values[:, :, r] = x
        x = np.clip(x + rng.normal(0.0, 1.0, size=x.shape), 0.0, 100.0)
    return host_tags, values


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _label(name: str, value: str) -> bytes:
    n, v = name.encode(), value.encode()
    msg = b"\x0a" + _varint(len(n)) + n + b"\x12" + _varint(len(v)) + v
    return b"\x0a" + _varint(len(msg)) + msg  # TimeSeries.labels = 1


class RequestTemplate:
    """The wire bytes of one WriteRequest of one scrape round, built once;
    a request then only overwrites the sample values and the timestamp in
    place (prometheus remote.proto: WriteRequest.timeseries=1,
    TimeSeries.labels=1/.samples=2, Sample.value=1 (double)/.timestamp=2)."""

    TS_VARINT = len(_varint(BASE_MS))

    def __init__(self, host_tags: list[dict]):
        sample_len = 1 + 8 + 1 + self.TS_VARINT
        sample_field = 1 + 1 + sample_len  # tag, len, message
        buf = bytearray()
        val_off, ts_off = [], []
        for tags in host_tags:
            tag_bytes = b"".join(_label(n, v) for n, v in sorted(tags.items()))
            for field in CPU_FIELDS:
                labels = _label("__name__", f"cpu_{field}") + tag_bytes
                buf += b"\x0a" + _varint(len(labels) + sample_field)
                buf += labels
                buf += b"\x12" + bytes([sample_len]) + b"\x09"
                val_off.append(len(buf))
                buf += bytes(8) + b"\x10"
                ts_off.append(len(buf))
                buf += bytes(self.TS_VARINT)
        self._buf = np.frombuffer(buf, dtype=np.uint8)
        self._val_idx = np.asarray(val_off)[:, None] + np.arange(8)
        self._ts_idx = np.asarray(ts_off)[:, None] + np.arange(self.TS_VARINT)

    def fill(self, values: np.ndarray, ts_ms: int) -> bytes:
        """values[field, host] of one round and its timestamp -> request
        bytes (series order is host-major)."""
        v = np.ascontiguousarray(values.T).astype("<f8")
        self._buf[self._val_idx] = v.reshape(-1).view(np.uint8).reshape(-1, 8)
        enc = _varint(int(ts_ms))
        require(len(enc) == self.TS_VARINT, "timestamp varint width changed")
        self._buf[self._ts_idx] = np.frombuffer(enc, dtype=np.uint8)
        return self._buf.tobytes()


def check_encoder(host_tags, values) -> None:
    """The hand-written encoder against the protobuf runtime, on one small
    request (importing the pb module does not pull JAX in)."""
    from horaedb_tpu.pb import remote_write_pb2

    req = remote_write_pb2.WriteRequest()
    req.ParseFromString(RequestTemplate(host_tags[:2]).fill(values[:, :2, 1], BASE_MS))
    require(len(req.timeseries) == 2 * len(CPU_FIELDS), "encoder: series count")
    for i, series in enumerate(req.timeseries):
        h, f = divmod(i, len(CPU_FIELDS))
        labels = {lb.name.decode(): lb.value.decode() for lb in series.labels}
        require(labels == {"__name__": f"cpu_{CPU_FIELDS[f]}", **host_tags[h]},
                f"encoder: labels of series {i}")
        got = [(s.timestamp, s.value) for s in series.samples]
        require(got == [(BASE_MS, float(values[f, h, 1]))],
                f"encoder: samples of series {i}")


WIDE_METRIC = "smoke_wide_values"
WIDE_VALUES = (1e300, 2.5e300, 1e-300, 5e-324, 3.5e38, 99.99967667212489)


def send_wide_series(server: "Server", ts: np.ndarray) -> np.ndarray:
    """One more series, one request: WIDE_VALUES in turn at every scrape
    time. Returns its values as [1, rounds]."""
    from horaedb_tpu.pb import remote_write_pb2

    wide = np.resize(np.asarray(WIDE_VALUES), len(ts))
    req = remote_write_pb2.WriteRequest()
    series = req.timeseries.add()
    for name, value in (("__name__", WIDE_METRIC), ("hostname", "host_0")):
        series.labels.add(name=name.encode(), value=value.encode())
    for t, v in zip(ts, wide):
        series.samples.add(timestamp=int(t), value=float(v))
    status, resp = server.request(
        "POST", "/api/v1/write",
        body=pa.Codec("snappy").compress(req.SerializeToString(), asbytes=True),
        headers={"Content-Encoding": "snappy",
                 "Content-Type": "application/x-protobuf"})
    require(status == 200 and json.loads(resp)["samples"] == len(ts),
            f"write of {WIDE_METRIC}: {status} {resp[:300]!r}")
    return wide[None, :]


# ---------------------------------------------------------------------------
# the child server
# ---------------------------------------------------------------------------


class Server:
    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.proc: subprocess.Popen | None = None
        self.port = 0
        self._conn: http.client.HTTPConnection | None = None
        # the whole run's clock: past it every wait fails instead of hanging
        self._deadline = time.monotonic() + BUDGET_S

    def remaining(self) -> float:
        left = self._deadline - time.monotonic()
        require(left > 0, f"the run's own limit of {BUDGET_S} s is spent")
        return left

    def command(self, cfg: str) -> list[str]:
        return [sys.executable, "-m", "horaedb_tpu.server.main", "--config", cfg]

    def start(self) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        with open(os.path.join(ROOT, "docs", "example.toml"), encoding="utf-8") as f:
            toml = f.read()
        data_dir = os.path.join(self.out_dir, "data")
        for old, new in (
            ("port = 5000", f"port = {self.port}"),
            ('data_dir = "/tmp/horaedb-tpu"', f'data_dir = "{data_dir}"'),
        ):
            require(toml.count(old) == 1, f"docs/example.toml: expected one {old!r}")
            toml = toml.replace(old, new)
        cfg = os.path.join(self.out_dir, "server.toml")
        with open(cfg, "w", encoding="utf-8") as f:
            f.write(toml)
        self._log = open(os.path.join(self.out_dir, "server.log"), "wb")
        self.proc = subprocess.Popen(
            self.command(cfg), cwd=ROOT, stdout=self._log, stderr=subprocess.STDOUT,
            env=dict(os.environ),  # JAX_COMPILATION_CACHE_DIR passes unchanged
        )
        while True:
            self.alive()
            try:
                if self.request("GET", "/")[0] == 200:
                    return
            except OSError:
                pass
            self.remaining()
            time.sleep(0.2)

    def alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise Failed(f"the server died (exit code {rc}); see "
                         f"{os.path.join(self.out_dir, 'server.log')}")

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None):
        """(status, body bytes) over one kept-alive connection."""
        for attempt in (0, 1):
            left = self.remaining()
            try:
                if self._conn is None:
                    self._conn = http.client.HTTPConnection("127.0.0.1", self.port)
                if self._conn.sock is None:
                    self._conn.connect()
                self._conn.sock.settimeout(left)
                self._conn.request(method, path, body=body, headers=headers or {})
                resp = self._conn.getresponse()
                return resp.status, resp.read()
            except (OSError, http.client.HTTPException) as e:
                self._conn.close()
                self._conn = None
                self.alive()
                self.remaining()
                if attempt or body is not None:  # a write is never sent twice
                    raise ConnectionError(f"{method} {path.split('?')[0]}: {e!r}") from e

    def get_json(self, path: str, **params):
        if params:
            path += "?" + urllib.parse.urlencode(params)
        status, body = self.request("GET", path)
        require(status == 200, f"GET {path}: {status} {body[:300]!r}")
        return json.loads(body)

    def metrics(self) -> dict[str, float]:
        status, body = self.request("GET", "/metrics")
        require(status == 200, f"GET /metrics: {status}")
        out = {}
        for line in body.decode().splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                out[name] = float(value)
        return out

    def stop(self) -> None:
        if self._conn is not None:
            self._conn.close()
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


def count_files(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def dump_kernels(server: Server, report_dir: str) -> dict:
    k = server.get_json("/debug/kernels")
    with open(os.path.join(report_dir, "kernels.json"), "w", encoding="utf-8") as f:
        json.dump(k, f, indent=1)
    return k


def kernel_totals(server: Server) -> dict:
    k = server.get_json("/debug/kernels")
    return {
        "compiles": sum(e["compiles"] for e in k["kernels"]),
        "compile_seconds": round(sum(e["compile_seconds"] for e in k["kernels"]), 3),
    }


# ---------------------------------------------------------------------------
# queries and their float64 references
# ---------------------------------------------------------------------------


def window_reduce(values: np.ndarray, ts: np.ndarray, steps: np.ndarray, fn):
    """fn over the samples in [t - step, t) per step t (the engine's
    documented window, promql/eval.py): values[..., rounds] -> [..., steps];
    NaN where a window holds no sample."""
    out = np.full(values.shape[:-1] + (len(steps),), np.nan)
    for i, t in enumerate(steps):
        sel = (ts >= t - STEP_S * 1000) & (ts < t)
        if sel.any():
            out[..., i] = fn(values[..., sel], axis=-1)
    return out


def ask(server: Server, path: str, params: dict) -> tuple[dict, float]:
    t0 = time.perf_counter()
    body = server.get_json(path, explain=1, **params)
    require(body.get("status") == "success", f"{path}: {str(body)[:300]}")
    return body, time.perf_counter() - t0


def by_host(body: dict) -> dict[str, list]:
    out = {}
    for series in body["data"]["result"]:
        host = series["metric"]["hostname"]
        require(host not in out, f"two result series for {host}")
        out[host] = series.get("values") or [series["value"]]
    return out


def compare(got: dict[str, list], want: np.ndarray, hosts: list[int],
            steps: np.ndarray, exact: bool, what: str) -> None:
    """Equal bucket sets and values: want[len(hosts), len(steps)]."""
    names = {f"host_{h}" for h in hosts}
    require(set(got) == names,
            f"{what}: hosts differ ({len(got)} answered, {len(names)} expected)")
    for row, h in enumerate(hosts):
        pairs = got[f"host_{h}"]
        w = want[row]
        present = ~np.isnan(w)
        got_ts = np.asarray([round(float(p[0]) * 1000) for p in pairs], dtype=np.int64)
        require(np.array_equal(got_ts, steps[present]),
                f"{what}: bucket set of host_{h} differs")
        got_v = np.asarray([float(p[1]) for p in pairs])
        if exact:
            good = np.array_equal(got_v, w[present])
        else:
            good = np.allclose(got_v, w[present], rtol=MEAN_RTOL, atol=MEAN_ATOL)
        require(good, f"{what}: values of host_{h} differ from the float64 reference")


def explain_summary(body: dict) -> dict:
    ex = body.get("explain") or {}
    serving = ex.get("serving") or {}
    return {
        "scan_paths": ex.get("scan_paths"),
        "agg_impl": ex.get("agg_impl"),
        "kernels": [e["kernel"] for e in ex.get("kernels") or []],
        "bound": ex.get("bound"),
        "compile_s": ex.get("compile_s"),
        "steady_s": ex.get("steady_s"),
        "stages_s": ex.get("stages_s"),
        "ssts_read": (ex.get("ssts") or {}).get("read"),
        "cache": serving.get("cache"),
        "rollup": serving.get("rollup"),
    }


def stage_seconds(metrics: dict) -> dict:
    out = {}
    for key, value in metrics.items():
        if key.startswith("horaedb_scan_stage_seconds_sum{"):
            stage = key.split('stage="')[1].split('"')[0]
            count = metrics[key.replace("_sum{", "_count{")]
            if count:
                out[stage] = [round(value, 4), int(count)]
    return out


def settle_compaction(server: Server, before: dict) -> dict:
    """Wait until every task the picker queued has finished and a later
    pick found nothing more to do."""
    def counts(m):
        return (m.get('horaedb_compaction_picks_total{outcome="queued"}', 0.0),
                m.get('horaedb_compactions_total{result="ok"}', 0.0),
                m.get('horaedb_compactions_total{result="error"}', 0.0),
                m.get('horaedb_compaction_picks_total{outcome="empty"}', 0.0))
    empty0 = counts(before)[3]
    last, since = None, time.monotonic()
    while True:
        server.alive()
        now = counts(server.metrics())
        queued, ok, err, empty = now
        require(err == 0, f"{int(err)} compaction task(s) failed")
        if now != last:
            last, since = now, time.monotonic()
        if queued == ok and empty > empty0 and time.monotonic() - since >= 2.0:
            return {"tasks_done": int(ok - counts(before)[1])}
        server.remaining()
        time.sleep(0.5)


# ---------------------------------------------------------------------------


def run(args, server: Server) -> dict:
    rounds = int(round(args.hours * 3600 * 1000 / SCRAPE_MS))
    require(rounds >= 2 * STEP_S * 1000 // SCRAPE_MS, "--hours too small")
    n_series = args.hosts * len(CPU_FIELDS)

    # 1. the parser, from the committed source
    t0 = time.perf_counter()
    native = os.path.join(ROOT, "horaedb_tpu", "native")
    built = subprocess.run(["make", "-C", native, "clean", "all"],
                           capture_output=True, text=True)
    require(built.returncode == 0, f"native parser build failed:\n{built.stderr}")
    require(os.path.exists(os.path.join(native, "libremote_write.so")),
            "native parser build left no library")
    emit("build", t0, target="horaedb_tpu/native/libremote_write.so")

    # the child
    t0 = time.perf_counter()
    server.start()
    info = server.get_json("/api/v1/status/buildinfo")["data"]
    require(info.get("parser_backend") == "native",
            f"server parses with {info.get('parser_backend')!r}, not the native parser")
    k = server.get_json("/debug/kernels")
    cache_dir = k["compile_cache_dir"]
    cache_files = count_files(cache_dir)
    emit("start", t0, parser_backend=info["parser_backend"],
         platform=k["platform"], device_kind=k["device_kind"],
         device_count=k["device_count"], compile_cache_dir=cache_dir,
         compile_cache_warm=cache_files > 0, compile_cache_files=cache_files)

    # 2. the fleet, over HTTP in time order
    t0 = time.perf_counter()
    host_tags, values = make_fleet(args.seed, args.hosts, rounds)
    ts = BASE_MS + SCRAPE_MS * np.arange(rounds, dtype=np.int64)
    check_encoder(host_tags, values)
    gen_s = time.perf_counter() - t0
    tmpl = RequestTemplate(host_tags)
    sent = wire_bytes = 0
    t_ingest = time.perf_counter()
    for r in range(rounds):  # one request per scrape round
        raw = tmpl.fill(values[:, :, r], ts[r])
        body = pa.Codec("snappy").compress(raw, asbytes=True)
        status, resp = server.request(
            "POST", "/api/v1/write", body=body,
            headers={"Content-Encoding": "snappy",
                     "Content-Type": "application/x-protobuf"})
        require(status == 200, f"write {r}: {status} {resp[:300]!r}")
        acked = json.loads(resp)["samples"]
        require(acked == n_series, f"write {r}: {acked} samples acknowledged")
        sent += acked
        wire_bytes += len(body)
    ingest_s = time.perf_counter() - t_ingest
    emit("ingest", t0, series=n_series, samples=sent, requests=rounds,
         wire_bytes=wire_bytes, generate_seconds=round(gen_s, 3),
         ingest_seconds=round(ingest_s, 3),
         samples_per_second=round(sent / ingest_s, 1))

    # 3. three queries; the first of each shape may pay its compiles inside
    # the longest deadline the server allows, the same shape asked again
    # must hold under the default one
    end_s = (BASE_MS + rounds * SCRAPE_MS) // 1000
    all_hosts = list(range(args.hosts))
    rng = np.random.default_rng(args.seed + 1)
    one, other = (int(h) for h in rng.choice(args.hosts, size=2, replace=False))
    long_timeout = f"{FIRST_TIMEOUT_S}s"

    def range_query(expr, fn, source, hosts, start_s, exact, what, **extra):
        steps = 1000 * np.arange(start_s, end_s + 1, STEP_S, dtype=np.int64)
        body, secs = ask(server, "/api/v1/query_range", {
            "query": expr, "start": start_s, "end": end_s,
            "step": f"{STEP_S}s", **extra})
        want = window_reduce(source[hosts], ts, steps, fn)
        compare(by_host(body), want, hosts, steps, exact, what)
        return body, secs

    def instant_query(metric, source, hosts, what, **extra):
        at_s = end_s
        body, secs = ask(server, "/api/v1/query", {
            "query": metric, "time": at_s, **extra})
        # the last sample at or before `time`, within the 5 m lookback
        compare(by_host(body), source[hosts, -1:], hosts,
                np.asarray([at_s * 1000], dtype=np.int64), True, what)
        return body, secs

    def phase(name, first, *again):
        t0 = time.perf_counter()
        totals = [kernel_totals(server)]
        asked = []
        for query in (first, *again):
            asked.append(query())
            totals.append(kernel_totals(server))
        report = {}
        for key, (body, secs), k0, k1, timeout in zip(
            ("first", "again", "third"), asked, totals, totals[1:],
            (long_timeout, "default", "default")
        ):
            report[key] = {
                "seconds": round(secs, 3), "timeout": timeout,
                # process-wide: background compaction's compiles land here
                # too; `compile_s` below is the query's own
                "server_compiles": k1["compiles"] - k0["compiles"],
                "server_compile_seconds": round(
                    k1["compile_seconds"] - k0["compile_seconds"], 3),
                **explain_summary(body),
            }
        emit(name, t0, **report)
        return asked[0][0]

    hour_start = end_s - 3600
    phase(
        "query_groupby_1_1_1",
        lambda: range_query(
            f'max_over_time(cpu_usage_user{{hostname="host_{one}"}}[5m])',
            np.max, values[0], [one], hour_start, True, "max_over_time, one host",
            timeout=long_timeout),
        lambda: range_query(
            f'max_over_time(cpu_usage_user{{hostname="host_{other}"}}[5m])',
            np.max, values[0], [other], hour_start, True,
            "max_over_time, another host"),
    )
    window_start = BASE_MS // 1000 + STEP_S
    downsample = phase(
        "query_downsample_all_hosts",
        lambda: range_query(
            "avg_over_time(cpu_usage_user[5m])", np.mean, values[0], all_hosts,
            window_start, False, "avg_over_time, every host",
            timeout=long_timeout),
        lambda: range_query(
            "avg_over_time(cpu_usage_system[5m])", np.mean, values[1], all_hosts,
            window_start, False, "avg_over_time, every host, another metric"),
    )
    phase(
        "query_lastpoint",
        lambda: instant_query("cpu_usage_user", values[0], all_hosts,
                              "last value, every host", timeout=long_timeout),
        lambda: instant_query("cpu_usage_system", values[1], all_hosts,
                              "last value, every host, another metric"),
    )
    # one series of samples no f32 exponent holds: an accelerator carries
    # f64 as a pair of f32, so these come back right only if the server
    # keeps its selections on integer lanes and such sums on the host
    wide = send_wide_series(server, ts)
    phase(
        "query_wide_values",
        lambda: range_query(
            f"avg_over_time({WIDE_METRIC}[5m])", np.mean, wide, [0],
            hour_start, False, "avg_over_time of 1e300s", timeout=long_timeout),
        lambda: range_query(
            f"max_over_time({WIDE_METRIC}[5m])", np.max, wide, [0],
            hour_start, True, "max_over_time of 1e300s"),
        lambda: instant_query(WIDE_METRIC, wide, [0], "last value of 1e300s"),
    )

    # 4. compaction, and the downsample again: the same answer
    t0 = time.perf_counter()
    before = server.metrics()
    server.get_json("/compact")
    settled = settle_compaction(server, before)
    body, secs = range_query(
        "avg_over_time(cpu_usage_user[5m])", np.mean, values[0], all_hosts,
        window_start, False, "avg_over_time after compaction")
    got, was = by_host(body), by_host(downsample)
    for host, pairs in was.items():
        a = np.asarray([float(p[1]) for p in pairs])
        b = np.asarray([float(p[1]) for p in got[host]])
        require(np.allclose(a, b, rtol=MEAN_RTOL, atol=MEAN_ATOL),
                f"{host}: the answer changed across the compaction")
    emit("compact", t0, **settled, query_seconds=round(secs, 3),
         **explain_summary(body))

    # 5. the chip did the work
    t0 = time.perf_counter()
    k = dump_kernels(server, args.report_dir)
    compiled = {e["kernel"]: e["compiles"] for e in k["kernels"] if e["compiles"]}
    metrics = server.metrics()
    device_merges = sum(v for k2, v in metrics.items() if k2.startswith(
        'horaedb_scan_path_total{path="device_merge'))
    host_merges = metrics.get('horaedb_scan_path_total{path="host_merge"}', 0.0)
    device = {"platform": k["platform"], "kind": k["device_kind"],
              "count": k["device_count"]}
    emit("kernels", t0, **device, kernels_compiled=compiled,
         compile_seconds={e["kernel"]: e["compile_seconds"]
                          for e in k["kernels"] if e["compiles"]},
         compile_seconds_total=kernel_totals(server)["compile_seconds"],
         scan_path_device=device_merges, scan_path_host=host_merges,
         # every scan and compaction of the run, compiles deducted:
         # stage -> [seconds, times entered]
         scan_stage_seconds=stage_seconds(metrics),
         compile_cache_dir=cache_dir, compile_cache_files=count_files(cache_dir))
    require(device["platform"] == "tpu",
            f"the server ran on platform {device['platform']!r}, not on a TPU")
    require(AGG_KERNELS & set(compiled), "no aggregation kernel was compiled")
    require(MERGE_KERNELS & set(compiled), "no merge/sort kernel was compiled")
    require(device_merges > 0, "no merge ran on the device "
            '(horaedb_scan_path_total{path="device_merge*"} is 0)')
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hosts", type=int, default=1000)
    ap.add_argument("--hours", type=float, default=2.0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".chip_smoke"),
                    help="scratch directory (emptied first): the server's "
                         "config, data and log")
    ap.add_argument("--report-dir",
                    default=os.path.join(ROOT, "chiprun_out", "chip_smoke"),
                    help="where kernels.json and the end of the server log go")
    args = ap.parse_args()

    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    os.makedirs(args.report_dir, exist_ok=True)
    server = Server(args.out)
    device = None
    t_run = time.perf_counter()
    try:
        device = run(args, server)
    except Exception as e:  # noqa: BLE001 — every failure ends the run
        emit("failed", t_run, ok=False, error=f"{type(e).__name__}: {e}")
        if server.proc is not None and server.proc.poll() is None:
            try:  # diagnostics only: what had been compiled when it failed
                dump_kernels(server, args.report_dir)
            except (OSError, Failed, http.client.HTTPException):
                pass
    t0 = time.perf_counter()
    server.stop()
    log = os.path.join(args.out, "server.log")
    if os.path.exists(log):
        with open(log, "rb") as f:
            f.seek(max(0, os.path.getsize(log) - 256 * 1024))
            tail = f.read()
        with open(os.path.join(args.report_dir, "server.log.tail"), "wb") as f:
            f.write(tail)
    emit("stop", t0, server_exit_code=server.proc.returncode if server.proc else None,
         total_seconds=round(time.perf_counter() - t_run, 3))
    if "jax" in sys.modules:  # the parent must never hold the chip
        emit("failed", t0, ok=False, error="the parent imported JAX")
        device = None
    if device is None:
        print(json.dumps({"ok": False}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
