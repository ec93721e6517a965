"""The one child: the normal server on the cell's chip.

docs/example.toml as shipped apart from port and data directory, started
through bench_chip/trace/serve_traced.py, which calls
horaedb_tpu.server.main.main() in the same process and adds a side thread
that answers two things only the process holding the chip can: the
device's memory statistics, and a jax.profiler trace on request. The
parent (this process) never imports JAX. Taken from chip_smoke.py's
Server; connections are per caller so that several workers can send.
The child leads a session of its own: a stop signals the whole session,
the server and whatever it started, and the child ends itself if this
process dies first (serve_traced.py watches for that).
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.parse

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Failed(Exception):
    """A phase of the run failed; the run prints no result."""


def require(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def parse_metrics(text: str) -> dict[str, float]:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


class Conn:
    """One kept-alive connection; one caller at a time."""

    def __init__(self, port: int, timeout: float):
        self.port, self.timeout = port, timeout
        self._c: http.client.HTTPConnection | None = None

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None, timeout: float | None = None):
        """(status, body). A request is never sent twice: a connection
        that fails mid-request raises ConnectionError."""
        if self._c is None:
            self._c = http.client.HTTPConnection("127.0.0.1", self.port)
        try:
            if self._c.sock is None:
                self._c.connect()
                self._c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._c.sock.settimeout(timeout or self.timeout)
            self._c.request(method, path, body=body, headers=headers or {})
            resp = self._c.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException) as e:
            self.close()
            raise ConnectionError(f"{method} {path.split('?')[0]}: {e!r}") from e

    def close(self) -> None:
        if self._c is not None:
            self._c.close()
            self._c = None


class Server:
    def __init__(self, out_dir: str, budget_s: float):
        self.out_dir = out_dir
        self.proc: subprocess.Popen | None = None
        self._swept = False  # the child's session has had its last SIGKILL
        self.port = 0
        self._admin: Conn | None = None
        self._log = None
        self._fifo = os.path.join(out_dir, "control.fifo")
        self._replies = 0
        self._deadline = time.monotonic() + budget_s
        self._budget_s = budget_s

    # -- life ---------------------------------------------------------------

    def remaining(self) -> float:
        left = self._deadline - time.monotonic()
        require(left > 0, f"the run's own limit of {self._budget_s} s is spent")
        return left

    def write_config(self) -> str:
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
        return cfg

    def start(self, extra_toml: str = "") -> None:
        """Boot the child and wait for its health check. `extra_toml` is
        for the controls under bench_chip/tests only: a cell's run never
        passes it."""
        cfg = os.path.join(self.out_dir, "server.toml")
        if not os.path.exists(cfg):
            cfg = self.write_config()
            if extra_toml:
                with open(cfg, encoding="utf-8") as f:
                    toml = f.read()
                for old, new in json.loads(extra_toml).items():
                    require(toml.count(old) == 1, f"server.toml: expected one {old!r}")
                    toml = toml.replace(old, new)
                with open(cfg, "w", encoding="utf-8") as f:
                    f.write(toml)
        if not os.path.exists(self._fifo):
            os.mkfifo(self._fifo)
        self._log = open(os.path.join(self.out_dir, "server.log"), "ab")
        env = dict(os.environ)  # JAX_COMPILATION_CACHE_DIR passes unchanged
        env["BENCH_CHIP_CONTROL"] = self._fifo
        env["BENCH_CHIP_PARENT"] = str(os.getpid())
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "trace", "serve_traced.py"),
             "--config", cfg],
            cwd=ROOT, stdout=self._log, stderr=subprocess.STDOUT, env=env,
            start_new_session=True)
        self._swept = False
        self._admin = Conn(self.port, 60.0)
        while True:
            self.alive()
            try:
                if self._admin.request("GET", "/", timeout=5.0)[0] == 200:
                    return
            except ConnectionError:
                pass
            self.remaining()
            time.sleep(0.2)

    def alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise Failed(f"the server died (exit code {rc}); see "
                         f"{os.path.join(self.out_dir, 'server.log')}")

    def signal_session(self, sig: int) -> None:
        """`sig` to the child's session: the server and whatever it started."""
        try:
            os.killpg(self.proc.pid, sig)
        except ProcessLookupError:
            pass  # none of them is left

    def kill(self) -> None:
        """SIGKILL: no shutdown hook runs, nothing buffered is flushed."""
        self._admin.close()
        self.signal_session(signal.SIGKILL)
        self.proc.wait()
        self._swept = True
        self._log.close()

    def stop(self) -> int | None:
        if self._admin is not None:
            self._admin.close()
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.signal_session(signal.SIGTERM)
            try:
                self.proc.wait(30)
            except subprocess.TimeoutExpired:
                self.signal_session(signal.SIGKILL)
                self.proc.wait()
        if not self._swept:  # what the server started and left behind; once, before its id is reused
            self.signal_session(signal.SIGKILL)
            self._swept = True
        if self._log is not None and not self._log.closed:
            self._log.close()
        return self.proc.returncode

    # -- talk ---------------------------------------------------------------

    def conn(self, timeout: float) -> Conn:
        return Conn(self.port, timeout)

    def get_json(self, path: str, timeout: float | None = None, **params):
        if params:
            path += "?" + urllib.parse.urlencode(params)
        self.alive()
        status, body = self._admin.request("GET", path, timeout=timeout)
        require(status == 200, f"GET {path}: {status} {body[:300]!r}")
        return json.loads(body)

    def metrics(self) -> dict[str, float]:
        self.alive()
        status, body = self._admin.request("GET", "/metrics")
        require(status == 200, f"GET /metrics: {status}")
        return parse_metrics(body.decode())

    def control(self, command: str, timeout: float = 120.0) -> dict:
        """One line down the FIFO to the child's side thread; its answer
        comes back as a file of its own."""
        self._replies += 1
        reply = os.path.join(self.out_dir, f"reply.{self._replies}.json")
        with open(self._fifo, "w", encoding="utf-8") as f:
            f.write(f"{command} {reply}\n")
        end = time.monotonic() + timeout
        while not os.path.exists(reply):
            self.alive()
            require(time.monotonic() < end, f"control {command!r}: no answer")
            time.sleep(0.02)
        with open(reply, encoding="utf-8") as f:
            out = json.load(f)
        require("error" not in out, f"control {command!r}: {out.get('error')}")
        return out

    def settle_compaction(self, before: dict) -> dict:
        """Wait until every task the picker queued has finished and a later
        pick found nothing more to do (chip_smoke.py's settle_compaction)."""
        def counts(m):
            return (m.get('horaedb_compaction_picks_total{outcome="queued"}', 0.0),
                    m.get('horaedb_compactions_total{result="ok"}', 0.0),
                    m.get('horaedb_compactions_total{result="error"}', 0.0),
                    m.get('horaedb_compaction_picks_total{outcome="empty"}', 0.0))
        empty0 = counts(before)[3]
        last, since = None, time.monotonic()
        while True:
            now = counts(self.metrics())
            queued, ok, err, empty = now
            require(err == 0, f"{int(err)} compaction task(s) failed")
            if now != last:
                last, since = now, time.monotonic()
            if queued == ok and empty > empty0 and time.monotonic() - since >= 2.0:
                return {"tasks_done": int(ok - counts(before)[1])}
            self.remaining()
            time.sleep(0.5)
