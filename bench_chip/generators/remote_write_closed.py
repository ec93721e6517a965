"""Prometheus remote write, closed loop: `shards` senders, each sending
its next `samples_per_send`-sample snappy request when the last is
acknowledged, replaying the fleet's scrape rounds in time order.

Series i belongs to shard i % shards (Prometheus shards by a hash of the
label set, so every target's series spread over all shards); a shard cuts
its series of one round into requests of `samples_per_send`. The warm-up
registers the fleet by sending rounds 0..register_rounds and then runs the
window's own loop for `warm_seconds`, so that the flush and merge row
classes a window meets under the compaction scheduler compile in set-up;
the window goes on from the round the warm-up reached.

`correct`: once the window has closed the child is stopped as the mix
says (`kill`: SIGKILL, no shutdown hook) and started again on the same
directory; a sample of (family, target) pairs drawn from the seed, with
the series of every shard's last acknowledged request in it, is read
back over every round and held to the reference.
"""

from __future__ import annotations

import itertools
import json
import threading
import urllib.parse

import numpy as np

from bench_chip import wire
from bench_chip.loop import Request, closed_loop, ok, share
from bench_chip.reference import remote_write_closed as ref


class Mix:
    def __init__(self, traffic: dict, fleet, seed: int):
        self.t, self.fleet, self.seed = traffic, fleet, seed
        self.shards = int(traffic["shards"])
        self.per_send = int(traffic["samples_per_send"])
        self.register_rounds = int(traffic["register_rounds"])
        blocks = fleet.blocks()
        self.chunks: list[list[np.ndarray]] = []   # [shard][chunk] -> series indices
        self.templates: list[list[wire.Template]] = []
        for s in range(self.shards):
            mine = np.arange(s, fleet.n_series, self.shards)
            cut = [mine[i:i + self.per_send] for i in range(0, len(mine), self.per_send)]
            self.chunks.append(cut)
            self.templates.append([wire.Template([blocks[i] for i in c], 1) for c in cut])
        self._rounds: dict[int, np.ndarray] = {}
        self._lock = threading.Lock()

    def _values(self, rnd: int) -> np.ndarray:
        with self._lock:
            v = self._rounds.get(rnd)
            if v is None:
                v = self._rounds[rnd] = self.fleet.values(rnd)
                self._rounds.pop(rnd - 8, None)  # shards stay within a few rounds
            return v

    def stream(self, shard: int, first_round: int, rounds: int | None = None):
        """Shard `shard`'s requests from `first_round` on, in time order."""
        for rnd in (itertools.count(first_round) if rounds is None
                    else range(first_round, first_round + rounds)):
            values = self._values(rnd)
            ts = np.asarray([self.fleet.ts(rnd)])
            for c, idx in enumerate(self.chunks[shard]):
                raw = self.templates[shard][c].fill(values[idx][:, None], ts)
                yield Request("POST", "/api/v1/write", wire.compress(raw), wire.HEADERS,
                              meta=(shard, c, rnd), units=len(idx))

    # -- the phases the harness drives ----------------------------------------

    def warm(self, server, timeout: float) -> dict:
        win = closed_loop(server, self.shards,
                          lambda s: self.stream(s, 0, self.register_rounds), None, timeout)
        bad = [r for r in win.records if not acked(r)]
        if bad:
            raise RuntimeError(f"registering the fleet failed: {bad[0].status} "
                               f"{bad[0].error or bad[0].body[:300]!r}")
        before = list(win.records)
        pre = closed_loop(server, self.shards, lambda s: self.stream(s, self.register_rounds),
                          float(self.t["warm_seconds"]), timeout)
        bad = [r for r in pre.records if not acked(r)]
        if bad:
            raise RuntimeError(f"warm-up write failed: {bad[0].status} "
                               f"{bad[0].error or bad[0].body[:300]!r}")
        self._before = before + pre.records
        # every shard goes on from the round after the last it sent
        self._next = [max((r.meta[2] for r in self._before if r.meta[0] == s)) + 1
                      for s in range(self.shards)]
        return {"register_s": win.seconds, "requests": len(self._before),
                "samples": sum(r.units for r in self._before)}

    def window(self, server, seconds: float, timeout: float):
        return closed_loop(server, self.shards,
                           lambda s: self.stream(s, self._next[s]), seconds, timeout)

    def check(self, server, win, restart) -> dict:
        unanswered = sum(1 for r in win.records if not acked(r))
        how = self.t.get("stop_before_readback")
        if how:
            restart(how)
        # state[shard][chunk][round]: 1 acknowledged, -1 sent and not, 0 never sent
        n_rounds = max(r.meta[2] for r in self._before + win.records) + 1
        state = [[np.zeros(n_rounds, dtype=np.int8) for _ in cut] for cut in self.chunks]
        for r in self._before + win.records:
            s, c, rnd = r.meta
            state[s][c][rnd] = 1 if acked(r) else -1
        per_target = len(self.fleet.per_target)
        families = sorted({name for name, _, _ in self.fleet.per_target})
        rng = np.random.default_rng([self.seed, 3])
        pairs = {(families[int(rng.integers(len(families)))], int(rng.integers(self.fleet.targets)))
                 for _ in range(int(self.t["readback_pairs"]))}
        for s in range(self.shards):  # the series of each shard's last acknowledged request
            mine = [r for r in win.records if r.meta[0] == s and acked(r)]
            if mine:
                i = int(self.chunks[s][mine[-1].meta[1]][0])
                pairs.add((self.fleet.per_target[i % per_target][0], i // per_target))
        wanted = {}  # (family, target) -> the series indices of that family at that target
        for family, target in pairs:
            wanted[(family, target)] = [target * per_target + k
                                        for k, (name, _, _) in enumerate(self.fleet.per_target)
                                        if name == family]
        cols = sorted({i for idx in wanted.values() for i in idx})
        col_of = {i: n for n, i in enumerate(cols)}
        values = np.stack([self.fleet.values(r)[cols] for r in range(n_rounds)])  # [round, col]
        step = self.fleet.interval_ms // 1000
        steps_ms = np.asarray([self.fleet.ts(r) + self.fleet.interval_ms for r in range(n_rounds)])
        lost = wrong = extra = compared = 0
        # an answer that comes late is late, not wrong: the reopened child merges what the
        # window left, and a merge row class it meets for the first time compiles for half a
        # minute with the read-back behind it, past the server's default 30 s deadline
        wait = int(self.t["readback_timeout_s"])
        for family, target in sorted(pairs):
            expected = {}
            for i in wanted[(family, target)]:
                labels = self.fleet.labels(i)
                del labels["__name__"]
                # series i is the (i // shards)-th of shard i % shards
                s, c = i % self.shards, (i // self.shards) // self.per_send
                expected[frozenset(labels.items())] = (values[:, col_of[i]], state[s][c])
                compared += int(np.sum(state[s][c] == 1))
            expr = f'max_over_time({family}{{instance="{self.fleet.instance(target)}"}}[{step}s])'
            body = server.get_json("/api/v1/query_range?" + urllib.parse.urlencode(
                {"query": expr, "start": int(steps_ms[0] // 1000),
                 "end": int(steps_ms[-1] // 1000), "step": step,
                 "timeout": f"{wait}s"}), timeout=wait + 15.0)
            a, b, c2 = ref.compare(body["data"]["result"], expected, steps_ms)
            lost, wrong, extra = lost + a, wrong + b, extra + c2
        lim = self.t["limits"]
        self.compared = compared
        return {"unanswered": [unanswered, lim["unanswered"]],
                "lost_samples": [lost, lim["lost_samples"]],
                "wrong_values": [wrong, lim["wrong_values"]],
                "extra_samples": [extra, lim["extra_samples"]]}

    def explain(self, server) -> dict:
        """One read-back query with ?explain=1: the route the check's reads took."""
        family = self.fleet.per_target[0][0]
        step = self.fleet.interval_ms // 1000
        expr = f'max_over_time({family}{{instance="{self.fleet.instance(0)}"}}[{step}s])'
        t0 = self.fleet.ts(0) // 1000 + step
        wait = int(self.t["readback_timeout_s"])
        return server.get_json("/api/v1/query_range?" + urllib.parse.urlencode(
            {"query": expr, "start": t0, "end": t0 + 4 * step, "step": step, "explain": 1,
             "timeout": f"{wait}s"}), timeout=wait + 15.0).get("explain") or {}

    def counts(self, win, t0: float | None = None, t1: float | None = None) -> dict:
        done = [(r, share(r, t0, t1)) for r in win.records if acked(r)]
        return {"operations": sum(f for _, f in done),
                "samples": sum(r.units * f for r, f in done),
                "wire_bytes": sum(r.sent_bytes * f for r, f in done)}


def acked(r) -> bool:
    """A 2xx whose body acknowledges every sample of the request."""
    if not ok(r):
        return False
    try:
        return json.loads(r.body).get("samples") == r.units
    except ValueError:
        return False


def build(traffic: dict, config: dict, fleet, seed: int) -> Mix:
    return Mix(traffic, fleet, seed)
