"""TSBS devops queries, closed loop: each worker sends its next PromQL
range query when the last answers.

The mix's file gives the query's text with {field} and {hosts}
placeholders (the selected hosts' names joined by `|`), how many hosts it
selects (0: all), the range and step, and
what the reference computes (`inner`, `across`, `group_by`). A query's
field, host and window start are drawn from the seed, the start uniformly
at whole seconds over the data's range as TSBS draws its windows.
"""

from __future__ import annotations

import itertools
import json
import urllib.parse

import numpy as np

from bench_chip.loop import Request, closed_loop, ok, share
from bench_chip.reference import tsbs_queries as ref


class Mix:
    def __init__(self, traffic: dict, fleet, seed: int):
        self.t, self.fleet, self.seed = traffic, fleet, seed
        self.workers = int(traffic["workers"])
        self.range_s, self.step_s = int(traffic["range_s"]), int(traffic["step_s"])
        first = int(fleet.ts[0] // 1000)
        last = int(fleet.ts[-1] // 1000) + fleet.interval_ms // 1000
        # a window [s, s + range) inside the data
        self.start_lo, self.start_hi = first, last - self.range_s
        if self.start_hi < self.start_lo:
            raise ValueError("the data is shorter than the query's range")

    def draw(self, rng) -> dict:
        """One query's parameters."""
        field = int(rng.integers(len(self.fleet.fields)))
        n = int(self.t["hosts_per_query"])
        hosts = sorted(int(h) for h in rng.choice(self.fleet.hosts, size=n, replace=False)) \
            if n else list(range(self.fleet.hosts))
        s = int(rng.integers(self.start_lo, self.start_hi + 1))
        return {"field": field, "hosts": hosts, "start": s}

    def request(self, q: dict, **extra) -> Request:
        names = "|".join(self.fleet.host_tags[h]["hostname"] for h in q["hosts"])
        expr = self.t["query"].format(field=self.fleet.fields[q["field"]], hosts=names)
        params = {"query": expr, "start": q["start"] + self.step_s,
                  "end": q["start"] + self.range_s, "step": self.step_s, **extra}
        return Request("GET", "/api/v1/query_range?" + urllib.parse.urlencode(params),
                       meta=q)

    def stream(self, worker: int, purpose: int, **extra):
        """Worker `worker`'s endless queries; `purpose` keeps the warm-up's
        draws apart from the window's."""
        rng = np.random.default_rng([self.seed, purpose, worker])
        return (self.request(self.draw(rng), **extra) for _ in itertools.count())

    # -- the phases the harness drives ----------------------------------------

    def warm(self, server, timeout: float) -> dict:
        """The window's own shapes at the window's own concurrency, until a
        whole pass compiles nothing (at most `warm_passes`)."""
        passes = []
        for p in range(int(self.t["warm_passes"])):
            before = compiles(server)
            # a cold compile may outlast the default deadline: the warm-up asks for
            # the longest the server allows, the window's queries for nothing
            win = closed_loop(server, self.workers,
                              lambda w, p=p: self.stream(w, 100 + p, timeout=self.t["warm_timeout"]),
                              None, timeout, per_worker=int(self.t["warm_per_worker"]))
            bad = [r for r in win.records if not ok(r)]
            if bad:
                raise RuntimeError(f"warm-up query failed: {bad[0].status} "
                                   f"{bad[0].error or bad[0].body[:300]!r}")
            passes.append(compiles(server) - before)
            if p and passes[-1] == 0:
                break
        return {"compiles_per_pass": passes}

    def window(self, server, seconds: float, timeout: float):
        return closed_loop(server, self.workers, lambda w: self.stream(w, 0), seconds, timeout)

    def want(self, q: dict, dtype=np.float64):
        """(reference answer, names of its rows, steps) of one query."""
        steps = ref.steps_ms(q["start"] + self.step_s, q["start"] + self.range_s, self.step_s)
        hosts = q["hosts"]
        want = ref.answer(self.fleet.values[q["field"]][hosts], self.fleet.ts, steps,
                          self.step_s, self.t["inner"], self.t.get("across"), dtype)
        return want, [self.fleet.host_tags[h]["hostname"] for h in hosts], steps

    def check(self, server, win, restart) -> dict:
        """Every answer the window's clients received against the
        reference. Returns name -> [value, limit]."""
        faults, gap, unanswered, self.compared = 0, 0.0, 0, 0
        for r in win.records:
            if not ok(r):
                unanswered += 1
                continue
            body = json.loads(r.body)
            if body.get("status") != "success":
                faults += 1
                continue
            want, names, steps = self.want(r.meta)
            f, g = ref.compare(body["data"]["result"], want, names,
                               self.t.get("group_by"), steps)
            faults += f
            gap = max(gap, g)
            self.compared += want.size
        lim = self.t["limits"]
        return {"unanswered": [unanswered, lim["unanswered"]],
                "shape_faults": [faults, lim["shape_faults"]],
                "value_gap": [gap, lim["value_gap"]]}

    def explain(self, server) -> dict:
        """One more query of the mix with ?explain=1, after the window."""
        req = self.request(self.draw(np.random.default_rng([self.seed, 999])))
        return server.get_json(req.path + "&explain=1").get("explain") or {}

    def counts(self, win, t0: float | None = None, t1: float | None = None) -> dict:
        """What the work count and the readers divide by, over the window
        or over [t0, t1] of it (an answer counts there by the share of its
        time that lies inside)."""
        n = sum(share(r, t0, t1) for r in win.records if ok(r))
        return {"operations": n, "queries": n}


def compiles(server) -> int:
    return sum(e["compiles"] for e in server.get_json("/debug/kernels")["kernels"])


def build(traffic: dict, config: dict, fleet, seed: int) -> Mix:
    return Mix(traffic, fleet, seed)
