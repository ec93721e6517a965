"""A Prometheus fleet of node_exporter targets: every target exposes the
same list of series, which the configuration's file holds as a table of
families (names x label sets). Counters climb by a per-series step with
jitter; gauges wander about a per-series level. A round's values are a
function of (seed, round) alone, so the generator and the reference each
make any round anew and hold no history.
"""

from __future__ import annotations

import itertools

import numpy as np

from bench_chip import wire

BASE_MS = 1_767_225_600_000  # 2026-01-01T00:00:00Z


def expand(families: list[dict]) -> list[tuple[str, dict, str]]:
    """The table -> one (metric name, labels, kind) a series, in table order."""
    out = []
    for fam in families:
        keys = list(fam.get("labels", {}))
        combos = list(itertools.product(*(fam["labels"][k] for k in keys))) or [()]
        for name in fam["names"]:
            for combo in combos:
                out.append((name, dict(zip(keys, combo)), fam["kind"]))
    return out


class Fleet:
    def __init__(self, config: dict, seed: int):
        self.seed = seed
        self.targets = int(config["targets"])
        self.interval_ms = int(config["scrape_interval_s"]) * 1000
        self.per_target = expand(config["series"])
        self.job = config["job"]
        n = self.targets * len(self.per_target)
        self.n_series = n
        rng = np.random.default_rng([seed, 1])
        self.is_counter = np.tile(
            np.asarray([kind == "counter" for _, _, kind in self.per_target]), self.targets)
        # counters: whole numbers, a start and a step a round; gauges: a level and a swing
        self.level = np.where(self.is_counter,
                              np.floor(rng.uniform(0, 1e9, n)), rng.uniform(0, 1e6, n))
        self.step = np.where(self.is_counter,
                             np.floor(rng.uniform(1, 1e4, n)), rng.uniform(0, 1e3, n))

    def instance(self, target: int) -> str:
        return f"10.0.{target // 250}.{target % 250 + 1}:9100"

    def labels(self, i: int) -> dict:
        """Series i (target-major) as Prometheus sends it."""
        target, k = divmod(i, len(self.per_target))
        name, labels, _ = self.per_target[k]
        return {"__name__": name, "instance": self.instance(target), "job": self.job, **labels}

    def blocks(self) -> list[bytes]:
        return [wire.series_labels(self.labels(i)) for i in range(self.n_series)]

    def ts(self, rnd: int) -> int:
        return BASE_MS + rnd * self.interval_ms

    def values(self, rnd: int) -> np.ndarray:
        """Every series' sample of scrape round `rnd`."""
        noise = np.random.default_rng([self.seed, 2, rnd]).random(self.n_series)
        counter = self.level + self.step * rnd + np.floor(noise * self.step)
        gauge = self.level + self.step * (2.0 * noise - 1.0)
        return np.where(self.is_counter, counter, gauge)

    def load(self, server) -> dict:
        """No history: the fleet registers itself through the mix's own
        requests (the warm-up)."""
        return {"samples": 0, "requests": 0, "wire_bytes": 0, "seconds": 0.0}


def build(config: dict, seed: int) -> Fleet:
    return Fleet(config, seed)
