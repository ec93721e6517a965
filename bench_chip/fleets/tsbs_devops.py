"""TSBS devops, cpu-only: hosts x the 10 cpu_* fields, one reading per
log interval, a clamped random walk in [0, 100]; 10 host tags with TSBS's
value sets. Taken from chip_smoke.py's make_fleet (the program may change;
the yardstick may not)."""

from __future__ import annotations

import time

import numpy as np

from bench_chip import wire

CPU_FIELDS = (
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest",
    "usage_guest_nice",
)
REGIONS = ("us-east-1", "us-west-1", "us-west-2", "eu-west-1", "eu-central-1",
           "ap-southeast-1", "ap-southeast-2", "ap-northeast-1", "sa-east-1")
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCHES = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
ENVIRONMENTS = ("production", "staging", "test")
BASE_MS = 1_767_225_600_000  # 2026-01-01T00:00:00Z: aligned to any segment


class Fleet:
    def __init__(self, config: dict, seed: int):
        self.hosts = int(config["hosts"])
        self.interval_ms = int(config["log_interval_s"]) * 1000
        self.rounds = int(config["hours"] * 3600 * 1000) // self.interval_ms
        self.batch_rounds = int(config["assumed"]["loader_rounds_per_request"])
        self.fields = CPU_FIELDS
        rng = np.random.default_rng(seed)
        self.host_tags = []
        for h in range(self.hosts):
            region = REGIONS[rng.integers(len(REGIONS))]
            self.host_tags.append({
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
        # values[field, host, round]
        self.values = np.empty((len(CPU_FIELDS), self.hosts, self.rounds))
        x = rng.uniform(0.0, 100.0, size=(len(CPU_FIELDS), self.hosts))
        for r in range(self.rounds):
            self.values[:, :, r] = x
            x = np.clip(x + rng.normal(0.0, 1.0, size=x.shape), 0.0, 100.0)
        self.ts = BASE_MS + self.interval_ms * np.arange(self.rounds, dtype=np.int64)

    def series_labels(self) -> list[dict]:
        """Host-major: a host's 10 fields are read together."""
        return [{"__name__": f"cpu_{f}", **tags}
                for tags in self.host_tags for f in self.fields]

    def batches(self):
        """The loader's requests in time order: `batch_rounds` readings of
        every series each (TSBS's batch of 10,000 host readings at 100
        hosts), as uncompressed WriteRequests."""
        blocks = [wire.series_labels(lb) for lb in self.series_labels()]
        templates = {}
        for r0 in range(0, self.rounds, self.batch_rounds):
            n = min(self.batch_rounds, self.rounds - r0)
            if n not in templates:
                templates[n] = wire.Template(blocks, n)
            # [field, host, n] -> [host, field, n] -> [series, n]
            v = self.values[:, :, r0:r0 + n].transpose(1, 0, 2).reshape(-1, n)
            yield templates[n].fill(v, self.ts[r0:r0 + n]), v.size

    def load(self, server) -> dict:
        """One loader connection, in time order."""
        conn = server.conn(300.0)
        sent = requests = wire_bytes = 0
        t0 = time.perf_counter()
        try:
            for raw, samples in self.batches():
                body = wire.compress(raw)
                status, resp = conn.request("POST", "/api/v1/write", body, wire.HEADERS)
                if status != 200:
                    raise RuntimeError(f"load request {requests}: {status} {resp[:300]!r}")
                sent += samples
                requests += 1
                wire_bytes += len(body)
        finally:
            conn.close()
        secs = time.perf_counter() - t0
        return {"samples": sent, "requests": requests, "wire_bytes": wire_bytes,
                "seconds": secs, "samples_per_s": sent / secs}


def build(config: dict, seed: int) -> Fleet:
    return Fleet(config, seed)
