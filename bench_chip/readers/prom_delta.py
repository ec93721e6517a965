"""Counters and histogram sums of /metrics, as the difference between the
window's end and its start.

    {"reader": "prom_delta",
     "sum": [{"name": "horaedb_scan_stage_seconds_sum", "labels": {"stage": ["kernel", "transfer"]}}],
     "per": "queries" | "window_s" | [terms] | absent,
     "scale": 1000, "absent": 0}

A term adds every series of that family whose labels match (a label may
list several values). `per` divides by a count of the window (a key of the
generator's `counts`), by the window's seconds or by another delta.
Host-clock sums over concurrent shards overlap: seconds per operation,
never a share of wall time and never a device number. The program makes a
labelled series when it first counts on it: `"absent": 0` reads a counter
that is not there yet as not having moved. Otherwise nothing matching, or
a divisor of nought, reads as nothing.
"""

from __future__ import annotations

import re

_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def split(key: str) -> tuple[str, dict]:
    name, _, rest = key.partition("{")
    return name, dict(_LABEL.findall(rest))


def delta(term: dict, m0: dict, m1: dict):
    want = {k: (v if isinstance(v, list) else [v]) for k, v in term.get("labels", {}).items()}
    total, found = 0.0, False
    for key, after in m1.items():
        name, labels = split(key)
        if name != term["name"] or any(labels.get(k) not in vs for k, vs in want.items()):
            continue
        found = True
        total += after - m0.get(key, 0.0)
    return total if found else None


def read(spec: dict, ctx: dict, m0: dict | None = None, m1: dict | None = None):
    m0 = ctx["metrics0"] if m0 is None else m0
    m1 = ctx["metrics1"] if m1 is None else m1
    parts = [delta(t, m0, m1) for t in spec["sum"]]
    if all(p is None for p in parts):
        if "absent" not in spec:
            return None
        parts = [float(spec["absent"])]
    value = sum(p for p in parts if p is not None)
    per = spec.get("per")
    if isinstance(per, list):
        div = sum(d for d in (delta(t, m0, m1) for t in per) if d is not None)
    elif per == "window_s":
        div = ctx["window_s"]
    elif per:
        div = ctx["counts"].get(per)
    else:
        div = 1.0
    if not div:
        return None
    return value / div * spec.get("scale", 1.0)
