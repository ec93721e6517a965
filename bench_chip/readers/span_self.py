"""Self time of a span from /debug/traces: its duration minus what its
children cover, and minus the seconds its `minus_attr` attribute lists
(the served query's root span has no child spans today: the scan's stage
lanes ride on it as the attribute `stages`). Median, in milliseconds, over
the sampled requests whose root carries the span's name. Host clock."""

from __future__ import annotations

import statistics


def covered(children: list[dict]) -> float:
    spans = sorted((c["start_ms"], c["start_ms"] + 1000.0 * c["duration_s"]) for c in children)
    total, end = 0.0, float("-inf")
    for a, b in spans:
        if b > end:
            total += b - max(a, end)
            end = b
    return total / 1000.0


def selfs(span: dict, spec: dict, out: list[float]) -> None:
    if span["name"] == spec["span"]:
        own = span["duration_s"] - covered(span.get("children", []))
        attr = (span.get("attrs") or {}).get(spec.get("minus_attr", ""), None)
        if isinstance(attr, dict):
            own -= sum(v for v in attr.values() if isinstance(v, (int, float)))
        out.append(max(own, 0.0))
    for child in span.get("children", []):
        selfs(child, spec, out)


def read(spec: dict, ctx: dict):
    out: list[float] = []
    for tree in ctx["trees"]:
        selfs(tree["root"], spec, out)
    return 1000.0 * statistics.median(out) if out else None
