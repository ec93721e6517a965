"""What the device trace of the serving process says (trace/reduce.py).

idle_pct: 1 - busy over the traced window. roofline_pct: the least time
the chip could take for what the operations that completed in the traced
window logically needed (work/<generator>.py, from shapes alone; the
peaks of peaks.json by device kind; an unknown device is an error), over
the device's busy time there. The same work whatever implements it. No
busy time, or no completed operation, reads as nothing, never as 0.
"""

from __future__ import annotations

import importlib
import json
import os

from bench_chip.readers import prom_delta

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"peaks.json has no device kind {device_kind!r}")
    return table[device_kind]


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace["window_s"]:
        return None
    if spec["field"] == "idle_pct":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if spec["field"] == "roofline_pct":
        counts = dict(ctx["trace_counts"])
        for name, term in spec.get("counters", {}).items():  # deltas over the traced span
            counts[name] = prom_delta.delta(term, ctx["trace_metrics0"], ctx["trace_metrics1"]) or 0.0
        if not counts.get("operations") or not trace["busy_s"]:
            return None
        work = importlib.import_module(f"bench_chip.work.{ctx['traffic']['generator']}")
        need = work.logical(ctx["traffic"], ctx["config"], counts)
        peak = peaks(ctx["device_kind"])
        least_s = max(need["bytes"] / peak["hbm_bytes_per_s"], need["flops"] / peak["flops_per_s"])
        return 100.0 * least_s / trace["busy_s"]
    raise KeyError(f"device_trace has no field {spec['field']!r}")
