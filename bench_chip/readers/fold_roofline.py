"""One program's share of its roofline, from the device trace.

    {"reader": "fold_roofline", "program": "jit_downsample_fold"}

The least time the chip could take for what the operations that completed
in the traced span logically needed (work/<generator>.py, from shapes
alone: the same work whatever implements it; the peaks of peaks.json by
device kind), over the seconds the trace's reduction lists for that ONE
program (trace/reduce.py `device_ops`), not over everything the device
did. A trace that does not list the program (a program without it, or one
that never ran it in the span) reads as nothing, never as 0.
"""

from __future__ import annotations

import importlib

from bench_chip.readers import device_trace


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    counts = ctx.get("trace_counts") or {}
    if not trace or not counts.get("operations"):
        return None
    busy_s = sum(seconds for name, seconds in trace.get("device_ops") or []
                 if name == spec["program"])
    if not busy_s:
        return None
    work = importlib.import_module(f"bench_chip.work.{ctx['traffic']['generator']}")
    need = work.logical(ctx["traffic"], ctx["config"], counts)
    peak = device_trace.peaks(ctx["device_kind"])
    least_s = max(need["bytes"] / peak["hbm_bytes_per_s"], need["flops"] / peak["flops_per_s"])
    return 100.0 * least_s / busy_s
