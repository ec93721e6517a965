#!/usr/bin/env python3
"""From a jax.profiler trace (.xplane.pb) to the device's numbers.

    python bench_chip/trace/reduce.py <trace dir or .xplane.pb>   -> one JSON object

What a trace of the serving process on a TPU v5e holds (looked at by hand,
PR 26): one plane a chip, `/device:TPU:<n>`, with the lines `XLA Modules`
(one event a launched program, named `jit_<function>(<fingerprint>)`: the
xjit kernels appear under their Python function's name, not under their
`kernel=` label), `XLA Ops` (one event an HLO op, named by its HLO text)
and `Async XLA Ops` (copies in flight); and `/host:CPU` with one line a
host thread (`python`, the PJRT and runtime threads). Both clocks count
nanoseconds from the start of the session and agree to about a
millisecond.

busy_s: the union of the intervals in which an op (XLA Ops or Async XLA
Ops; XLA Modules where a plane has neither) ran, averaged over the device
planes. window_s: from the end of the profiler's own `start_trace` call to
the start of its `stop_trace` call as the host's Python line shows them
(from the first to the last event where it shows neither); device
intervals are cut to that window. device_ops: seconds by program
(XLA Modules, fingerprints stripped), longest first. idle_gaps: the
longest gaps between busy intervals, each named after the narrowest host
event that was open at the gap's middle and is no wait.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"
WAITS = re.compile(r"wait|sleep|select|poll|acquire|futex|idle|recv|accept|run_forever|_run_once"
                   r"|threading.py|run_until_complete|asyncio/runners|selectors.py", re.I)
TOP = 10


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    device_planes, host_lines = [], []
    first, last = float("inf"), float("-inf")
    n_events = 0
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            n_events += len(evs)
            if evs:
                first = min(first, min(a for _, a, _ in evs))
                last = max(last, max(b for _, _, b in evs))
            lines[line.name] = evs
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:"):
            device_planes.append(lines)
        elif plane.name.startswith("/host:"):
            host_lines.extend((name, evs) for name, evs in lines.items())
    for _, evs in host_lines:  # the profiler's own calls are not the window
        for name, a, b in evs:
            if name.endswith(" start_trace") and b < last:
                first = max(first, b)
            elif name.endswith(" stop_trace") and a > first:
                last = min(last, a)
    if not device_planes:
        raise RuntimeError("the trace holds no device plane: no operation was traced on a device")

    busy_ns, module_ns = [], []
    ops: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    def cut(evs):
        return [(n, max(a, first), min(b, last)) for n, a, b in evs if b > first and a < last]

    for lines in device_planes:
        modules = cut(lines.get(MODULE_LINE, []))
        lines = {name: cut(lines.get(name, [])) for name in OP_LINES}
        op_events = [ev for name in OP_LINES for ev in lines.get(name, [])] or modules
        merged = union([(a, b) for _, a, b in op_events])
        busy_ns.append(sum(b - a for a, b in merged))
        module_ns.append(sum(b - a for a, b in union([(a, b) for _, a, b in modules])))
        for name, a, b in modules:
            key = re.sub(r"\(\d+\)$", "", name)
            ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
        edges = [(first, first)] + merged + [(last, last)]
        gaps.extend((edges[i][1], edges[i + 1][0]) for i in range(len(edges) - 1)
                    if edges[i + 1][0] > edges[i][1])
    chips = len(device_planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    named: dict[str, float] = {}
    for a, b in gaps[:200]:
        name = host_at(host_lines, (a + b) / 2.0)
        named[name] = named.get(name, 0.0) + (b - a) / 1e9 / chips
    def by_time(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {
        "busy_s": sum(busy_ns) / chips / 1e9,
        "module_busy_s": sum(module_ns) / chips / 1e9,
        "window_s": (last - first) / 1e9,
        "chips": chips,
        "events": n_events,
        "device_ops": [[k, v / chips] for k, v in by_time(ops)][:TOP],
        "idle_gaps": by_time(named)[:TOP],
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
    }


def host_at(host_lines, t: float) -> str:
    """The narrowest host event open at t that is no wait, as line:event."""
    best, width = "host: nothing traced", float("inf")
    for line, evs in host_lines:
        for name, a, b in evs:
            if a <= t <= b and b - a < width and b > a and not WAITS.search(name):
                best, width = f"{line.split('/')[0]}: {name[:80]}", b - a
    return best


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1])))
