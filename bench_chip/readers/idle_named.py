"""How much of the device's idle time the trace can put a name to.

The reduction (trace/reduce.py) lists the longest gaps between the
device's busy intervals, each named after the narrowest host event open at
its middle that is no wait, and "host: nothing traced" where no such event
was open. This is the share of the listed idle seconds that has a name:
the program's stage annotations (`flush.*`, `compaction.*`, `scan.*`,
`ingest.*`, `xjit.*`) and the runtime's own events (compiler passes).
No trace, or no idle second listed, reads as nothing.
"""

UNNAMED = "host: nothing traced"


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    gaps = trace.get("idle_gaps") or []
    listed = sum(seconds for _, seconds in gaps)
    if not listed:
        return None
    return 100.0 * sum(seconds for name, seconds in gaps if name != UNNAMED) / listed
