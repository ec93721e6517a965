"""A number the benchmark's own clients measured: a field of the window's
summary (loop.summary), or the set-up time."""


def read(spec: dict, ctx: dict):
    return ctx["summary"].get(spec["field"])
