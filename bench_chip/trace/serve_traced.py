"""The server, with the two things only the chip's own process can answer.

Calls horaedb_tpu.server.main.main() in this process, unchanged, and adds
one side thread that blocks on a FIFO (BENCH_CHIP_CONTROL) and answers

    memory <reply>            the device's memory statistics
    trace_start <dir> <reply> jax.profiler.start_trace(dir)
    trace_stop <reply>        jax.profiler.stop_trace()

each with one JSON file written whole (rename). The program has no such
hook; this wrapper is the benchmark's and goes when the server owns one.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def _answer(parts: list[str]) -> dict:
    import jax

    if parts[0] == "memory":
        worst = {}
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            if stats.get("peak_bytes_in_use", -1) >= worst.get("peak_bytes_in_use", -1):
                worst = stats
        return {k: v for k, v in worst.items() if isinstance(v, (int, float))}
    if parts[0] == "trace_start":
        t0 = time.perf_counter()
        # the Python tracer off: on, a 9 s trace of the serving process held 1.5 M
        # host events and its stop_trace blocked for 17 s (my chip run, PR 26)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(parts[1], profiler_options=options)
        return {"started_unix": time.time(), "start_call_s": time.perf_counter() - t0}
    if parts[0] == "trace_stop":
        stopped = time.time()
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        return {"stopped_unix": stopped, "stop_call_s": time.perf_counter() - t0}
    raise ValueError(f"unknown command {parts[0]!r}")


def _serve(fifo: str) -> None:
    while True:
        with open(fifo, encoding="utf-8") as f:  # blocks until the parent writes
            lines = f.read().splitlines()
        for line in lines:
            parts = line.split()
            if not parts:
                continue
            reply = parts.pop()
            try:
                out = _answer(parts)
            except Exception as e:  # noqa: BLE001 — the parent reads the failure
                out = {"error": f"{type(e).__name__}: {e}"}
            with open(reply + ".tmp", "w", encoding="utf-8") as f:
                json.dump(out, f)
            os.replace(reply + ".tmp", reply)


if __name__ == "__main__":
    control = os.environ.get("BENCH_CHIP_CONTROL")
    if control:
        threading.Thread(target=_serve, args=(control,), daemon=True,
                         name="bench-chip-control").start()
    from horaedb_tpu.server.main import main

    main()
