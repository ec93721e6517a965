#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python bench_chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent (this process) never imports JAX. Its one child is the normal
server on the cell's chip, docs/example.toml as shipped apart from port
and data directory, in a session of its own that every stop signals whole.
A run: build the C++ parser, start the child, make the fleet from --seed,
go through the mix's set-up phases (load, settle, warm), measure for
--seconds, read /metrics, /debug/kernels and /debug/traces, decide
`correct`, stop the child, print one JSON line last. A platform other
than `tpu`, or a thread of the run still alive at its end, fails the run
after every phase has passed.

A window may hold writes (a path under /api/v1/write) and queries, from
closed loops, an open loop at a fixed rate (loop.open_loop; a request's
latency runs from when it was due, `<class>_late_ms` says how late the
sends fell), or both under one barrier (loop.run_loops). The summary
gives `write_*` and `query_*` for each class the window holds (p50_ms,
p95_ms, max_ms, rate, unit_rate, turnaround_ms); the unprefixed fields
are over the class the traffic file's `"timed": "write" | "query"` names,
or over every request where it names none, which a window of both
classes may not do. A traced run reads up to SPAN_SAMPLE span trees for
each root span name in the whole trace ring, so that a class that
answers less often still has its trees.

Everything that belongs to one cell, configuration, mix or metric is a
file found by the name BENCHMARK.json gives; nothing here names one.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from collections import Counter

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from bench_chip import loop  # noqa: E402
from bench_chip.child import Failed, Server, parse_metrics, require  # noqa: E402

# the run's own limit on its waits: a cell's first run in a checkout compiles
# and may take 1,200 s; past this every wait fails instead of hanging
BUDGET_S = 1150.0
# the profiler's share of the window, at most a third of it: at 40 s that is 13.3 s, most of
# one compaction cycle (about 14.5 s), so that a traced span holds a chain of merges and not
# only the quiet phase between two
TRACE_SECONDS = 14.0
SPAN_SAMPLE = 48          # span trees read back after a traced window, for each root name
ROOT_SPANS = ("GET /api/v1/query", "POST /api/v1/write", "POST /api/v1/query")


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def kind(package: str, name: str):
    require(name.replace("_", "").isalnum(), f"bad {package} kind {name!r}")
    return importlib.import_module(f"bench_chip.{package}.{name}")


def find_cell(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    require(len(cells) == 1, f"BENCHMARK.json has no workload {workload!r}")
    cell = cells[0]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def metrics_of(bench: dict, section: str, workload: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def cache_dir_expected() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")


def override(config: dict, pairs: list[str]) -> None:
    """Test sizes (--set hosts=10): never used by a cell's run."""
    for pair in pairs:
        key, _, value = pair.partition("=")
        require(key in config, f"--set: the configuration has no key {key!r}")
        config[key] = json.loads(value)


def traced_window(server: Server, win_started: threading.Event, seconds: float,
                  trace_dir: str, out: dict) -> None:
    """Start and stop the child's profiler inside the window."""
    try:
        win_started.wait()
        length = min(TRACE_SECONDS, seconds / 3.0)
        time.sleep(seconds * 0.4)
        started = server.control(f"trace_start {trace_dir}")
        out["t0"] = time.perf_counter()
        conn = server.conn(30.0)  # its own: the admin connection is the main thread's
        out["metrics0"] = conn.request("GET", "/metrics")[1].decode()
        time.sleep(length)
        out["metrics1"] = conn.request("GET", "/metrics")[1].decode()
        conn.close()
        out["t1"] = time.perf_counter()
        stopped = server.control("trace_stop")
        out.update(started)
        out.update(stopped)
    except Exception as e:  # noqa: BLE001 — the run fails on it below
        out["error"] = f"{type(e).__name__}: {e}"


def span_trees(server: Server) -> list[dict]:
    """Up to SPAN_SAMPLE trees, newest first, for each root span name of
    the window's requests, from a listing of the whole ring."""
    listed = server.get_json("/debug/traces", limit=0)["traces"]
    kept: Counter = Counter()
    out = []
    for t in listed:
        name = t["name"]
        if not name.startswith(ROOT_SPANS) or kept[name] >= SPAN_SAMPLE:
            continue
        try:
            out.append(server.get_json(f"/debug/traces/{t['trace_id']}"))
        except Failed:
            continue  # evicted from the ring between the two reads
        kept[name] += 1
    return out


def reduce_trace(trace_dir: str, out_dir: str) -> dict:
    """bench_chip/trace/reduce.py in a process of its own (it imports JAX
    for ProfileData, held to the CPU: the parent never does)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(HERE, "trace", "reduce.py"), trace_dir],
                       capture_output=True, text=True, env=env, timeout=240)
    require(r.returncode == 0, f"trace reduction failed:\n{r.stderr[-2000:]}")
    with open(os.path.join(out_dir, "trace_reduced.json"), "w", encoding="utf-8") as f:
        f.write(r.stdout)
    return json.loads(r.stdout)


def run(args, server: Server, out_dir: str) -> tuple[dict, int]:
    """Every phase of the run. Returns the result and the chips the cell needs."""
    t_setup = time.perf_counter()
    bench = load_json(args.benchmark or os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, args.workload)
    override(config, args.set)
    for pair in args.set_traffic:
        key, _, value = pair.partition("=")
        traffic[key] = json.loads(value)

    # the parser, from the committed source
    t0 = time.perf_counter()
    native = os.path.join(ROOT, "horaedb_tpu", "native")
    built = subprocess.run(["make", "-C", native], capture_output=True, text=True)
    require(built.returncode == 0, f"native parser build failed:\n{built.stderr}")
    log("build", seconds=time.perf_counter() - t0)

    # the child boots while the fleet is made
    t0 = time.perf_counter()
    boot_err = []

    def booting():
        try:
            server.start(args.child_toml)
        except Exception as e:  # noqa: BLE001 — raised below
            boot_err.append(e)
    boot = threading.Thread(target=booting)
    boot.start()
    fleet = kind("fleets", config["fleet"]).build(config, args.seed)
    mix = kind("generators", traffic["generator"]).build(traffic, config, fleet, args.seed)
    made_s = time.perf_counter() - t0
    boot.join()
    if boot_err:
        raise boot_err[0]
    info = server.get_json("/api/v1/status/buildinfo")["data"]
    require(info.get("parser_backend") == "native",
            f"server parses with {info.get('parser_backend')!r}, not the native parser")
    k = server.get_json("/debug/kernels")
    device = {"platform": k["platform"], "kind": k["device_kind"], "count": k["device_count"]}
    require(os.path.realpath(k["compile_cache_dir"]) == os.path.realpath(cache_dir_expected()),
            f"compile cache at {k['compile_cache_dir']!r}, expected {cache_dir_expected()!r}")
    cache_files = len(os.listdir(k["compile_cache_dir"])) if os.path.isdir(k["compile_cache_dir"]) else 0
    log("start", seconds=time.perf_counter() - t0, fleet_and_mix_s=made_s, **device,
        parser_backend=info["parser_backend"], compile_cache_dir=k["compile_cache_dir"],
        compile_cache_files=cache_files)

    timeout = float(traffic["request_timeout_s"])
    for phase in traffic["setup"]:
        t0 = time.perf_counter()
        if phase == "load":
            report = fleet.load(server)
        elif phase == "settle":
            before = server.metrics()
            server.get_json("/compact")
            report = server.settle_compaction(before)
        elif phase == "warm":
            report = mix.warm(server, max(timeout, 300.0))
        else:
            raise Failed(f"unknown set-up phase {phase!r}")
        log(phase, phase_s=time.perf_counter() - t0, **report)
    setup_s = time.perf_counter() - t_setup
    log("setup", setup_s=setup_s)

    # the window
    metrics0, kernels0 = server.metrics(), server.get_json("/debug/kernels")
    trace_dir = os.path.join(out_dir, "trace")
    traced: dict = {}
    tracer = None
    if args.trace:
        started = threading.Event()
        tracer = threading.Thread(target=traced_window,
                                  args=(server, started, args.seconds, trace_dir, traced))
        tracer.start()
        started.set()  # the window opens within milliseconds of this
    win = mix.window(server, args.seconds, timeout)
    if tracer is not None:
        tracer.join()
        require("error" not in traced, f"tracing failed: {traced.get('error')}")
    metrics1, kernels1 = server.metrics(), server.get_json("/debug/kernels")
    summary = loop.summary(win, traffic.get("timed"))
    summary["setup_s"] = setup_s
    log("window", **summary)
    trees = span_trees(server) if args.trace else []
    memory = server.control("memory")
    # correct: the answers the timed clients received, against the reference
    def restart(how: str) -> None:
        t0 = time.perf_counter()
        require(how == "kill", f"unknown stop {how!r}")
        server.kill()
        server.start()
        log("restart", how=how, seconds=time.perf_counter() - t0)

    t0 = time.perf_counter()
    compared = mix.check(server, win, restart)
    correct = all(value <= limit for value, limit in compared.values())
    log("check", seconds=time.perf_counter() - t0, correct=correct,
        compared_values=getattr(mix, "compared", None))
    # the route, from one more request of the mix's own kind with ?explain=1: after
    # the window and after the check (a read flushes what a buffering server holds)
    explain = mix.explain(server)
    log("route", scan_paths=explain.get("scan_paths"), agg_impl=explain.get("agg_impl"),
        agg_impls=explain.get("agg_impls"), stages_s=explain.get("stages_s"),
        serving=explain.get("serving"), batching=explain.get("batching"),
        kernels_compiled={e["kernel"]: e["compiles"] for e in kernels1["kernels"] if e["compiles"]})
    rc = server.stop()
    log("stop", server_exit_code=rc)

    ctx = {
        "summary": summary, "counts": mix.counts(win), "window_s": summary["window_s"],
        "metrics0": metrics0, "metrics1": metrics1, "kernels0": kernels0, "kernels1": kernels1,
        "trees": trees, "trace": None, "traffic": traffic, "config": config,
        "device_kind": device["kind"],
    }
    with open(os.path.join(out_dir, "report.json"), "w", encoding="utf-8") as f:
        json.dump({"summary": summary, "counts": ctx["counts"], "explain": explain,
                   "metrics0": metrics0, "metrics1": metrics1, "memory": memory,
                   "compiles": {e["kernel"]: [e["compiles"], e["compile_seconds"]]
                                for e in kernels1["kernels"] if e["compiles"]},
                   "compared": compared, "traced": {k2: v for k2, v in traced.items()
                                                    if not k2.startswith("metrics")},
                   # the window's timeline: [worker, sent, done] from the window's start
                   "records": [[r.worker, round(r.sent - win.start, 4), round(r.done - win.start, 4)]
                               for r in win.records]}, f)
    breakdown = None
    reduced = None
    if args.trace:
        try:
            reduced = reduce_trace(trace_dir, out_dir)
        except Failed:
            if device["platform"] == "tpu":
                raise
            log("trace", note="a rehearsal off the chip has no device plane to reduce")
    if reduced is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)  # the reduction is kept, the trace is not
        ctx["trace"] = reduced
        ctx["trace_counts"] = mix.counts(win, traced["t0"], traced["t1"])
        ctx["trace_metrics0"] = parse_metrics(traced["metrics0"])
        ctx["trace_metrics1"] = parse_metrics(traced["metrics1"])
        ctx["trace_window_s"] = traced["t1"] - traced["t0"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"][:10],
                     "idle_gaps": reduced["idle_gaps"][:10]}
        log("trace", start_call_s=traced.get("start_call_s"), stop_call_s=traced.get("stop_call_s"),
            busy_s=reduced["busy_s"], window_s=reduced["window_s"],
            module_busy_s=reduced.get("module_busy_s"), events=reduced.get("events"))
    device["memory_peak_bytes"] = int(memory.get("peak_bytes_in_use", 0))

    section, where = ("per_layer", "layer_metrics") if args.trace else ("end_to_end", "end_to_end")
    metrics = {}
    for m in metrics_of(bench, section, args.workload):
        spec = load_json(HERE, where, m["name"] + ".json")
        value = kind("readers", spec["reader"]).read(spec, ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": summary["attempted"], "failed": summary["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {name: {"value": v, "limit": lim} for name, (v, lim) in compared.items()}
    for name, (v, lim) in compared.items():
        print(f"compared {name}: {v} (limit {lim})", file=sys.stderr)
    return result, cell["chips"]


def run_cell(argv: list[str]) -> tuple[dict | None, str]:
    """Parse, run, stop the child whatever happened. (result, why not)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    # for bench_chip/tests and rehearsals only: a cell's run passes none of these
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the configuration (tiny test sizes)")
    ap.add_argument("--set-traffic", action="append", default=[], metavar="KEY=JSON")
    ap.add_argument("--child-toml", default="", help=argparse.SUPPRESS)
    ap.add_argument("--benchmark", default="", help="another BENCHMARK.json (cells a later PR would add)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if args.seed < 0:
        return None, "--seed must not be negative"
    out_dir = args.out or os.path.join(ROOT, ".bench_chip", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    before = set(threading.enumerate())
    server = Server(out_dir, BUDGET_S)
    result, why = None, ""
    try:
        result, chips = run(args, server, out_dir)
    except Exception as e:  # noqa: BLE001 — every failure ends the run with no result
        why = f"{type(e).__name__}: {e}"
    finally:
        server.stop()
        shutil.rmtree(os.path.join(out_dir, "data"), ignore_errors=True)
    left = stray_threads(before)
    if left:
        return None, "; ".join(filter(None, [why, f"thread(s) of the run still alive at its end: {left}"]))
    if why:
        return None, why
    device = result["device"]
    if device["platform"] != "tpu" or device["count"] < chips:
        return result, (f"the server ran on {device['count']} device(s) of platform "
                        f"{device['platform']!r}; the cell needs {chips} tpu chip(s)")
    return result, ""


def stray_threads(before=()) -> list[str]:
    """Non-daemon threads other than the main one, not among `before`, still alive."""
    return [t.name for t in threading.enumerate()
            if t is not threading.main_thread() and t not in before and not t.daemon and t.is_alive()]


def terminated(signum, frame) -> None:
    """SIGTERM ends the run as an error does: the child is stopped on the way out."""
    raise SystemExit(128 + signum)


def main() -> int:
    signal.signal(signal.SIGTERM, terminated)
    result, why = run_cell(sys.argv[1:])
    if "jax" in sys.modules:  # the parent must never hold the chip
        why = "the parent imported JAX"
    rc = 0
    if why:
        held = f"; every phase passed, the result is withheld: {json.dumps(result)}" if result else ""
        print(f"bench_chip: no result: {why}{held}", file=sys.stderr)
        rc = 3
    else:
        print(json.dumps(result))
    if stray_threads():  # the interpreter would wait for them at exit: a hang is worse
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
