"""Test harness: CPU-backed JAX with a virtual 8-device mesh.

Mirrors the reference's test strategy (SURVEY §4): tmpdir/in-memory object
stores stand in for S3, and `xla_force_host_platform_device_count=8` gives a
fake multi-chip mesh so sharding tests run anywhere (the TPU analog of the
reference's shared-runtime test fixtures, storage.rs:386-396).
"""

import os

# Must happen before jax initializes a backend. Force CPU: unit tests are
# deterministic oracles; the driver benches the real chip separately.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Any test path that hits the aggregation dispatcher's 'auto' cold may
# trigger a calibration micro-A/B (ops/agg_registry.py); shrink it so the
# one-time cost is milliseconds, not seconds. Tests that pin their own
# size/cache (test_agg_registry.py) override via monkeypatch.
os.environ.setdefault("HORAEDB_AGG_CALIB_N", "20000")

import asyncio
import faulthandler
import functools
import gc
import io
import signal

import pytest

# A pytest plugin may have imported jax before this conftest ran; the backend
# is still uninitialized at collection time, so the config route also works.
import jax

jax.config.update("jax_platforms", "cpu")


# pytest-timeout is not installed: one hung test must fail alone, with a
# stack, instead of costing the whole run its time limit.
TEST_WATCHDOG_S = 180


def _pending_task_stacks() -> str:
    """Where every task of the running event loop is parked: a hung async
    test is an await that never resolves, which no thread stack shows."""
    try:
        tasks = asyncio.all_tasks(asyncio.get_running_loop())
    except RuntimeError:  # the test runs no loop
        return ""
    out = io.StringIO()
    for task in tasks:
        task.print_stack(file=out)
    return out.getvalue()


@pytest.fixture(autouse=True, scope="module")
def _settled_heap():
    """What the files before this one left alive in the worker's process
    (jax's caches, compiled programs, metric children: 1e5 objects and more)
    is swept once and frozen, so that a full collection inside this file
    walks this file's objects only. Unfrozen, one full pass late in a whole
    run holds every thread 0.2-0.7 s (measured under `-n 6`, PR 31), which
    the tests that time the event loop or run under a 50 ms deadline read
    as the program's own lateness."""
    gc.collect()
    gc.freeze()
    yield


@pytest.fixture(autouse=True)
def _watchdog(request):
    def on_alarm(signum, frame):
        pytest.fail(
            f"{request.node.nodeid} ran over {TEST_WATCHDOG_S} s (watchdog)\n"
            + _pending_task_stacks(),
            pytrace=True,
        )

    # the stacks of every thread go to stderr just before the alarm fails
    # the test from the main thread
    faulthandler.dump_traceback_later(TEST_WATCHDOG_S - 1, exit=False)
    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_WATCHDOG_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
        faulthandler.cancel_dump_traceback_later()


def async_test(fn):
    """Run an async test via asyncio.run (no pytest-asyncio dependency)."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return asyncio.run(fn(*args, **kwargs))

    return wrapper


@pytest.fixture()
def mem_store():
    from horaedb_tpu.objstore import MemStore

    return MemStore()


@pytest.fixture()
def local_store(tmp_path):
    from horaedb_tpu.objstore import LocalStore

    return LocalStore(str(tmp_path / "store"))
