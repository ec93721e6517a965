"""The TaskGroup (horaedb_tpu/common/aio.py) honors the
structured-concurrency contract the engine relies on: children are
joined or reaped before the block exits, and a lone failure (child or
body) re-raises the exception itself."""

import asyncio
import builtins
import contextlib

import pytest

from horaedb_tpu.common.aio import TaskGroup
from tests.conftest import async_test


@contextlib.contextmanager
def expect_child_error(exc_type):
    """pytest.raises(exc_type) that ALSO accepts the >= 3.11 real
    TaskGroup's ExceptionGroup wrapper around the same child error."""
    group_cls = getattr(builtins, "BaseExceptionGroup", None)
    try:
        yield
    except exc_type:
        return
    except BaseException as e:  # noqa: BLE001 — test helper
        if group_cls is not None and isinstance(e, group_cls) and any(
            isinstance(sub, exc_type) for sub in e.exceptions
        ):
            return
        raise
    raise AssertionError(f"{exc_type.__name__} not raised")


async def _child(log, i, t):
    try:
        await asyncio.sleep(t)
        log.append(f"done{i}")
    except asyncio.CancelledError:
        log.append(f"cancelled{i}")
        raise


class TestTaskGroupContract:
    @async_test
    async def test_all_children_joined_before_exit(self):
        log = []
        async with TaskGroup() as tg:
            tg.create_task(_child(log, 0, 0.01))
            tg.create_task(_child(log, 1, 0.02))
        assert sorted(log) == ["done0", "done1"]

    @async_test
    async def test_child_failure_cancels_siblings_and_propagates(self):
        log = []

        async def boom():
            await asyncio.sleep(0.01)
            raise ValueError("x")

        with expect_child_error(ValueError):
            async with TaskGroup() as tg:
                tg.create_task(_child(log, 0, 10))
                tg.create_task(boom())
        assert log == ["cancelled0"]

    @async_test
    async def test_parent_cancellation_reaps_children(self):
        """Shutdown-time cancel of the awaiting task must not leave
        children running against a closing store (data.py flush path)."""
        log = []

        async def body():
            async with TaskGroup() as tg:
                tg.create_task(_child(log, 0, 10))
                tg.create_task(_child(log, 1, 10))

        t = asyncio.get_running_loop().create_task(body())
        await asyncio.sleep(0.05)
        t.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t
        await asyncio.sleep(0.05)
        assert sorted(log) == ["cancelled0", "cancelled1"]

    @async_test
    async def test_task_spawned_during_drain_is_joined(self):
        """A child may fan out further work via tg.create_task while
        __aexit__ is already draining; the block must join it too."""
        log = []

        async def grandchild():
            await asyncio.sleep(0.02)
            log.append("grandchild")

        async def child(tg):
            await asyncio.sleep(0.01)
            tg.create_task(grandchild())
            log.append("child")

        async with TaskGroup() as tg:
            tg.create_task(child(tg))
        assert log == ["child", "grandchild"]

    @async_test
    async def test_task_spawned_during_abort_does_not_leak(self):
        """A cancelled child's finally handler spawning follow-up work:
        either the spawn is refused (the real TaskGroup while shutting
        down) or the task is reaped before the block exits — it must
        never OUTLIVE the block."""
        log = []

        async def orphan():
            try:
                await asyncio.sleep(0.05)
                log.append("orphan-ran")
            except asyncio.CancelledError:
                log.append("orphan-reaped")
                raise

        async def child(tg):
            try:
                await asyncio.sleep(10)
            finally:
                try:
                    tg.create_task(orphan())
                except RuntimeError:
                    log.append("spawn-refused")

        async def boom():
            await asyncio.sleep(0.01)
            raise ValueError("x")

        with expect_child_error(ValueError):
            async with TaskGroup() as tg:
                tg.create_task(child(tg))
                tg.create_task(boom())
        await asyncio.sleep(0.1)
        assert "orphan-ran" not in log, log
        assert log.count("orphan-reaped") + log.count("spawn-refused") == 1, log

    @async_test
    async def test_create_task_after_exit_raises(self):
        async with TaskGroup() as tg:
            tg.create_task(asyncio.sleep(0))
        with pytest.raises(RuntimeError):
            tg.create_task(asyncio.sleep(0))

    def test_create_task_outside_loop_raises(self):
        tg = TaskGroup()

        async def never():  # pragma: no cover - must not run
            raise AssertionError

        with pytest.raises(RuntimeError):
            tg.create_task(never())

    @async_test
    async def test_body_exception_cancels_children(self):
        log = []
        with pytest.raises(KeyError):
            async with TaskGroup() as tg:
                tg.create_task(_child(log, 0, 10))
                await asyncio.sleep(0.01)  # let the child start
                raise KeyError("body")
        assert log == ["cancelled0"]
