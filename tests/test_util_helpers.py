"""Tests for the shared fixtures themselves + a scan-through-check_stream
round trip (test_util.rs usage parity)."""

import pytest

from horaedb_tpu.objstore import MemStore
from horaedb_tpu.storage import ObjectBasedStorage, ScanRequest, TimeRange, WriteRequest
from tests.conftest import async_test
from tests.util import DequeBatchStream, check_stream, record_batch


class TestRecordBatchBuilder:
    def test_literal_builder(self):
        b = record_batch(pk=("i64", [1, 2, 3]), value=("f64", [0.5, 1.5, 2.5]))
        assert b.num_rows == 3
        assert b.schema.names == ["pk", "value"]
        assert b.column("value").to_pylist() == [0.5, 1.5, 2.5]

    def test_binary_column(self):
        b = record_batch(k=("u64", [1]), payload=("bin", [b"xyz"]))
        assert b.column("payload").to_pylist() == [b"xyz"]


class TestStreams:
    @async_test
    async def test_deque_stream_and_check(self):
        batches = [
            record_batch(a=("i64", [1, 2])),
            record_batch(a=("i64", [3])),
        ]
        await check_stream(DequeBatchStream(batches), [record_batch(a=("i64", [1, 2, 3]))])

    @async_test
    async def test_check_stream_mismatch_raises(self):
        with pytest.raises(AssertionError):
            await check_stream(
                DequeBatchStream([record_batch(a=("i64", [1]))]),
                [record_batch(a=("i64", [2]))],
            )

    @async_test
    async def test_check_stream_against_engine_scan(self):
        store = MemStore()
        schema = record_batch(pk=("i64", [0]), v=("f64", [0.0])).schema
        eng = await ObjectBasedStorage.try_new(
            "db", store, schema, 1, 3_600_000,
            enable_compaction_scheduler=False, start_background_merger=False,
        )
        await eng.write(
            WriteRequest(
                record_batch(pk=("i64", [3, 1, 2]), v=("f64", [3.0, 1.0, 2.0])),
                TimeRange(10, 11),
            )
        )
        await check_stream(
            eng.scan(ScanRequest(range=TimeRange(0, 100))),
            [record_batch(pk=("i64", [1, 2, 3]), v=("f64", [1.0, 2.0, 3.0]))],
        )
        await eng.close()


def test_every_test_runs_under_the_watchdog():
    """tests/conftest.py arms a SIGALRM watchdog around each test (no
    pytest-timeout in the image): one hung test fails alone, with a stack."""
    import signal

    from tests import conftest

    remaining, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < remaining <= conftest.TEST_WATCHDOG_S
    assert signal.getsignal(signal.SIGALRM) not in (signal.SIG_DFL, signal.SIG_IGN, None)


def test_the_watchdog_names_the_await_a_hung_test_is_parked_on():
    """A hung async test is an await that never resolves; the alarm's
    message carries every pending task's stack (no thread stack has it)."""
    import asyncio

    from tests import conftest

    async def parked_here():
        await asyncio.Event().wait()

    async def main():
        task = asyncio.create_task(parked_here())
        await asyncio.sleep(0)
        stacks = conftest._pending_task_stacks()
        task.cancel()
        return stacks

    assert "parked_here" in asyncio.run(main())
    assert conftest._pending_task_stacks() == ""  # no loop, nothing to say
