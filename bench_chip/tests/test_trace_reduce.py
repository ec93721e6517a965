"""The trace reduction on the small recorded trace (TPU v5 lite, PR 26):
three launches each of jit_my_sort_kernel (1.589 ms) and jit_my_sum_kernel
(0.572 ms) in a 177 ms window; the first sort started 0.8 ms before the
profiler's start_trace call returned, so 0.8 ms of it is cut."""

import os

import pytest

from bench_chip.trace import reduce

RECORDED = os.path.join(os.path.dirname(reduce.__file__), "recorded", "two_kernels.xplane.pb")


def test_union_merges_overlaps():
    assert reduce.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [(0, 3), (5, 7), (9, 9)]


def test_recorded_trace():
    out = reduce.reduce(RECORDED)
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(0.17704, abs=2e-4)
    ops = dict(out["device_ops"])
    assert set(ops) == {"jit_my_sort_kernel", "jit_my_sum_kernel"}
    assert ops["jit_my_sum_kernel"] == pytest.approx(3 * 0.000572429, rel=1e-3)
    assert ops["jit_my_sort_kernel"] == pytest.approx(3 * 0.001589 - 0.000806, rel=2e-3)
    # ops fill their programs all but a few microseconds
    assert out["busy_s"] == pytest.approx(out["module_busy_s"], rel=1e-2)
    assert out["busy_s"] == pytest.approx(sum(ops.values()), rel=1e-2)
    assert 0 < out["busy_s"] < out["window_s"]
    # the gaps are named and add up to the idle time
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-2)


def test_no_device_plane_is_an_error(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(RuntimeError, match="no device plane"):
        reduce.reduce(str(tmp_path))
