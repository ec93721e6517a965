"""The plain reference of the TSBS devops queries: numpy, float64, from the
seed's arrays. It imports nothing of the program.

The window the program documents (promql/eval.py): at each step t the
samples with t - step <= ts < t. `inner` reduces each series over each
window; `across` then reduces over the selected hosts (None keeps one row
a host). NaN marks a window that holds no sample.
"""

from __future__ import annotations

import numpy as np

_REDUCE = {"mean": np.mean, "max": np.max, "min": np.min, "sum": np.sum}


def steps_ms(start_s: int, end_s: int, step_s: int) -> np.ndarray:
    """Prometheus's range grid: start + k*step for k in 0..floor((end-start)/step)."""
    return 1000 * np.arange(start_s, end_s + 1, step_s, dtype=np.int64)


def answer(values: np.ndarray, ts_ms: np.ndarray, steps: np.ndarray, step_s: int,
           inner: str, across: str | None, dtype=np.float64) -> np.ndarray:
    """values[hosts, rounds] -> [hosts, steps], or [1, steps] with `across`.
    `dtype` is float64; the control computes in a lower one."""
    v = values.astype(dtype)
    out = np.full((v.shape[0], len(steps)), np.nan, dtype=dtype)
    fn = _REDUCE[inner]
    for i, t in enumerate(steps):
        lo = np.searchsorted(ts_ms, t - step_s * 1000, side="left")
        hi = np.searchsorted(ts_ms, t, side="left")
        if hi > lo:
            out[:, i] = fn(v[:, lo:hi], axis=1, dtype=dtype) if inner in ("mean", "sum") \
                else fn(v[:, lo:hi], axis=1)
    if across is not None:
        out = _REDUCE[across](out, axis=0, keepdims=True)
    return out


def compare(result: list, want: np.ndarray, names: list[str] | None, group_by: str | None,
            steps: np.ndarray) -> tuple[int, float]:
    """One answer (`data.result` of the response) against want[rows, steps].
    Returns (shape faults, widest value gap). A shape fault is a missing,
    doubled or unexpected series or step. The gap of a value is
    |got - want| / (|want| + 1): the values lie in [0, 100]."""
    rows = {}
    faults = 0
    for series in result:
        key = series["metric"].get(group_by) if group_by else ""
        if key in rows:
            faults += 1
        rows[key] = series["values"]
    expected = names if group_by else [""]
    faults += len(set(rows) ^ set(expected))
    gap = 0.0
    for row, key in enumerate(expected):
        if key not in rows:
            continue
        w = want[row]
        present = ~np.isnan(w)
        got_ts = np.asarray([round(float(p[0]) * 1000) for p in rows[key]], dtype=np.int64)
        if not np.array_equal(got_ts, steps[present]):
            faults += 1
            continue
        got_v = np.asarray([float(p[1]) for p in rows[key]])
        if got_v.size:
            w = w[present].astype(np.float64)
            gap = max(gap, float(np.max(np.abs(got_v - w) / (np.abs(w) + 1.0))))
    return faults, gap
