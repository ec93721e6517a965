"""The plain reference of the write path's guarantees: what was
acknowledged is there, as sent, for every later read.

The read-back asks max_over_time(<family>{instance=..}[interval]) at every
scrape time plus one interval: with the program's half-open window
[t - step, t) each step holds exactly the one sample sent for t - step. So
the expected answer of a series is its sent value at each round whose
request was acknowledged, and nothing where none was sent. A round whose
request was sent and not acknowledged may read either way.
"""

from __future__ import annotations

import numpy as np


def compare(result: list, expected: dict, steps_ms: np.ndarray) -> tuple[int, int, int]:
    """`result`: data.result of one read-back. `expected`: label-set key ->
    (values[rounds], state[rounds]) with state 1 acknowledged, 0 never
    sent, -1 sent and unacknowledged. steps_ms[k] is round k's step.
    Returns (lost, wrong, extra) sample counts."""
    lost = wrong = extra = 0
    seen = set()
    at = {int(t): k for k, t in enumerate(steps_ms)}
    for series in result:
        key = frozenset(series["metric"].items())
        if key not in expected or key in seen:
            extra += len(series["values"])
            continue
        seen.add(key)
        values, state = expected[key]
        got = np.full(len(steps_ms), np.nan)
        for t, v in series["values"]:
            k = at.get(round(float(t) * 1000))
            if k is None:
                extra += 1
            else:
                got[k] = float(v)
        have = ~np.isnan(got)
        lost += int(np.sum((state == 1) & ~have))
        extra += int(np.sum((state == 0) & have))
        wrong += int(np.sum((state == 1) & have & (got != values)))
    for key in set(expected) - seen:
        lost += int(np.sum(expected[key][1] == 1))
    return lost, wrong, extra
