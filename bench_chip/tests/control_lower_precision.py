#!/usr/bin/env python3
"""The control of the query cells: the plain reference put in the
program's place and computed in float32, the nearest precision below what
the configuration states (float64 samples; selections exact; sums as the
program holds them today). It has to come out as not correct.

    python bench_chip/tests/control_lower_precision.py <traffic name> <seed> [<seed> ...]

prints, a seed, the widest value gap over as many queries as a window
answers (`--queries`, 400 by default) at the cell's own size, beside the
mix's limit. No chip is needed: nothing of the program runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))

from bench_chip.fleets import tsbs_devops  # noqa: E402
from bench_chip.generators import tsbs_queries  # noqa: E402
from bench_chip.reference import tsbs_queries as ref  # noqa: E402


def load(*parts):
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def served(mix, q: dict, dtype) -> list:
    """The reference's answer in `dtype`, in the shape the server answers in."""
    want, names, steps = mix.want(q, dtype)
    group = mix.t.get("group_by")
    out = []
    for row, name in enumerate(names if group else [""]):
        present = ~np.isnan(want[row])
        out.append({"metric": {group: name} if group else {},
                    "values": [[t / 1000.0, repr(float(v))]
                               for t, v in zip(steps[present], want[row][present])]})
    return out


def gap(traffic_name: str, seed: int, queries: int, dtype=np.float32, config: dict | None = None):
    """(shape faults, widest value gap) of the control over `queries` queries."""
    config = config or load("configs", "tsbs-devops-cpu-100.json")
    traffic = load("traffic", traffic_name + ".json")
    fleet = tsbs_devops.build(config, seed)
    mix = tsbs_queries.build(traffic, config, fleet, seed)
    rng = np.random.default_rng([seed, 0, 0])
    faults, worst = 0, 0.0
    for _ in range(queries):
        q = mix.draw(rng)
        want, names, steps = mix.want(q)
        f, g = ref.compare(served(mix, q, dtype), want, names, traffic.get("group_by"), steps)
        faults, worst = faults + f, max(worst, g)
    return faults, worst, traffic["limits"]["value_gap"]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("traffic")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--queries", type=int, default=400)
    a = ap.parse_args()
    for s in a.seeds:
        f, g, lim = gap(a.traffic, s, a.queries)
        print(json.dumps({"traffic": a.traffic, "seed": s, "control": "float32", "queries": a.queries,
                          "shape_faults": f, "value_gap": g, "limit": lim, "correct": f == 0 and g <= lim}))
