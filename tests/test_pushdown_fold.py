"""The pushdown's fold as ONE compiled program a class (ops/aggregate.py
`fold_sorted` / `downsample_fold`), against plain references on the CPU.

A fold pads its rows, series and buckets to power-of-two classes and runs
one program; the grids it hands back must be what the unpadded arithmetic
gives: counts equal, selections (min, max) a stored sample bit for bit,
sums equal for the scatter (the same additions in the same order) and to
rounding for `runs` (a tree's order). The served queries are held to the
benchmark's own reference (bench_chip/reference/tsbs_queries.py).
"""

import json
import urllib.parse

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from bench_chip import wire
from bench_chip.fleets import tsbs_devops
from bench_chip.reference import tsbs_queries as ref
from horaedb_tpu.common import xprof
from horaedb_tpu.ops import agg_registry, aggregate
from horaedb_tpu.server.config import Config
from horaedb_tpu.server.main import build_app
from tests.conftest import async_test

ROW_COUNTS = [1, 10, 35, 44, 359, 360, 361, 1023, 1025, 8192, 8193]
# TSBS's two grids, a grid that fills its class and two that lie one past a class edge
GRIDS = [(1, 60), (8, 60), (100, 12), (3, 7), (9, 5)]
VARIANTS = ["plain", "valid", "nan_inf", "out_of_grid", "misses"]
BUCKET_MS = 60_000
T0 = 1_767_225_600_123  # no whole bucket: a window starts at any second


def rows_of(n: int, num_series: int, num_buckets: int, variant: str, seed: int = 0):
    """n rows sorted by (series, ts), seeded; what the variant adds: a
    `valid` lane, NaN and +/-inf values, timestamps outside the grid, or
    set-membership misses (rows of no selected series, which keep their
    monotone position and ride `valid`, as read.py's `dense_sid` makes them)."""
    rng = np.random.default_rng([seed, n, num_series, num_buckets, VARIANTS.index(variant)])
    sid = np.sort(rng.integers(0, num_series, n)).astype(np.int32)
    span = num_buckets * BUCKET_MS
    lo, hi = (-span // 4, span + span // 4) if variant == "out_of_grid" else (0, span)
    ts = np.empty(n, dtype=np.int64)
    for s in np.unique(sid):
        m = sid == s
        ts[m] = T0 + np.sort(rng.integers(lo, hi, int(m.sum())))
    vals = rng.uniform(-100.0, 100.0, n)
    valid = None
    if variant == "valid":
        valid = rng.random(n) > 0.3
    elif variant == "nan_inf":
        vals[rng.random(n) < 0.05] = np.nan
        vals[rng.random(n) < 0.05] = np.inf
        vals[rng.random(n) < 0.05] = -np.inf
    elif variant == "misses":
        valid = rng.random(n) > 0.2
    return ts, sid, vals, valid


def reference(ts, sid, vals, valid, num_series, num_buckets):
    """numpy float64, row by row in the rows' order: np.add.at is the
    sequential scatter."""
    bucket = (ts - T0) // BUCKET_MS
    ok = (bucket >= 0) & (bucket < num_buckets)
    if valid is not None:
        ok &= valid
    cell = (sid.astype(np.int64) * num_buckets + bucket)[ok]
    v = vals[ok]
    cells = num_series * num_buckets
    out = {"sum": np.zeros(cells), "count": np.zeros(cells),
           "min": np.full(cells, np.inf), "max": np.full(cells, -np.inf)}
    with np.errstate(invalid="ignore"):
        np.add.at(out["sum"], cell, v)
        np.add.at(out["count"], cell, 1.0)
        # a NaN in a cell wins both selections, as float min/max propagate it
        np.fmin.at(out["min"], cell, v)
        np.fmax.at(out["max"], cell, v)
        nan_cells = np.zeros(cells, bool)
        nan_cells[cell[np.isnan(v)]] = True
    out["min"][nan_cells] = np.nan
    out["max"][nan_cells] = np.nan
    return {k: g.reshape(num_series, num_buckets) for k, g in out.items()}


def same_bits(a, b):
    a, b = np.ascontiguousarray(a, dtype=np.float64), np.ascontiguousarray(b, dtype=np.float64)
    nan = np.isnan(a)
    return np.array_equal(nan, np.isnan(b)) and \
        np.array_equal(a[~nan].view(np.int64), b[~nan].view(np.int64))


@pytest.fixture
def pin(monkeypatch):
    def to(impl):
        monkeypatch.setenv("HORAEDB_AGG_IMPL", impl)
    return to


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: f"{g[0]}x{g[1]}")
@pytest.mark.parametrize("n", ROW_COUNTS)
def test_padded_scatter_fold_is_the_sequential_reference(pin, n, grid, variant):
    num_series, num_buckets = grid
    ts, sid, vals, valid = rows_of(n, num_series, num_buckets, variant)
    pin("scatter")
    got, run = aggregate.fold_sorted(ts, sid, vals, T0, BUCKET_MS, num_series,
                                     num_buckets, valid=valid)
    want = reference(ts, sid, vals, valid, num_series, num_buckets)
    assert run.impl == "scatter" and run.rows_real == n
    assert run.rows_class >= max(n, 512) and run.rows_class & (run.rows_class - 1) == 0
    assert np.array_equal(got["count"], want["count"])
    assert same_bits(got["sum"], want["sum"])
    assert same_bits(got["min"], want["min"]) and same_bits(got["max"], want["max"])
    with np.errstate(invalid="ignore"):
        host = agg_registry.host_downsample_sorted(
            ts, sid, vals, T0, BUCKET_MS, num_series, num_buckets, valid=valid)
    assert np.array_equal(got["count"], host["count"])
    assert same_bits(got["min"], host["min"]) and same_bits(got["max"], host["max"])
    with np.errstate(invalid="ignore"):
        np.testing.assert_allclose(got["sum"], host["sum"], rtol=1e-12, atol=1e-9)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: f"{g[0]}x{g[1]}")
@pytest.mark.parametrize("n", ROW_COUNTS)
def test_padded_runs_fold_selects_bit_for_bit_and_sums_to_rounding(pin, n, grid, variant):
    """`runs` is what an accelerator's 64-bit lanes take: no scatter. Its
    sums add in a tree's order."""
    num_series, num_buckets = grid
    ts, sid, vals, valid = rows_of(n, num_series, num_buckets, variant)
    pin("runs")
    got, run = aggregate.fold_sorted(ts, sid, vals, T0, BUCKET_MS, num_series,
                                     num_buckets, valid=valid)
    want = reference(ts, sid, vals, valid, num_series, num_buckets)
    assert run.impl == "runs"
    assert np.array_equal(got["count"], want["count"])
    assert same_bits(got["min"], want["min"]) and same_bits(got["max"], want["max"])
    with np.errstate(invalid="ignore"):
        np.testing.assert_allclose(got["sum"], want["sum"], rtol=1e-12, atol=1e-9)


@pytest.mark.parametrize("impl", ["scatter", "runs"])
def test_padding_changes_no_bit_of_the_unpadded_body(pin, impl):
    """The program at a class against the same body at the exact shapes
    (what the eager call computed before there were classes)."""
    num_series, num_buckets, n = 5, 9, 777
    ts, sid, vals, valid = rows_of(n, num_series, num_buckets, "valid")
    pin(impl)
    got, run = aggregate.fold_sorted(ts, sid, vals, T0, BUCKET_MS, num_series,
                                     num_buckets, valid=valid)
    assert (run.rows_class, run.grid_class) == (1024, (8, 16))
    flat = jax.jit(aggregate._fold_grids, static_argnums=(7, 8, 9, 10))(
        ts, sid, vals, valid, aggregate.f64_order_keys(vals)[0], np.int64(T0),
        np.int64(BUCKET_MS), num_series, num_buckets, True, impl)
    exact = {k: np.asarray(g).reshape(num_series, num_buckets) for k, g in flat.items()}
    for stat in ("min", "max"):
        exact[stat] = aggregate.f64_from_order_keys(exact[stat])
    for stat in ("count", "min", "max"):
        assert same_bits(got[stat], exact[stat]), stat
    if impl == "scatter":
        assert same_bits(got["sum"], exact["sum"])
    else:
        # the padding rows lengthen the last run, with zeros: its tree of
        # additions is another one, and no other cell's
        differs = got["sum"].view(np.int64) != exact["sum"].view(np.int64)
        assert differs.sum() <= 1
        np.testing.assert_allclose(got["sum"], exact["sum"], rtol=1e-14)


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.uint32])
@pytest.mark.parametrize("impl", ["scatter", "runs"])
@pytest.mark.parametrize("n", [1, 511, 512, 513])
def test_integer_values_sum_exactly_in_64_bits(pin, n, impl, dtype):
    """Integer lanes keep to integers: sums widen to 64 bits (past f32's and
    a narrow lane's range), selections are the lane's own values, and an
    empty cell holds the lane's limits as `jax.ops.segment_min/max` fill them."""
    num_series, num_buckets = 3, 7
    ts, sid, _vals, valid = rows_of(n, num_series, num_buckets, "valid")
    rng = np.random.default_rng(n)
    info = np.iinfo(dtype)
    vals = rng.integers(max(info.min, -2**31), min(info.max, 2**31), n).astype(dtype)
    pin(impl)
    got, run = aggregate.fold_sorted(ts, sid, vals, T0, BUCKET_MS, num_series,
                                     num_buckets, valid=valid)
    assert run.impl == impl and run.rows_class == (512 if n <= 512 else 1024)
    cell = (sid.astype(np.int64) * num_buckets + (ts - T0) // BUCKET_MS)[valid]
    cells = num_series * num_buckets
    want_sum = np.zeros(cells, np.int64)
    np.add.at(want_sum, cell, vals[valid].astype(np.int64))
    want_min, want_max = np.full(cells, info.max, dtype), np.full(cells, info.min, dtype)
    np.minimum.at(want_min, cell, vals[valid])
    np.maximum.at(want_max, cell, vals[valid])
    assert not np.issubdtype(got["sum"].dtype, np.floating)
    assert np.array_equal(got["sum"].ravel().astype(np.int64), want_sum)
    assert np.array_equal(got["count"].ravel(), np.bincount(cell, minlength=cells))
    assert np.array_equal(got["min"].ravel(), want_min)
    assert np.array_equal(got["max"].ravel(), want_max)


def test_a_fold_of_no_rows_is_an_empty_grid(pin):
    pin("scatter")
    none = np.empty(0, np.int64)
    got, run = aggregate.fold_sorted(none, none.astype(np.int32), np.empty(0), T0, BUCKET_MS, 3, 7)
    assert run.rows_real == 0 and got["count"].shape == (3, 7) and not got["count"].any()
    assert np.all(got["min"] == np.inf) and np.all(got["max"] == -np.inf)


def compiles_of(kernel: str) -> int:
    return sum(e["compiles"] for e in xprof.catalog() if e["kernel"] == kernel)


def test_one_compile_for_21_row_counts_of_one_class(pin):
    """Every count a one-host hour can cut is one row class: one program."""
    pin("scatter")
    counts = [1, 10, 35, 44, 59, 77, 101, 120, 150, 180, 199, 222, 256, 290, 301,
              317, 333, 350, 359, 360, 361]
    assert len(set(counts)) == 21
    before = compiles_of("downsample_fold")
    for i, n in enumerate(counts):
        # a grid and a flag no other test of this file uses: the class is new here
        ts, sid, vals, _ = rows_of(n, 2, 17, "plain", seed=i)
        _, run = aggregate.fold_sorted(ts, sid, vals, T0 + 1000 * i, BUCKET_MS, 2, 17,
                                       with_minmax=False)
        assert (run.rows_class, run.grid_class) == (512, (2, 32))
    assert compiles_of("downsample_fold") - before == 1


def test_a_smaller_fold_rides_a_compiled_class_and_a_larger_one_adds_its_own(pin):
    """A random window cuts every row count out of a segment; only the
    largest class a panel needs ever compiles."""
    pin("scatter")
    grid = dict(num_series=6, num_buckets=21)  # (8, 32): this test's own
    def fold(n, seed):
        ts, sid, vals, _ = rows_of(n, 6, 21, "plain", seed=seed)
        got, run = aggregate.fold_sorted(ts, sid, vals, T0, BUCKET_MS, **grid)
        want = reference(ts, sid, vals, None, 6, 21)
        assert same_bits(got["sum"], want["sum"]) and same_bits(got["max"], want["max"])
        return run.rows_class
    before = compiles_of("downsample_fold")
    assert fold(5000, 1) == 8192
    assert compiles_of("downsample_fold") - before == 1
    assert [fold(n, n) for n in (3, 400, 1025, 8192)] == [8192] * 4
    assert compiles_of("downsample_fold") - before == 1
    assert fold(8193, 2) == 16384 and fold(9000, 3) == 16384 and fold(77, 4) == 8192
    assert compiles_of("downsample_fold") - before == 2
    assert aggregate._ROW_CLASSES.pick(("none",), 1 << 20) == 1 << 20
    assert aggregate._ROW_CLASSES.pick(("none",), 512) == 512  # past the ride's limit


@pytest.mark.parametrize("impl", ["scatter", "runs"])
def test_a_change_of_t0_or_of_the_step_compiles_nothing(pin, impl):
    """`t0` and `bucket_ms` are operands: neither an xjit retrace nor any
    other XLA compile (an eager operation's) follows a new window."""
    xprof.register_metrics()
    pin(impl)
    ts, sid, vals, valid = rows_of(300, 3, 7, "valid")
    aggregate.fold_sorted(ts, sid, vals, T0, BUCKET_MS, 3, 7, valid=valid)
    xjit0, xla0 = compiles_of("downsample_fold"), xprof.xla_totals()["compiles"]
    for shift in (1, 999, 60_000, 123_456_789):
        ts2, sid2, vals2, valid2 = rows_of(200 + shift % 97, 3, 7, "valid", seed=shift)
        aggregate.fold_sorted(ts2 + shift, sid2, vals2, T0 + shift, BUCKET_MS + shift % 7,
                              3, 7, valid=valid2)
    assert compiles_of("downsample_fold") == xjit0
    assert xprof.xla_totals()["compiles"] == xla0


def test_an_accelerators_f64_takes_runs_and_an_f32_lane_the_choice(monkeypatch, pin):
    pin("scatter")
    ts, sid, vals, _ = rows_of(400, 3, 7, "plain")
    monkeypatch.setattr(aggregate, "device_f64_is_exact", lambda: False)
    assert aggregate.fold_sorted(ts, sid, vals, T0, BUCKET_MS, 3, 7)[1].impl == "runs"
    got, run = aggregate.fold_sorted(ts, sid, vals.astype(np.float32), T0, BUCKET_MS, 3, 7)
    assert run.impl == "scatter" and got["sum"].dtype == np.float32


def test_the_fold_counts_itself_and_names_its_class(pin):
    from horaedb_tpu.server.metrics import GLOBAL_METRICS
    from horaedb_tpu.storage import scanstats

    def counter(line_start):
        for line in GLOBAL_METRICS.render().splitlines():
            if line.startswith(line_start):
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    pin("runs")
    ts, sid, vals, _ = rows_of(360, 2, 60, "plain")  # a grid class of this test's own
    folds0 = counter('horaedb_pushdown_folds_total{impl="runs"}')
    real0 = counter('horaedb_pushdown_rows_total{kind="real"}')
    pad0 = counter('horaedb_pushdown_rows_total{kind="padded"}')
    with scanstats.scan_stats() as st:
        aggregate.fold_sorted(ts, sid, vals, T0, BUCKET_MS, 2, 60)
    assert counter('horaedb_pushdown_folds_total{impl="runs"}') - folds0 == 1
    assert counter('horaedb_pushdown_rows_total{kind="real"}') - real0 == 360
    assert counter('horaedb_pushdown_rows_total{kind="padded"}') - pad0 == 152
    assert st.counts["folds"] == 1 and st.counts["fold_class_512x2x64"] == 1
    assert st.counts["fold_rows_real"] == 360 and st.counts["fold_rows_padded"] == 152
    assert {"fold_prep", "fold_h2d", "fold_kernel", "fold_d2h"} <= set(st.seconds)
    assert st.kernels.get("downsample_fold") == 1


# -- the served queries, against the benchmark's reference ---------------------

TSBS_SHAPES = {
    "single-groupby-1-1-1": "tsbs-single-groupby-1-1-1",
    "double-groupby-1": "tsbs-double-groupby-1",
}


def tsbs_traffic(name: str) -> dict:
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "bench_chip", "traffic", name + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("impl", ["scatter", "runs"])
@pytest.mark.parametrize("shape", sorted(TSBS_SHAPES))
@async_test
async def test_served_tsbs_query_equals_the_reference(tmp_path, monkeypatch, shape, impl):
    """4 hosts x 2 h of the TSBS fleet over remote write, then both query
    shapes through /api/v1/query_range: the pushdown's fold (pinned to a
    device program) answers what the plain reference computes."""
    monkeypatch.setenv("HORAEDB_AGG_IMPL", impl)
    fleet = tsbs_devops.build({"hosts": 4, "log_interval_s": 10, "hours": 2,
                               "assumed": {"loader_rounds_per_request": 100}}, seed=2**31 + 5)
    traffic = tsbs_traffic(TSBS_SHAPES[shape])
    # the mix's ranges at a size the 2 h hold: an hour per minute, 2 h per half hour
    range_s, step_s = (3600, 60) if shape.startswith("single") else (5400, 1800)
    window = {"single-groupby-1-1-1": "1m", "double-groupby-1": "30m"}[shape]
    query = traffic["query"].replace("[1m]", f"[{window}]").replace("[1h]", f"[{window}]")
    app = await build_app(Config.from_toml(
        f'port = 0\n[metric_engine.storage.object_store]\ntype = "Local"\n'
        f'data_dir = "{tmp_path}/data"\n'))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        for raw, _samples in fleet.batches():
            r = await client.post("/api/v1/write", data=wire.compress(raw), headers=wire.HEADERS)
            assert r.status == 200, await r.text()
        rng = np.random.default_rng(11)
        first = int(fleet.ts[0] // 1000)
        for _ in range(3):
            field = int(rng.integers(len(fleet.fields)))
            hosts = [int(rng.integers(fleet.hosts))] if traffic["hosts_per_query"] else \
                list(range(fleet.hosts))
            start = first + int(rng.integers(0, 7200 - range_s + 1))
            names = "|".join(fleet.host_tags[h]["hostname"] for h in hosts)
            params = {"query": query.format(field=fleet.fields[field], hosts=names),
                      "start": start + step_s, "end": start + range_s, "step": step_s,
                      "explain": 1}
            r = await client.get("/api/v1/query_range?" + urllib.parse.urlencode(params))
            body = await r.json()
            assert r.status == 200 and body["status"] == "success", body
            steps = ref.steps_ms(start + step_s, start + range_s, step_s)
            want = ref.answer(fleet.values[field][hosts], fleet.ts, steps, step_s,
                              traffic["inner"], traffic.get("across"))
            faults, gap = ref.compare(body["data"]["result"], want,
                                      [fleet.host_tags[h]["hostname"] for h in hosts],
                                      traffic.get("group_by"), steps)
            assert faults == 0
            assert gap <= traffic["limits"]["value_gap"], gap
            explain = body["explain"]
            assert explain["agg_impls"] == [impl]
            assert explain["fold"]["folds"] >= 1 and explain["fold"]["rows_real"] > 0
            # one packed pass a fold, and the loaded fleet rewrites no sample
            pack = explain["fold"]["pack_order"]
            assert sum(pack.values()) == explain["fold"]["folds"] and pack["dedup"] == 0
            assert all(c.split("x")[0] != "0" for c in explain["fold"]["classes"])
            assert "fold_kernel" in explain["stages_s"]
    finally:
        await client.close()
