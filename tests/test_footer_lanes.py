"""Row groups pruned from a footer's min/max lanes (`storage/read.py`
`_select_row_groups` over `ops/filter.py` `prune_lanes`) against the plain
reference: the walk over pyarrow's metadata objects with a `{column: (lo,
hi)}` dict a row group and `filter_ops.prune_range`, as the selection was
before the lanes. Real parquet footers; the two keep lists are equal in
every case, cold (the lanes are built) and warm (they are there), and a
warm read walks the metadata objects only where a leaf needs the scalar
form."""

import calendar
import datetime
import io

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from horaedb_tpu.ops import filter as F
from horaedb_tpu.storage import read as read_mod
from horaedb_tpu.storage import scanstats

T63 = 1 << 63
T64 = 1 << 64
TS0 = 1_700_000_000_123  # a millisecond value, not a whole second


# -- the plain reference -------------------------------------------------------

def _ref_stat(v, is_unsigned):
    if isinstance(v, datetime.datetime):
        return calendar.timegm(v.utctimetuple()) * 1000 + v.microsecond // 1000
    if is_unsigned and isinstance(v, int) and v < 0:
        return v + T64
    return v


def reference_keep(meta, schema, predicate) -> list[int]:
    keep = []
    unsigned = {n for n in schema.names
                if pa.types.is_unsigned_integer(schema.field(n).type)}
    for rg in range(meta.num_row_groups):
        stats = {}
        g = meta.row_group(rg)
        for ci in range(g.num_columns):
            col = g.column(ci)
            st = col.statistics
            if st is not None and st.has_min_max:
                name = col.path_in_schema
                lo = _ref_stat(st.min, name in unsigned)
                hi = _ref_stat(st.max, name in unsigned)
                if lo > hi:
                    continue
                stats[name] = (lo, hi)
        if F.prune_range(predicate, stats):
            keep.append(rg)
    return keep


# -- the footers ---------------------------------------------------------------

def _footer(table: pa.Table, rows_a_group: int, **kw):
    buf = io.BytesIO()
    pq.write_table(table, buf, row_group_size=rows_a_group, **kw)
    pf = pq.ParquetFile(io.BytesIO(buf.getvalue()))
    return pf.metadata, pf.schema_arrow


def _mixed():
    """Ten row groups of four rows. `id`: uint64 on both sides of 2**63,
    row group 4 straddling it (its signed statistics wrap: unusable);
    `neg`: int64 from -20; `ts`: timestamp("ms") at millisecond values;
    `f`: float64 from -inf to +inf; `b`: binary; `flag`: boolean; `u32`:
    uint32 up to its top; `nostat`: written without statistics."""
    n = 40
    ids = [1, 2, 3, 4, 10, 11, 12, 13, 20, 21, 22, 23, 30, 31, 32, 33,
           T63 - 2, T63 - 1, T63, T63 + 1,
           *(T63 + 100 + i for i in range(16)),
           T64 - 4, T64 - 3, T64 - 2, T64 - 1]
    f = np.linspace(-9.5, 9.5, n)
    f[0], f[-1] = -np.inf, np.inf
    cols = {
        "id": pa.array(ids, type=pa.uint64()),
        "neg": pa.array(np.arange(-20, 20), type=pa.int64()),
        "ts": pa.array(TS0 + 1001 * np.arange(n), type=pa.timestamp("ms")),
        "f": pa.array(f, type=pa.float64()),
        "b": pa.array([b"k%02d" % i for i in range(n)], type=pa.binary()),
        "flag": pa.array([i >= 20 for i in range(n)], type=pa.bool_()),
        "u32": pa.array([*range(36), 2**32 - 4, 2**32 - 3, 2**32 - 2, 2**32 - 1],
                        type=pa.uint32()),
        "nostat": pa.array(np.arange(n), type=pa.int64()),
    }
    stats_for = [c for c in cols if c != "nostat"]
    return _footer(pa.table(cols), 4, write_statistics=stats_for)


def _data_like(groups: int, rows_a_group: int):
    """The data table's shape: metric_id, tsid (uint64, seahash-like, most
    above 2**63), ts, value, sorted by (metric_id, tsid, ts)."""
    n = groups * rows_a_group
    metric = np.repeat(np.array([7, T63 + 7, T64 - 9], dtype=np.uint64),
                       -(-n // 3))[:n]
    tsid = (np.arange(n, dtype=np.uint64) // np.uint64(6)) * np.uint64(T63 // 64 + 12345)
    order = np.lexsort((tsid, metric))
    ts = TS0 + 10_000 * (np.arange(n) % 6)
    return _footer(pa.table({
        "metric_id": pa.array(metric[order]),
        "tsid": pa.array(tsid[order]),
        "ts": pa.array(ts, type=pa.int64()),
        "value": pa.array(np.arange(n) * 0.25),
    }), rows_a_group), (metric[order], tsid[order])


FOOTERS = {}


def footer(name: str):
    if not FOOTERS:
        FOOTERS["mixed"] = _mixed()
        FOOTERS["one"], _ = _data_like(1, 12)
        FOOTERS["many"], FOOTERS["many-lanes"] = _data_like(600, 2)
    return FOOTERS[name]


# -- the cases: (footer, predicate, what serves the WARM read) ------------------

LANES, WALK = "lanes", "walk"
OPS = ("eq", "ne", "lt", "le", "gt", "ge")


def _cases():
    out = []

    def add(foot, label, pred, served):
        out.append(pytest.param(foot, pred, served, id=f"{foot}-{label}"))

    # every op below, at, between and above the bounds, in each numeric domain
    for op in OPS:
        for lit in (0, 1, 11, 14, T63, T63 + 105, T64 - 1):
            add("mixed", f"id-{op}-{lit}", F.Compare("id", op, lit), LANES)
        for lit in (-21, -20, -7, 0, 19, 20):
            add("mixed", f"neg-{op}-{lit}", F.Compare("neg", op, lit), LANES)
        for lit in (TS0 - 1, TS0, TS0 + 1001 * 4 - 1, TS0 + 1001 * 4, TS0 + 1001 * 39,
                    TS0 + 1001 * 39 + 1):
            add("mixed", f"ts-{op}-{lit - TS0}", F.Compare("ts", op, lit), LANES)
        for lit in (-np.inf, -9.5, 0.0, 0.25, np.inf, np.nan):
            add("mixed", f"f-{op}-{lit}", F.Compare("f", op, float(lit)), LANES)
        add("mixed", f"u32-{op}-top", F.Compare("u32", op, 2**32 - 2), LANES)
    # literals the lane's dtype cannot hold exactly, or of another kind
    for op in ("eq", "ne", "lt", "ge"):
        add("mixed", f"id-{op}-negative", F.Compare("id", op, -1), WALK)
        add("mixed", f"id-{op}-2^64", F.Compare("id", op, T64), WALK)
        add("mixed", f"neg-{op}-2^63", F.Compare("neg", op, T63), WALK)
        add("mixed", f"id-{op}-fraction", F.Compare("id", op, 11.5), WALK)
        add("mixed", f"id-{op}-string", F.Compare("id", op, "11"), WALK)
        add("mixed", f"f-{op}-2^60+1", F.Compare("f", op, (1 << 60) + 1), WALK)
        add("mixed", f"neg-{op}-bool", F.Compare("neg", op, True), WALK)
        add("mixed", f"f-{op}-npfloat32", F.Compare("f", op, np.float32(0.3)), WALK)
    add("mixed", "id-eq-whole-float", F.Compare("id", "eq", 11.0), LANES)
    add("mixed", "id-eq-np-uint64", F.Compare("id", "eq", np.uint64(T63 + 105)), LANES)
    add("mixed", "neg-lt-np-int64", F.Compare("neg", "lt", np.int64(-7)), LANES)
    add("mixed", "f-gt-int", F.Compare("f", "gt", 3), LANES)
    add("mixed", "id-eq-slot", F.Compare("id", "eq", F.Slot(0, "id")), WALK)
    # statistics that are not numbers, no statistics, no such column
    add("mixed", "b-eq", F.Compare("b", "eq", b"k05"), WALK)
    add("mixed", "b-lt", F.Compare("b", "lt", b"k12"), WALK)
    add("mixed", "b-eq-number", F.Compare("b", "eq", 5), WALK)
    add("mixed", "b-inset", F.InSet("b", (b"k01", b"k30")), WALK)
    add("mixed", "flag-eq", F.Compare("flag", "eq", True), WALK)
    add("mixed", "nostat-eq", F.Compare("nostat", "eq", 3), LANES)
    add("mixed", "nostat-inset", F.InSet("nostat", (3, 4)), LANES)
    add("mixed", "missing-eq", F.Compare("nope", "eq", 3), LANES)
    add("mixed", "missing-inset", F.InSet("nope", ()), LANES)
    # sets of 0, 1 and 100 values
    add("mixed", "id-inset-0", F.InSet("id", ()), LANES)
    add("mixed", "id-inset-1", F.InSet("id", (T63 + 105,)), LANES)
    add("mixed", "id-inset-1-gap", F.InSet("id", (15,)), LANES)
    add("mixed", "id-inset-100",
        F.InSet("id", tuple(T63 + 90 + 3 * i for i in range(100))), LANES)
    add("mixed", "id-inset-100-np",
        F.InSet("id", tuple(np.arange(5, 505, 5, dtype=np.uint64))), LANES)
    add("mixed", "id-inset-negative", F.InSet("id", (11, -1)), WALK)
    add("mixed", "id-inset-2^64", F.InSet("id", (T64, 11)), WALK)
    add("mixed", "id-inset-string", F.InSet("id", (11, "x")), WALK)
    add("mixed", "neg-inset", F.InSet("neg", (-100, -7, 100)), LANES)
    add("mixed", "f-inset-nan", F.InSet("f", (float("nan"),)), LANES)
    add("mixed", "f-inset-inf-nan", F.InSet("f", (float("nan"), float("-inf"), 0.25)), LANES)
    add("mixed", "ts-inset", F.InSet("ts", (TS0 + 1001 * 17, TS0 + 5)), LANES)
    # the nodes that stay conservative, and trees
    add("mixed", "probe", F.InSetProbe("id", 0, 1, 4), LANES)
    add("mixed", "not", F.Not(F.Compare("id", "eq", 11)), LANES)
    add("mixed", "and", F.And(F.Compare("id", "ge", 20), F.Compare("neg", "lt", 0)), LANES)
    add("mixed", "or", F.Or(F.Compare("id", "lt", 3), F.Compare("f", "gt", 9.0)), LANES)
    add("mixed", "and-empty", F.And(), LANES)
    add("mixed", "or-empty", F.Or(), LANES)
    add("mixed", "nested", F.And(
        F.Or(F.Compare("id", "eq", 11), F.InSet("id", (T63 + 101, T64 - 1))),
        F.Not(F.Compare("neg", "eq", 0)),
        F.Or(F.time_range_pred("ts", TS0 + 1001 * 8, TS0 + 1001 * 30),
             F.Compare("f", "eq", float("inf")))), LANES)
    add("mixed", "nested-with-binary", F.And(
        F.Compare("id", "ge", 20),
        F.Or(F.Compare("b", "eq", b"k25"), F.Compare("neg", "gt", 17))), WALK)
    add("mixed", "time-range", F.time_range_pred("ts", TS0 + 1001 * 10, TS0 + 1001 * 20), LANES)
    add("mixed", "nothing-kept",
        F.And(F.Compare("neg", "gt", 100), F.Compare("id", "lt", 0)), LANES)
    # the data table's own predicate, on one row group and on 600
    for foot in ("one", "many"):
        for m in (7, T63 + 7, T64 - 9, 8):
            add(foot, f"metric-{m}-window", F.And(
                F.Compare("metric_id", "eq", m),
                F.Compare("ts", "ge", TS0 + 10_000), F.Compare("ts", "lt", TS0 + 30_000)), LANES)
        add(foot, "tsids-100", F.And(
            F.Compare("metric_id", "eq", T63 + 7),
            F.Compare("ts", "ge", TS0), F.Compare("ts", "lt", TS0 + 60_000),
            F.InSet("tsid", tuple(
                (T63 // 64 + 12345) * (70 + 2 * i) % T64 for i in range(100)))), LANES)
        add(foot, "value-lt", F.Compare("value", "lt", 12.5), LANES)
        add(foot, "metric-ne", F.Compare("metric_id", "ne", 7), LANES)
        add(foot, "negative-metric", F.Compare("metric_id", "ge", -5), WALK)
    return out


def count_walks(monkeypatch) -> list:
    walks = []
    real = read_mod._row_group_stats

    def counted(*args):
        walks.append(1)
        return real(*args)

    monkeypatch.setattr(read_mod, "_row_group_stats", counted)
    return walks


@pytest.mark.parametrize("foot, predicate, served", _cases())
def test_the_lanes_keep_what_the_walk_keeps(foot, predicate, served, monkeypatch):
    meta, schema = footer(foot)
    want = reference_keep(meta, schema, predicate)
    walks = count_walks(monkeypatch)
    cold = read_mod._Footer(meta, schema)
    with scanstats.scan_stats() as st:
        assert read_mod._select_row_groups(cold, predicate) == want
    assert cold.lanes is not None and len(walks) == 1
    assert st.counts == {"footer_walks": 1}
    # warm: the lanes are there; only a leaf they cannot decide walks
    walks.clear()
    with scanstats.scan_stats() as st:
        assert read_mod._select_row_groups(cold, predicate) == want
    assert len(walks) == (served == WALK), served
    assert st.counts == {"footer_walks" if served == WALK else "footer_lanes": 1}


@pytest.mark.parametrize("foot", ["mixed", "one", "many"])
def test_no_predicate_keeps_every_row_group_and_reads_no_metadata(foot, monkeypatch):
    meta, schema = footer(foot)
    walks = count_walks(monkeypatch)
    f = read_mod._Footer(meta, schema)
    with scanstats.scan_stats() as st:
        keep = read_mod._select_row_groups(f, None)
    assert keep == reference_keep(meta, schema, None) == list(range(meta.num_row_groups))
    assert not walks and f.lanes is None and not st.counts


def test_the_lanes_hold_each_column_in_its_own_domain():
    meta, schema = footer("mixed")
    lanes = read_mod._footer_lanes(list(read_mod._row_group_stats(meta, schema)), schema)
    assert {k: (None if v is None else v[0].dtype.name) for k, v in lanes.items()} == {
        "id": "uint64", "neg": "int64", "ts": "int64", "f": "float64",
        "u32": "uint64", "b": None, "flag": None}
    lo, hi, usable = lanes["id"]
    assert usable.all()
    assert (int(lo[4]), int(hi[4])) == (T63 - 2, T63 + 1)    # the straddler, exact
    assert int(lo[5]) == T63 + 100 and int(hi[9]) == T64 - 1  # exact above 2**63
    lo, hi, usable = lanes["ts"]
    assert usable.all() and int(lo[0]) == TS0 and int(hi[9]) == TS0 + 1001 * 39
    lo, hi, _ = lanes["f"]
    assert lo[0] == -np.inf and hi[9] == np.inf
    # 600 row groups: the lanes are the columns' own extremes a group
    meta, schema = footer("many")
    _, tsid = footer("many-lanes")
    lanes = read_mod._footer_lanes(list(read_mod._row_group_stats(meta, schema)), schema)
    lo, hi, usable = lanes["tsid"]
    pairs = tsid.reshape(600, 2)
    assert usable.all()
    np.testing.assert_array_equal(lo, pairs.min(axis=1))
    np.testing.assert_array_equal(hi, pairs.max(axis=1))
    # a row group the walk left out (no statistics, no min/max, a wrapped
    # u64 range) is not usable and is kept; one integer outside the
    # column's domain leaves the whole column to the scalar form
    u64 = pa.schema([("id", pa.uint64()), ("n", pa.int64())])
    lanes = read_mod._footer_lanes(
        [{"id": (1, 5), "n": (0, 1)}, {"n": (2, T63)}, {"id": (T63, T64 - 1)}], u64)
    lo, hi, usable = lanes["id"]
    assert usable.tolist() == [True, False, True] and lanes["n"] is None
    keep = F.prune_lanes(F.Compare("id", "eq", 7), lanes, 3, None)
    assert keep.tolist() == [False, True, False]


def test_racing_reads_of_one_footer_build_the_same_lanes_and_keep_the_same_groups():
    """Sixteen threads prune by one cached footer at once, cold, under a
    short switch interval: every keep list is the reference's, whichever
    build of the lanes won the assignment."""
    import sys
    import threading

    meta, schema = footer("many")
    preds = [F.And(F.Compare("metric_id", "eq", m), F.Compare("ts", "ge", TS0 + 10_000 * k))
             for m in (7, T63 + 7, T64 - 9, -5) for k in range(4)]
    want = [reference_keep(meta, schema, p) for p in preds]
    shared = read_mod._Footer(meta, schema)
    got: list = [None] * len(preds)
    start = threading.Barrier(len(preds))

    def prune(i):
        start.wait(timeout=30)
        got[i] = [read_mod._select_row_groups(shared, preds[i]) for _ in range(5)]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=prune, args=(i,)) for i in range(len(preds))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == [[w] * 5 for w in want]
    assert shared.lanes is not None and set(shared.lanes) == {"metric_id", "tsid", "ts", "value"}
