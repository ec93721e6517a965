"""The aggregate pushdown's packed pass (storage/read.py
`_packed_downsample_pass`) against the full-row pass it replaced.

The pass orders only the rows the predicate and the series set keep, does
no ordering where they arrive in (series, ts) order, and ranks `__seq__`
only where a (series, ts) repeats. `full_row_pass` below is the pass as it
was: every row's seq ranked and packed into one key, rejected rows sunk
above bit 63, one stable argsort over every row, the last of each
(series, ts) kept. Both must hand the fold the same (ts, sid, values)
arrays, element for element, whatever order a segment's SSTs arrive in.
"""

import asyncio
import urllib.parse

import numpy as np
import pyarrow as pa
import pytest
from aiohttp.test_utils import TestClient, TestServer

from horaedb_tpu.objstore import MemStore
from horaedb_tpu.ops import filter as F
from horaedb_tpu.server.config import Config
from horaedb_tpu.server.main import build_app
from horaedb_tpu.storage import scanstats
from horaedb_tpu.storage.config import UpdateMode
from horaedb_tpu.storage.read import ParquetReader, _FoldSpec
from horaedb_tpu.storage.types import SEQ_COLUMN_NAME, StorageSchema
from tests.conftest import async_test
from tests.test_engine import make_remote_write
from tests.test_stage_funnel import counter

SEQ_BITS, TS_BITS = 12, 34
ORDERS = ("in_order", "sorted", "dedup")


def full_row_pass(ts, sid, mask, seq, val, seq_bits=SEQ_BITS):
    """The packed pass before it looked at its input: (sid, ts, seq-rank)
    of EVERY row in one u64, rejected rows at the sink, one stable argsort,
    keep-last within each (sid, ts). None past the seq or span budget."""
    n = len(ts)
    if n == 0:
        return (np.empty(0, np.int64),) * 3
    uniq_seq = np.unique(seq)
    if len(uniq_seq) > (1 << seq_bits):
        return None
    ts_min = int(ts.min())
    if int(ts.max()) - ts_min >= (1 << TS_BITS):
        return None
    srank = (np.searchsorted(uniq_seq, seq).astype(np.uint64)
             if len(uniq_seq) > 1 else np.zeros(n, np.uint64))
    shift_ts = np.uint64(seq_bits)
    shift_sid = np.uint64(seq_bits + TS_BITS)
    packed = ((sid.astype(np.int64).astype(np.uint64) << shift_sid)
              | ((ts - ts_min).astype(np.uint64) << shift_ts) | srank)
    sink = np.uint64(1 << 63)
    packed = np.where(mask, packed, sink)
    perm = np.argsort(packed, kind="stable")
    packed_s = packed[perm]
    group = packed_s >> shift_ts
    keep = np.empty(n, dtype=bool)
    keep[:-1] = group[:-1] != group[1:]
    keep[-1] = True
    keep &= packed_s < sink
    idx = perm[keep]
    return ts[idx], sid[idx].astype(np.int32), val[idx]


def reader() -> ParquetReader:
    schema = StorageSchema.try_new(
        pa.schema([("tsid", pa.uint64()), ("ts", pa.int64()), ("value", pa.float64())]),
        2, UpdateMode.OVERWRITE)
    return ParquetReader(MemStore(), None, schema)


def sst(tsids, ts, seq, rng):
    """One SST's rows: the (tsid, ts) cells pk-sorted, one write seq (an
    int: the flush's) or one seq a row (an array), seeded values."""
    tsids, ts = np.asarray(tsids, np.uint64), np.asarray(ts, np.int64)
    order = np.lexsort((ts, tsids))
    seq = np.broadcast_to(np.asarray(seq, np.uint64), len(ts))
    return tsids[order], ts[order], seq[order], rng.normal(size=len(ts)) * 1e3


def grid(tsids, ts):
    """Every (tsid, ts) pair of two ranges, as two flat lanes."""
    a, b = np.meshgrid(np.asarray(tsids), np.asarray(ts), indexing="ij")
    return a.ravel(), b.ravel()


T0 = 1_700_006_400_000
STEP = 10_000


def layout(name: str, rng):
    """A segment's SSTs in the order the reader concatenates them."""
    series, times = np.arange(100, 140), T0 + STEP * np.arange(300)
    if name == "one_sst":
        return [sst(*grid(series, times), 7, rng)]
    if name == "pk_disjoint":  # three shards, each its own run of series
        return [sst(*grid(part, times), 7 + i, rng)
                for i, part in enumerate(np.array_split(series, 3))]
    if name == "time_split_overlap":  # two compaction outputs split in time
        return [sst(*grid(series, times[:150]), 3, rng),
                sst(*grid(series, times[150:]), 9, rng)]
    if name in ("dup_newer_first", "dup_newer_last"):
        old = sst(*grid(series, times), 5, rng)
        new = sst(*grid(series[::4], times[100:200]), 11, rng)
        return [new, old] if name == "dup_newer_first" else [old, new]
    if name == "dup_equal_seq":  # one seq twice: the later in concatenation wins
        return [sst(*grid(series, times[:80]), 4, rng),
                sst(*grid(series[5:9], times[40:120]), 4, rng)]
    if name == "dup_at_the_seam":  # in order but for one key, twice at the join
        return [sst(*grid(series[:5], times[:200]), 4, rng),
                sst(*grid(series[4:], times[199:200]), 6, rng)]
    if name == "one_row":
        return [sst([120], [T0 + 5 * STEP], 2, rng)]
    if name == "span_budget":
        return [sst([100, 101], [T0, T0 + (1 << TS_BITS)], 2, rng)]
    if name in ("many_seqs", "many_seqs_dup"):  # 5,000 writes of one row each
        tsids, ts = grid(np.arange(100, 125), T0 + STEP * np.arange(200))
        runs = [sst(tsids, ts, np.arange(1, 5_001, dtype=np.uint64)[rng.permutation(5_000)],
                    rng)]
        if name == "many_seqs_dup":
            runs.append(sst([110], [T0 + 70 * STEP], 9_999, rng))
        return runs
    raise AssertionError(name)


def as_table(runs):
    tsid, ts, seq, val = (np.concatenate(c) for c in zip(*runs))
    return pa.table({"tsid": tsid, "ts": ts, "value": val, SEQ_COLUMN_NAME: seq})


def both_passes(runs, series_ids, predicate=None, num_series=None):
    """(new pass's answer, the orders it counted, the full-row pass's
    answer, the collector's notes) on one concatenated segment."""
    table = as_table(runs)
    spec = _FoldSpec(np.asarray(series_ids, np.uint64), T0, 60_000, 5, True)
    sid, hit = spec.dense_sid(table.column("tsid").to_numpy())
    before = {o: counter("horaedb_pushdown_pack_total", order=o) for o in ORDERS}
    with scanstats.scan_stats() as st:
        got = reader()._packed_downsample_pass(
            table, predicate, sid, hit, "ts", "value",
            len(series_ids) if num_series is None else num_series)
    counted = {o: counter("horaedb_pushdown_pack_total", order=o) - before[o] for o in ORDERS}
    mask = hit & F.eval_predicate_host(predicate, table)
    cols = {c: table.column(c).to_numpy() for c in ("ts", "value", SEQ_COLUMN_NAME)}
    want = full_row_pass(cols["ts"], sid, mask, cols[SEQ_COLUMN_NAME], cols["value"])
    notes = {o: st.counts.get("pack_" + o, 0) for o in ORDERS}
    return got, counted, want, notes


def assert_same(got, want):
    assert got is not None and want is not None
    for g, w, name in zip(got, want, ("ts", "sid", "values")):
        assert g.dtype == w.dtype, name
        np.testing.assert_array_equal(g, w, err_msg=name)


ALL_SERIES = np.arange(100, 140)
WINDOW = F.And(F.Compare("ts", "ge", T0 + 30 * STEP), F.Compare("ts", "lt", T0 + 240 * STEP))


@pytest.mark.parametrize("name, order", [
    ("one_sst", "in_order"),
    ("pk_disjoint", "in_order"),
    ("time_split_overlap", "sorted"),
    ("dup_newer_first", "dedup"),
    ("dup_newer_last", "dedup"),
    ("dup_equal_seq", "dedup"),
    ("dup_at_the_seam", "dedup"),
    ("one_row", "in_order"),
])
@pytest.mark.parametrize("predicate", [None, WINDOW], ids=["all_rows", "window"])
def test_equals_the_full_row_pass(name, order, predicate):
    runs = layout(name, np.random.default_rng(sum(map(ord, name))))
    got, counted, want, notes = both_passes(runs, ALL_SERIES, predicate)
    assert_same(got, want)
    assert counted == notes == {o: int(o == order) for o in ORDERS}


@pytest.mark.parametrize("name", ["one_sst", "time_split_overlap", "dup_newer_first"])
def test_series_set_misses(name):
    """Half the series asked for, and ids the segment lacks: misses drop
    before the order is looked at."""
    runs = layout(name, np.random.default_rng(3))
    ids = np.concatenate([np.arange(90, 100), ALL_SERIES[::2], [141, 150]])
    got, _, want, _ = both_passes(runs, ids, WINDOW)
    assert_same(got, want)
    assert len(got[0]) and set(np.unique(got[1])) <= set(range(10, 30))


@pytest.mark.parametrize("predicate", [
    F.And(WINDOW, F.InSet("tsid", tuple(range(100, 140, 3)) + (2**63 + 5,))),
    F.Or(F.Compare("ts", "lt", T0 + 10 * STEP), F.Not(F.Compare("tsid", "ne", 120))),
    F.And(WINDOW, F.Compare("tag", "eq", b"h7")),
], ids=["inset", "or_not", "binary_tag"])
def test_predicate_shapes(predicate):
    """Numeric leaves evaluate on numpy lanes, a binary column's on arrow:
    either way the rows kept are the ones arrow's evaluation keeps."""
    runs = layout("dup_newer_last", np.random.default_rng(8))
    table = as_table(runs)
    table = table.append_column("tag", pa.array(
        [b"h%d" % (t % 10) for t in table.column("tsid").to_pylist()], pa.binary()))
    spec = _FoldSpec(ALL_SERIES.astype(np.uint64), T0, 60_000, 5, True)
    sid, hit = spec.dense_sid(table.column("tsid").to_numpy())
    got = reader()._packed_downsample_pass(table, predicate, sid, hit, "ts", "value",
                                            len(ALL_SERIES))
    want = full_row_pass(table.column("ts").to_numpy(), sid,
                         hit & F.eval_predicate_host(predicate, table),
                         table.column(SEQ_COLUMN_NAME).to_numpy(),
                         table.column("value").to_numpy())
    assert_same(got, want)
    assert len(got[0])


def test_every_row_rejected():
    runs = layout("time_split_overlap", np.random.default_rng(4))
    got, counted, want, _ = both_passes(runs, ALL_SERIES, F.Compare("ts", "lt", T0))
    assert_same(got, want)
    assert len(got[0]) == 0 and counted["in_order"] == 1


def test_no_rows():
    runs = [sst([], [], 1, np.random.default_rng(5))]
    got, counted, want, _ = both_passes(runs, ALL_SERIES)
    assert [len(g) for g in got] == [0, 0, 0] and counted["in_order"] == 1
    assert_same(got, want)


def test_span_and_series_budgets_fall_back():
    """The key's budgets hold as they did: a span of 2^34 ms or 2^17 series
    hands the segment to the fused pass, and nothing is counted."""
    runs = layout("span_budget", np.random.default_rng(6))
    got, counted, want, _ = both_passes(runs, ALL_SERIES)
    assert got is None and want is None and not any(counted.values())
    runs = layout("one_sst", np.random.default_rng(6))
    got, counted, _, _ = both_passes(runs, ALL_SERIES, num_series=1 << 17)
    assert got is None and not any(counted.values())


def test_seq_budget_binds_only_on_duplicates():
    """5,000 distinct seqs: with no (series, ts) repeated the pass needs no
    rank and answers; with one repeat it has to rank, and past 2^12 seqs it
    hands the segment to the fused pass as before."""
    rng = np.random.default_rng(7)
    runs = layout("many_seqs", rng)
    got, counted, want, _ = both_passes(runs, ALL_SERIES, WINDOW)
    assert want is None  # the full-row pass refused it for its seqs alone
    table = as_table(runs)
    sid, hit = _FoldSpec(ALL_SERIES.astype(np.uint64), T0, 60_000, 5, True).dense_sid(
        table.column("tsid").to_numpy())
    wide = full_row_pass(table.column("ts").to_numpy(), sid,
                         hit & F.eval_predicate_host(WINDOW, table),
                         table.column(SEQ_COLUMN_NAME).to_numpy(),
                         table.column("value").to_numpy(), seq_bits=13)
    assert_same(got, wide)
    assert counted["in_order"] == 1

    got, counted, want, _ = both_passes(layout("many_seqs_dup", rng), ALL_SERIES, WINDOW)
    assert got is None and want is None and not any(counted.values())


@pytest.mark.parametrize("seed", range(6))
def test_random_layouts(seed):
    """Random segments: 1-4 SSTs over random series and time ranges, some
    overwriting others' cells, seqs in any order, concatenated in any
    order, under a random window and series subset."""
    rng = np.random.default_rng([36, seed])
    runs = []
    for _ in range(int(rng.integers(1, 5))):
        lo, hi = np.sort(rng.choice(np.arange(100, 141), 2, replace=False))
        t_lo = int(rng.integers(0, 200))
        tsids, ts = grid(np.arange(lo, hi), T0 + STEP * np.arange(t_lo, t_lo + 100))
        keep = rng.random(len(ts)) < 0.7
        runs.append(sst(tsids[keep], ts[keep], int(rng.integers(1, 50)), rng))
    runs = [runs[i] for i in rng.permutation(len(runs))]
    ids = np.sort(rng.choice(ALL_SERIES, 25, replace=False))
    lo = T0 + STEP * int(rng.integers(0, 150))
    pred = F.And(F.Compare("ts", "ge", lo), F.Compare("ts", "lt", lo + 120 * STEP))
    got, counted, want, _ = both_passes(runs, ids, pred)
    assert_same(got, want)
    assert sum(counted.values()) == 1


# -- the served PromQL answers, settled and overlapped stores -----------------

QUERIES = ("avg_over_time(cpu[10m])", "max by (dc) (max_over_time(cpu[10m]))")


async def answers(client, monkeypatch, reference: bool) -> dict:
    """The query_range bodies, byte for byte, by the pass as it is or by
    the full-row pass patched in its place."""
    real = ParquetReader._packed_downsample_pass

    def full_row(self, table, predicate, sid, sid_valid, ts_column, value_column, num_series):
        if num_series >= 1 << 17:
            return None
        mask = sid_valid & F.eval_predicate_host(predicate, table)
        return full_row_pass(table.column(ts_column).to_numpy(), sid, mask,
                             table.column(SEQ_COLUMN_NAME).to_numpy(),
                             table.column(value_column).to_numpy())

    if reference:
        monkeypatch.setattr(ParquetReader, "_packed_downsample_pass", full_row)
    try:
        out = {}
        for q in QUERIES:
            params = {"query": q, "start": T0 // 1000 + 600, "end": T0 // 1000 + 7200,
                      "step": 600}
            r = await client.get("/api/v1/query_range?" + urllib.parse.urlencode(params))
            assert r.status == 200, await r.text()
            out[q] = await r.read()
        return out
    finally:
        monkeypatch.setattr(ParquetReader, "_packed_downsample_pass", real)


async def explained(client) -> dict:
    params = {"query": QUERIES[0], "start": T0 // 1000 + 600, "end": T0 // 1000 + 7200,
              "step": 600, "explain": 1}
    r = await client.get("/api/v1/query_range?" + urllib.parse.urlencode(params))
    body = await r.json()
    assert r.status == 200, body
    return body["explain"]


async def settled_to_one_sst(client) -> dict:
    """/compact only triggers the merge: the explain of a query once the
    segment reads as one SST."""
    for _ in range(600):
        explain = await explained(client)
        if explain["ssts"]["selected"] == 1:
            return explain
        await asyncio.sleep(0.05)
    raise AssertionError(f"compaction never settled: {explain['ssts']}")


def fleet_writes(rng, rounds: int, start_s: int, scale: float = 1.0) -> list[bytes]:
    """`rounds` remote-write requests of 12 series x 60 samples each, 10 s
    apart from `start_s` seconds into the segment."""
    out = []
    for r in range(rounds):
        series = []
        for h in range(12):
            ts = T0 + 1000 * (start_s + 600 * r) + STEP * np.arange(60)
            series.append(({"__name__": "cpu", "host": f"h{h}", "dc": f"dc{h % 3}"},
                           [(int(t), float(v)) for t, v in
                            zip(ts, scale * rng.normal(50, 20, len(ts)))]))
        out.append(make_remote_write(series))
    return out


@async_test
async def test_served_promql_equals_the_full_row_pass(tmp_path, monkeypatch):
    """A compacted store answers in (series, ts) order with no sort; the same
    store with one more flush that overwrites part of it takes the dedup
    step; both answer the full-row pass's bytes, and the overwrite wins."""
    monkeypatch.setenv("HORAEDB_SERVING", "off")  # no cache or rollup answers
    app = await build_app(Config.from_toml(
        f'port = 0\n[metric_engine.storage.object_store]\ntype = "Local"\n'
        f'data_dir = "{tmp_path}/data"\n'))
    client = TestClient(TestServer(app))
    await client.start_server()
    rng = np.random.default_rng(36)
    try:
        for payload in fleet_writes(rng, 12, 0):
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200, await r.text()
        assert (await explained(client))["fold"]["pack_order"]["sorted"] == 1  # 12 flushes
        assert (await client.get("/compact")).status == 200
        orders = (await settled_to_one_sst(client))["fold"]["pack_order"]
        assert orders == {"in_order": 1, "sorted": 0, "dedup": 0}, orders
        settled = await answers(client, monkeypatch, reference=False)
        assert settled == await answers(client, monkeypatch, reference=True)

        # an overlapping flush SST: the third quarter hour's cells rewritten
        for payload in fleet_writes(rng, 2, 2700, scale=3.0):
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200, await r.text()
        overlapped = await answers(client, monkeypatch, reference=False)
        assert overlapped == await answers(client, monkeypatch, reference=True)
        assert overlapped != settled
        assert (await explained(client))["fold"]["pack_order"]["dedup"] == 1
    finally:
        await client.close()
