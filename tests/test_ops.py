"""Device kernels vs numpy oracles (SURVEY §4: 'differential tests of device
kernels vs numpy oracles at small scale')."""

import numpy as np
import pytest

from horaedb_tpu.ops import aggregate, dedup, filter as filter_ops, merge, sort
from horaedb_tpu.ops.blocks import Block, sort_sentinel


def rand_columns(rng, n, key_space=10):
    return {
        "pk1": rng.integers(0, key_space, n).astype(np.int64),
        "pk2": rng.integers(0, key_space, n).astype(np.int64),
        "ts": rng.integers(0, 1_000_000, n).astype(np.int64),
        "value": rng.normal(size=n).astype(np.float64),
        "__seq__": rng.integers(0, 100, n).astype(np.uint64),
    }


class TestBlock:
    def test_pad_and_roundtrip(self):
        rng = np.random.default_rng(0)
        arrays = rand_columns(rng, 100)
        b = Block.from_numpy(arrays, pad_multiple=64, pad_keys=("pk1", "pk2"))
        assert b.padded_len == 128
        assert b.num_valid == 100
        back = b.to_numpy()
        for k in arrays:
            np.testing.assert_array_equal(back[k], arrays[k])
        # padding keys are max sentinels
        pad_region = np.asarray(b.columns["pk1"])[100:]
        assert (pad_region == np.iinfo(np.int64).max).all()
        pad_vals = np.asarray(b.columns["value"])[100:]
        assert (pad_vals == 0).all()

    def test_sentinels(self):
        assert sort_sentinel(np.int64) == np.iinfo(np.int64).max
        assert sort_sentinel(np.float64) == np.inf
        assert sort_sentinel(np.uint64) == np.iinfo(np.uint64).max

    def test_arrow_roundtrip(self):
        import pyarrow as pa

        batch = pa.RecordBatch.from_pydict(
            {"a": pa.array([1, 2, 3], type=pa.int64()), "v": pa.array([1.0, 2.0, 3.0])}
        )
        b = Block.from_arrow(batch, pad_multiple=8)
        out = b.to_arrow()
        assert out.num_rows == 3
        assert out.column(0).to_pylist() == [1, 2, 3]


class TestSort:
    def test_matches_numpy_lexsort(self):
        rng = np.random.default_rng(1)
        cols = rand_columns(rng, 1000, key_space=20)
        b = Block.from_numpy(cols, pad_multiple=256, pad_keys=("pk1", "pk2", "__seq__"))
        out = sort.sort_columns(b.columns, ["pk1", "pk2", "__seq__"])
        got = {k: np.asarray(v)[: b.num_valid] for k, v in out.items()}

        order = np.lexsort((cols["__seq__"], cols["pk2"], cols["pk1"]))
        for k in cols:
            np.testing.assert_array_equal(got[k], cols[k][order])

    def test_stability(self):
        """Equal keys keep input order (required for the seq tie-break)."""
        keys = np.array([2, 1, 2, 1, 2], dtype=np.int64)
        payload = np.arange(5, dtype=np.int64)
        out = sort.sort_columns({"k": keys, "p": payload}, ["k"])
        np.testing.assert_array_equal(np.asarray(out["p"]), [1, 3, 0, 2, 4])


    @pytest.mark.parametrize("n", [1, 7, 1000, 5000])
    @pytest.mark.parametrize("on_device", [False, True])
    def test_sort_permutation_single_key_passes_match_lexsort(self, n, on_device):
        """The device sort is a chain of single-key u64 passes (ops/sort.py):
        u64 ids past 2^63, negative i64, floats and bools must order exactly
        as numpy's lexsort, with padding rows kept out of the answer."""
        import jax.numpy as jnp

        rng = np.random.default_rng(n)
        keys = [
            rng.integers(0, 5, n).astype(np.uint64) * np.uint64(1 << 62),
            rng.integers(-3, 3, n).astype(np.int64) * (1 << 61),
            np.round(rng.normal(size=n), 1) + 0.0,  # no -0.0: numpy ties it with 0.0
            rng.integers(0, 2, n).astype(bool),
        ]
        lanes = [jnp.asarray(k) for k in keys] if on_device else keys
        perm = np.asarray(sort.sort_permutation(lanes))
        assert perm.shape == (n,) and sorted(perm) == list(range(n))
        np.testing.assert_array_equal(perm, np.lexsort(tuple(reversed(keys))))

    def test_pow2_rows_classes(self):
        assert [sort.pow2_rows(n) for n in (0, 1, 1024, 1025, 10_000)] == \
            [1024, 1024, 1024, 2048, 16384]
        assert sort.pow2_rows(10_000, 8192) == 16384
        assert sort.pow2_rows(100, 8192) == 8192

    @pytest.mark.parametrize("arr", [
        np.array([0, 1, (1 << 63) - 1, 1 << 63, (1 << 64) - 1], dtype=np.uint64),
        np.array([-(1 << 63), -1, 0, 1, (1 << 63) - 1], dtype=np.int64),
        np.array([-5, 0, 7], dtype=np.int32),
        np.array([False, True]),
        np.array([-np.inf, -1.5, -0.0, 0.0, 1e-300, 2.0, np.inf]),
    ], ids=["u64", "i64", "i32", "bool", "f64"])
    @pytest.mark.parametrize("traced", [False, True])
    def test_order_u64_is_strictly_monotone(self, arr, traced):
        import jax.numpy as jnp

        out = np.asarray(sort.order_u64(jnp.asarray(arr) if traced else arr))
        assert out.dtype == np.uint64
        assert np.all(out[:-1] < out[1:]), arr.dtype


class TestFilter:
    def test_compare_and_bool_algebra(self):
        rng = np.random.default_rng(2)
        cols = rand_columns(rng, 500)
        b = Block.from_numpy(cols, pad_multiple=512)
        pred = filter_ops.And(
            filter_ops.Compare("pk1", "eq", 3),
            filter_ops.Or(
                filter_ops.Compare("value", "gt", 0.0),
                filter_ops.Compare("ts", "lt", 500_000),
            ),
        )
        mask = np.asarray(filter_ops.eval_predicate(pred, b.columns))[: b.num_valid]
        expect = (cols["pk1"] == 3) & ((cols["value"] > 0.0) | (cols["ts"] < 500_000))
        np.testing.assert_array_equal(mask, expect)

    def test_in_set(self):
        cols = {"tsid": np.array([1, 5, 9, 5, 2], dtype=np.int64)}
        mask = np.asarray(
            filter_ops.eval_predicate(filter_ops.InSet("tsid", (5, 2)), cols)
        )
        np.testing.assert_array_equal(mask, [False, True, False, True, True])

    def test_in_set_u64_ids_exact(self):
        """Mixed-magnitude u64 ids must not promote to float64 (which
        corrupts ids > 2**53) — the seahash TSID-membership case."""
        ids = np.array(
            [48143032671202699, 12578593541292850658, 14329183490546117337, 7],
            dtype=np.uint64,
        )
        pred = filter_ops.InSet("tsid", (48143032671202699, 12578593541292850658))
        mask = np.asarray(filter_ops.eval_predicate(pred, {"tsid": ids}))
        np.testing.assert_array_equal(mask, [True, True, False, False])

    def test_in_set_unrepresentable_values_dropped(self):
        """Negative / fractional values can never equal a u64 column —
        dropped, not crashed (numpy raises OverflowError on a raw cast)."""
        ids = np.array([5, 7], dtype=np.uint64)
        pred = filter_ops.InSet("tsid", (-1, 5, 2**70, 6.5))
        mask = np.asarray(filter_ops.eval_predicate(pred, {"tsid": ids}))
        np.testing.assert_array_equal(mask, [True, False])
        all_bad = filter_ops.InSet("tsid", (-1,))
        mask = np.asarray(filter_ops.eval_predicate(all_bad, {"tsid": ids}))
        np.testing.assert_array_equal(mask, [False, False])

    def test_inset_probe_template_stable_across_value_sets(self):
        """split_literals turns InSet into a dynamic membership probe: two
        different tsid sets of the same size bucket share one template (the
        jit cache key), and evaluation stays exact."""
        ids = np.array([1, 5, 9, 2**63 + 3], dtype=np.uint64)
        p1 = filter_ops.InSet("tsid", (5, 2**63 + 3, 9))
        p2 = filter_ops.InSet("tsid", (1, 2, 3))
        t1, l1 = filter_ops.split_literals(p1)
        t2, l2 = filter_ops.split_literals(p2)
        assert t1 == t2  # same bucket (4) -> same template -> same kernel
        a1 = filter_ops.literal_arrays(t1, l1, {"tsid": np.dtype(np.uint64)})
        a2 = filter_ops.literal_arrays(t2, l2, {"tsid": np.dtype(np.uint64)})
        m1 = np.asarray(filter_ops.eval_predicate(t1, {"tsid": ids}, a1))
        m2 = np.asarray(filter_ops.eval_predicate(t2, {"tsid": ids}, a2))
        np.testing.assert_array_equal(m1, [False, True, True, True])
        np.testing.assert_array_equal(m2, [True, False, False, False])

    def test_inset_probe_large_set_binary_search_path(self):
        """Sets above the broadcast threshold use sorted binary search —
        results must match exactly, including u64 ids and empty sets."""
        rng = np.random.default_rng(11)
        members = np.unique(rng.integers(0, 2**63, 400, dtype=np.uint64))[:300]
        ids = np.concatenate([members[:50], rng.integers(0, 2**62, 500).astype(np.uint64)])
        rng.shuffle(ids)
        pred = filter_ops.InSet("tsid", tuple(int(x) for x in members))
        t, lits = filter_ops.split_literals(pred)
        assert t.padded_size > 128
        arrs = filter_ops.literal_arrays(t, lits, {"tsid": np.dtype(np.uint64)})
        mask = np.asarray(filter_ops.eval_predicate(t, {"tsid": ids}, arrs))
        np.testing.assert_array_equal(mask, np.isin(ids, members))
        # empty set -> all False
        empty = filter_ops.InSet("tsid", tuple(int(x) for x in members[:0]))
        # force the large bucket by padding manually via a 200-value set of
        # out-of-domain (negative) values that all get dropped
        big_bad = filter_ops.InSet("tsid", tuple(range(-1, -200, -1)))
        t2, l2 = filter_ops.split_literals(big_bad)
        a2 = filter_ops.literal_arrays(t2, l2, {"tsid": np.dtype(np.uint64)})
        m2 = np.asarray(filter_ops.eval_predicate(t2, {"tsid": ids}, a2))
        assert not m2.any()
        del empty

    def test_compare_out_of_domain_literal_rejected(self):
        from horaedb_tpu.common.error import HoraeError

        ids = np.array([5, 7], dtype=np.uint64)
        with pytest.raises(HoraeError, match="out of range"):
            filter_ops.eval_predicate(filter_ops.Compare("tsid", "lt", -1), {"tsid": ids})
        with pytest.raises(HoraeError, match="fractional"):
            filter_ops.eval_predicate(filter_ops.Compare("tsid", "lt", 1.5), {"tsid": ids})

    def test_none_predicate_keeps_all(self):
        cols = {"a": np.zeros(4, dtype=np.int64)}
        assert np.asarray(filter_ops.eval_predicate(None, cols)).all()

    def test_time_range_pred(self):
        cols = {"ts": np.array([5, 10, 15, 20], dtype=np.int64)}
        pred = filter_ops.time_range_pred("ts", 10, 20)
        mask = np.asarray(filter_ops.eval_predicate(pred, cols))
        np.testing.assert_array_equal(mask, [False, True, True, False])

    def test_prune_range(self):
        pred = filter_ops.And(
            filter_ops.Compare("ts", "ge", 100),
            filter_ops.Compare("ts", "lt", 200),
        )
        assert filter_ops.prune_range(pred, {"ts": (150, 180)})
        assert filter_ops.prune_range(pred, {"ts": (0, 100)})      # 100 satisfies ge
        assert not filter_ops.prune_range(pred, {"ts": (0, 99)})
        assert not filter_ops.prune_range(pred, {"ts": (200, 300)})
        assert filter_ops.prune_range(pred, {})                     # unknown col: keep
        assert filter_ops.prune_range(None, {"ts": (0, 1)})


class TestDedup:
    def test_last_value_mask_matches_pandas_style_oracle(self):
        rng = np.random.default_rng(3)
        n = 800
        cols = rand_columns(rng, n, key_space=8)
        b = Block.from_numpy(cols, pad_multiple=1024, pad_keys=("pk1", "pk2", "__seq__"))
        sorted_cols = sort.sort_columns(b.columns, ["pk1", "pk2", "__seq__"])
        keep = np.asarray(
            dedup.dedup_last_value(sorted_cols, ["pk1", "pk2"], b.num_valid)
        )
        got = {k: np.asarray(v)[keep] for k, v in sorted_cols.items()}

        # oracle: for each (pk1, pk2) keep the row with max seq (ties: later row)
        order = np.lexsort((cols["__seq__"], cols["pk2"], cols["pk1"]))
        s = {k: v[order] for k, v in cols.items()}
        expect_idx = []
        i = 0
        while i < n:
            j = i
            while j + 1 < n and s["pk1"][j + 1] == s["pk1"][i] and s["pk2"][j + 1] == s["pk2"][i]:
                j += 1
            expect_idx.append(j)
            i = j + 1
        for k in cols:
            np.testing.assert_array_equal(got[k], s[k][np.array(expect_idx)])

    def test_run_starts_and_segment_ids(self):
        import jax.numpy as jnp

        keys = jnp.asarray(np.array([1, 1, 2, 2, 2, 3], dtype=np.int64))
        valid = jnp.ones(6, dtype=bool)
        starts = np.asarray(dedup.run_starts([keys], valid))
        np.testing.assert_array_equal(starts, [True, False, True, False, False, True])
        seg = np.asarray(dedup.segment_ids(dedup.run_starts([keys], valid)))
        np.testing.assert_array_equal(seg, [0, 0, 1, 1, 1, 2])


class TestMerge:
    def test_kway_merge_equals_global_sort(self):
        rng = np.random.default_rng(4)
        parts = []
        all_rows = []
        for _ in range(5):
            cols = rand_columns(rng, 200, key_space=50)
            order = np.lexsort((cols["__seq__"], cols["pk2"], cols["pk1"]))
            cols = {k: v[order] for k, v in cols.items()}
            all_rows.append(cols)
            parts.append(
                Block.from_numpy(cols, pad_multiple=256, pad_keys=("pk1", "pk2", "__seq__"))
            )
        merged = merge.merge_sorted([p.columns for p in parts], ["pk1", "pk2", "__seq__"])
        total_valid = sum(p.num_valid for p in parts)
        got = {k: np.asarray(v)[:total_valid] for k, v in merged.items()}

        cat = {k: np.concatenate([r[k] for r in all_rows]) for k in all_rows[0]}
        order = np.lexsort((cat["__seq__"], cat["pk2"], cat["pk1"]))
        for k in cat:
            np.testing.assert_array_equal(got[k], cat[k][order])


class TestAggregate:
    def test_grouped_stats_oracle(self):
        rng = np.random.default_rng(5)
        n, g = 1000, 16
        idx = rng.integers(0, g, n).astype(np.int32)
        vals = rng.normal(size=n)
        valid = rng.random(n) < 0.9
        out = aggregate.grouped_stats(vals, idx, valid, g)
        for gi in range(g):
            sel = vals[(idx == gi) & valid]
            assert np.isclose(float(out["sum"][gi]), sel.sum())
            assert float(out["count"][gi]) == len(sel)
            if len(sel):
                assert np.isclose(float(out["min"][gi]), sel.min())
                assert np.isclose(float(out["max"][gi]), sel.max())
                assert np.isclose(float(out["mean"][gi]), sel.mean())

    def test_grouped_stats_out_of_range_dropped(self):
        """Out-of-range indices are dropped even when marked valid (the
        pre-dispatch scatter-OOB contract) — and ALL stats agree on it."""
        vals = np.array([10.0, 20.0, 30.0, 40.0])
        idx = np.array([-1, 0, 1, 2], dtype=np.int32)  # -1 and 2 OOB for g=2
        valid = np.ones(4, dtype=bool)
        out = aggregate.grouped_stats(vals, idx, valid, 2)
        np.testing.assert_allclose(np.asarray(out["sum"]), [20.0, 30.0])
        np.testing.assert_allclose(np.asarray(out["count"]), [1.0, 1.0])
        np.testing.assert_allclose(np.asarray(out["min"]), [20.0, 30.0])
        np.testing.assert_allclose(np.asarray(out["max"]), [20.0, 30.0])

    def test_downsample_oracle(self):
        rng = np.random.default_rng(6)
        n, num_series, num_buckets = 2000, 4, 10
        bucket_ms = 300_000  # 5m
        t0 = 1_000_000
        ts = t0 + rng.integers(0, num_buckets * bucket_ms, n).astype(np.int64)
        sid = rng.integers(0, num_series, n).astype(np.int32)
        vals = rng.normal(size=n)
        valid = np.ones(n, dtype=bool)
        out = aggregate.downsample(ts, sid, vals, valid, t0, bucket_ms, num_series, num_buckets)
        assert out["mean"].shape == (num_series, num_buckets)
        bucket = (ts - t0) // bucket_ms
        for s in range(num_series):
            for bkt in range(num_buckets):
                sel = vals[(sid == s) & (bucket == bkt)]
                if len(sel):
                    assert np.isclose(float(out["mean"][s, bkt]), sel.mean()), (s, bkt)
                else:
                    assert float(out["count"][s, bkt]) == 0

    def test_downsample_out_of_grid_rows_dropped(self):
        ts = np.array([0, 1_000_000_000], dtype=np.int64)
        sid = np.array([0, 0], dtype=np.int32)
        vals = np.array([1.0, 99.0])
        out = aggregate.downsample(
            ts, sid, vals, np.ones(2, dtype=bool), 0, 1000, 1, 10
        )
        assert float(out["sum"].sum()) == 1.0

    def test_downsample_sorted_matches_scatter_path(self):
        """The engine's sorted-scan downsample (block-compaction sum/count
        path, ops/blockagg.py) must agree with the general scatter
        implementation."""
        rng = np.random.default_rng(8)
        num_series, num_buckets, bucket_ms = 6, 8, 1000
        n = 5000
        sid = np.sort(rng.integers(0, num_series, n).astype(np.int32))
        ts = np.empty(n, dtype=np.int64)
        for s in range(num_series):  # ts ascending within each series
            m = sid == s
            ts[m] = np.sort(rng.integers(0, num_buckets * bucket_ms, m.sum()))
        vals = rng.normal(size=n)
        got = aggregate.downsample_sorted(
            ts, sid, vals, 0, bucket_ms, num_series, num_buckets
        )
        expect = aggregate.downsample(
            ts, sid, vals, np.ones(n, dtype=bool), 0, bucket_ms, num_series, num_buckets
        )
        for k in ("sum", "count", "min", "max"):
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(expect[k]), rtol=1e-4, atol=1e-4
            )

    def test_f64_order_keys_roundtrip_bit_for_bit(self):
        vals = np.array([-np.inf, -1e300, -2.5, -0.0, 0.0, 1e-300, 3.0,
                         99.99967667212489, 1e300, np.inf])
        kmin, kmax = aggregate.f64_order_keys(vals)
        assert kmin is kmax and kmin.dtype == np.int64
        assert np.all(np.diff(kmin) > 0)  # the f64 total order, strictly
        back = aggregate.f64_from_order_keys(kmin)
        np.testing.assert_array_equal(back.view(np.int64), vals.view(np.int64))

    def test_f64_order_keys_nan_wins_both_lanes_and_fills_read_inf(self):
        vals = np.array([1.0, np.nan, -7.0])
        kmin, kmax = aggregate.f64_order_keys(vals)
        assert kmin is not kmax
        assert np.isnan(aggregate.f64_from_order_keys(np.array([kmin.min()])))[0]
        assert np.isnan(aggregate.f64_from_order_keys(np.array([kmax.max()])))[0]
        i64 = np.iinfo(np.int64)
        fills = aggregate.f64_from_order_keys(np.array([i64.max, i64.min]))
        assert fills[0] == np.inf and fills[1] == -np.inf

    @staticmethod
    def _sorted_rows(with_nan=False, wide=False):
        rng = np.random.default_rng(5)
        n, ns, nb = 4000, 8, 6
        sid = np.sort(rng.integers(0, ns - 1, n)).astype(np.int32)  # last series empty
        ts = np.concatenate([
            np.sort(rng.integers(0, nb * 1000, (sid == s).sum())) for s in range(ns)
        ]).astype(np.int64)
        vals = rng.uniform(-100, 100, n)
        if wide:  # magnitudes no f32 exponent holds
            vals[1::5] *= 1e300
            vals[2::5] *= 1e-300
        if with_nan:
            vals[::97] = np.nan
        return ts, sid, vals, dict(num_series=ns, num_buckets=nb, with_minmax=True)

    @pytest.mark.parametrize("impl", ["scatter", "block"])
    @pytest.mark.parametrize("with_nan", [False, True])
    def test_downsample_sorted_minmax_is_a_stored_sample_bit_for_bit(
        self, monkeypatch, with_nan, impl
    ):
        """The device lane's min/max reduce i64 order keys: the same grids,
        bit for bit, as numpy's float reduction on the host lane, with empty
        cells, NaN cells and 1e300 / 1e-300 samples."""
        ts, sid, vals, kw = self._sorted_rows(with_nan, wide=True)
        monkeypatch.setenv("HORAEDB_AGG_IMPL", "reduceat")
        want = aggregate.downsample_sorted(ts, sid, vals, 0, 1000, **kw)
        monkeypatch.setenv("HORAEDB_AGG_IMPL", impl)
        got = aggregate.downsample_sorted(ts, sid, vals, 0, 1000, **kw)
        for stat in ("min", "max"):  # NaN cells compare equal in place
            np.testing.assert_array_equal(
                np.asarray(got[stat]), np.asarray(want[stat]))
        assert np.abs(np.asarray(got["max"])[np.isfinite(got["max"])]).max() > 1e300

    def test_downsample_sorted_large_grid_takes_the_same_lanes(self, monkeypatch):
        """Past the f32-exact cell-id range the reductions are plain
        scatters over the same value and order-key lanes."""
        from horaedb_tpu.ops import blockagg

        ts, sid, vals, kw = self._sorted_rows(with_nan=True, wide=True)
        valid = np.arange(len(ts)) % 3 != 0
        monkeypatch.setenv("HORAEDB_AGG_IMPL", "reduceat")
        want = aggregate.downsample_sorted(ts, sid, vals, 0, 1000, valid=valid, **kw)
        monkeypatch.setenv("HORAEDB_AGG_IMPL", "scatter")
        monkeypatch.setattr(blockagg, "_F32_EXACT", 16)
        got = aggregate.downsample_sorted(ts, sid, vals, 0, 1000, valid=valid, **kw)
        for stat in ("min", "max", "count"):
            np.testing.assert_array_equal(
                np.asarray(got[stat]), np.asarray(want[stat]))
        np.testing.assert_allclose(
            np.asarray(got["sum"]), np.asarray(want["sum"]), rtol=1e-12)

    @pytest.fixture
    def on_an_accelerator(self, monkeypatch):
        monkeypatch.setattr(aggregate, "device_f64_is_exact", lambda: False)

    @pytest.mark.parametrize("values,holds", [
        ([0.0, 1.5, -99.0, np.nan, np.inf], True),
        ([1e300], False),            # reads back inf from an f32 pair
        ([1e-300], False),           # reads back 0
        ([3e38, 3e38], False),       # each fits, the sum does not
        ([1e-30, 1.0], True),
    ])
    def test_device_sums_hold_is_the_f32_pair_range(
        self, on_an_accelerator, values, holds
    ):
        assert aggregate.device_sums_hold(np.asarray(values)) is holds

    def test_device_sums_hold_anything_on_the_cpu(self):
        assert aggregate.device_sums_hold(np.asarray([1e300, 1e-300]))

    @pytest.mark.parametrize("wide", [False, True])
    def test_downsample_sorted_off_the_cpu_keeps_wide_values_on_the_host(
        self, monkeypatch, on_an_accelerator, wide
    ):
        """An accelerator's f64 cannot sum 1e300: such a block takes the
        host lane and says so; a block in range stays on the device lane
        the dispatcher chose."""
        from horaedb_tpu.ops import agg_registry

        ts, sid, vals, kw = self._sorted_rows(wide=wide)
        monkeypatch.setenv("HORAEDB_AGG_IMPL", "scatter")
        got = aggregate.downsample_sorted(ts, sid, vals, 0, 1000, **kw)
        assert agg_registry.last_choice() == ("reduceat" if wide else "scatter")
        want = agg_registry.host_downsample_sorted(ts, sid, vals, 0, 1000, **kw)
        for stat in ("min", "max", "count"):
            np.testing.assert_array_equal(
                np.asarray(got[stat]), np.asarray(want[stat]))
        np.testing.assert_allclose(
            np.asarray(got["sum"]), np.asarray(want["sum"]), rtol=1e-12)

    def test_downsample_sorted_off_the_cpu_refuses_an_f64_device_array(
        self, on_an_accelerator
    ):
        import jax.numpy as jnp

        from horaedb_tpu.common.error import HoraeError

        ts, sid, vals, kw = self._sorted_rows()
        with pytest.raises(HoraeError, match="already lost bits"):
            aggregate.downsample_sorted(ts, sid, jnp.asarray(vals), 0, 1000, **kw)

    def test_segment_last_value(self):
        vals = np.array([1.0, 2.0, 3.0, 4.0])
        seq = np.array([10, 30, 20, 5], dtype=np.uint64)
        idx = np.array([0, 0, 1, 1], dtype=np.int32)
        valid = np.ones(4, dtype=bool)
        out = np.asarray(
            aggregate.segment_last_value(vals, seq, idx, valid, 2)
        )
        np.testing.assert_allclose(out, [2.0, 3.0])  # max-seq value per group
