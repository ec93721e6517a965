"""Compile rehearsal: the served path's kernels, for a TPU v5e that is
described and not attached (on-chip-measurement guide, section 2).

Each case lowers and compiles one kernel for one described chip at the
dtypes the server hands it (the data table is u64 ids, i64 timestamps,
f64 values; jax_enable_x64 is on process-wide) and at the sizes of
`chip_smoke.py`'s default fleet: 1,000 hosts x 10 metrics, one sample per
10 s for 2 h, so one metric is 720,000 rows and a 5-minute grid over it is
1,000 x 24 cells. What the TPU compiler would refuse on the chip it
refuses here, at no chip time. Nothing runs: a pass says nothing about
results or speed.

The topology is described inside the module-scoped fixture only: the
process that describes it holds libtpu's lock until it exits, so it must
not happen at import (every xdist worker imports every test file).
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from horaedb_tpu.ops import aggregate as agg_ops
from horaedb_tpu.ops import blockagg, decode
from horaedb_tpu.ops import filter as F
from horaedb_tpu.ops import sort as sort_ops
from horaedb_tpu.storage import read as read_mod

# chip_smoke.py's default fleet
HOSTS, ROUNDS = 1000, 720
ROWS = HOSTS * ROUNDS                     # one metric over the window
BUCKETS = 24                              # 2 h of 5-minute steps
CELLS = HOSTS * BUCKETS
# device sorts pad to power-of-two row classes (ops/sort.py, read.py)
ROWS_PADDED = read_mod._merge_rows(ROWS)
WRITE_ROWS = sort_ops.pow2_rows(HOSTS * 10)               # one remote-write request
COMPACT_ROWS = read_mod._merge_rows(30 * HOSTS * 10)      # input_sst_max_num SSTs
LASTPOINT_ROWS = read_mod._merge_rows(HOSTS * 30)         # 5 m lookback, one metric
CALIB_N, CALIB_CELLS = 1 << 18, 1 << 14   # agg_registry's micro-A/B shape

# the data table's lanes as the scan hands them to the device
DATA_LANES = {
    "metric_id": jnp.uint64, "tsid": jnp.uint64, "field_id": jnp.uint64,
    "ts": jnp.int64, "value": jnp.float64, "__seq__": jnp.uint64,
}
PK = ("metric_id", "tsid", "field_id", "ts")
SORT_KEYS = PK + ("__seq__",)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype):
        if isinstance(shape, int):
            shape = (shape,)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def compile_for_chip(fn, *args, **kwargs):
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kwargs).compile()
    print(f"compiled in {time.perf_counter() - t0:.1f} s")
    assert compiled is not None
    return compiled


def query_template(dtypes):
    """The predicate every data-table query carries (engine/data.py
    `_predicate`), split into a jit template plus typed literal operands."""
    pred = F.And(
        F.Compare("metric_id", "eq", 1 << 63),
        F.Compare("ts", "ge", 0),
        F.Compare("ts", "lt", 7_200_000),
    )
    template, raw = F.split_literals(pred)
    return template, F.literal_arrays(template, raw, dtypes)


def literal_specs(spec, literals):
    return tuple(spec(np.shape(v), np.asarray(v).dtype) for v in literals)


# -- aggregation --------------------------------------------------------------


def test_downsample(spec):
    """ops/aggregate.py `downsample`: the fused scan's reduction."""
    compile_for_chip(
        agg_ops.downsample,
        spec(ROWS_PADDED, jnp.int64), spec(ROWS_PADDED, jnp.int32),
        spec(ROWS_PADDED, jnp.float64), spec(ROWS_PADDED, jnp.bool_),
        spec((), jnp.int64), spec((), jnp.int64),
        num_series=HOSTS, num_buckets=BUCKETS,
    )


def test_stacked_downsample(spec):
    """The query batcher's lane (server/batching.py): the all-hosts panel
    alone pads to B=1 x 2^20 rows, 1,024 series."""
    rows = 1 << 20
    compile_for_chip(
        agg_ops.stacked_downsample,
        spec((1, rows), jnp.int64), spec((1, rows), jnp.int32),
        spec((1, rows), jnp.float64), spec((1, rows), jnp.bool_),
        spec((1,), jnp.int64), spec((), jnp.int64),
        num_series=1024, num_buckets=BUCKETS,
        order_keys=(spec((1, rows), jnp.int64), spec((1, rows), jnp.int64)),
    )


@pytest.mark.parametrize("variant", sorted(blockagg._BLOCK_VARIANTS))
def test_block_sum_count_calibration(spec, variant):
    """ops/blockagg.py `block_sum_count`: every registered variant at the
    shape the dispatcher's cold micro-A/B launches it with."""
    block, ranks, bf16, scan = blockagg._BLOCK_VARIANTS[variant]
    compile_for_chip(
        blockagg._block_sum_count_xla,
        spec(CALIB_N, jnp.int32), spec(CALIB_N, jnp.float32),
        num_cells=CALIB_CELLS, block=block, ranks=ranks,
        bf16_onehot=bf16, scan_prologue=scan,
    )


def test_block_sum_count_served(spec):
    """The f32 lane at the served size, predicate weights riding along."""
    block, ranks, _, _ = blockagg._BLOCK_VARIANTS["block"]
    compile_for_chip(
        blockagg._block_sum_count_xla,
        spec(ROWS, jnp.int32), spec(ROWS, jnp.float32),
        num_cells=CELLS, block=block, ranks=ranks, w=spec(ROWS, jnp.float32),
    )


def test_block_min_max(spec):
    block, ranks, _, _ = blockagg._BLOCK_VARIANTS["block"]
    compile_for_chip(
        blockagg._block_min_max_xla,
        spec(ROWS, jnp.int32), spec(ROWS, jnp.float32),
        num_cells=CELLS, block=block, ranks=ranks, valid=spec(ROWS, jnp.bool_),
    )


@pytest.mark.parametrize("rows,cells", [(CALIB_N, CALIB_CELLS), (ROWS, CELLS)])
def test_scatter_fused(spec, rows, cells):
    compile_for_chip(
        blockagg._scatter_fused_sum_count,
        spec(rows, jnp.int32), spec(rows, jnp.float32), num_cells=cells,
    )


def test_downsample_sorted_f64(spec):
    """What the pushdown runs for the server's f64 values: the bucket
    arithmetic in i64 and the dtype-preserving f64 scatter, traced as one
    program here (the server dispatches it op by op)."""
    def fold(ts, sid, values, t0, bucket_ms):
        return agg_ops.downsample_sorted(
            ts, sid, values, t0, bucket_ms,
            num_series=HOSTS, num_buckets=BUCKETS, with_minmax=True,
        )

    compile_for_chip(
        jax.jit(fold),
        spec(ROWS, jnp.int64), spec(ROWS, jnp.int32), spec(ROWS, jnp.float64),
        spec((), jnp.int64), spec((), jnp.int64),
    )


@pytest.mark.parametrize("rows,series,buckets", [
    (512, 1, 64),          # TSBS single-groupby-1-1-1: one host's hour, any cut of it
    # TSBS double-groupby-1 (the cell tsbs100.double-groupby-1): 100 hosts x 12 h cut in two by a
    # segment's edge, the larger part 216,000 to 432,000 rows; the smaller part rides its class
    (1 << 18, 128, 16),
    (1 << 19, 128, 16),
    (1 << 20, 1024, 32),   # chip_smoke's all-hosts 5-minute panel
])
@pytest.mark.parametrize("impl", ["runs", "scatter"])
def test_downsample_fold(spec, rows, series, buckets, impl):
    """ops/aggregate.py `downsample_fold`: the pushdown's fold as ONE program a
    class, at the server's dtypes (f64 values, their i64 order keys);
    `runs` is what an accelerator's 64-bit lanes take, `scatter` its branch
    for a stream that is not monotone."""
    rows_class, series_class, buckets_class = agg_ops.fold_classes(rows, series, buckets)
    assert (rows_class, series_class, buckets_class) == (rows, series, buckets)
    compile_for_chip(
        agg_ops.downsample_fold,
        spec(rows, jnp.int64), spec(rows, jnp.int32), spec(rows, jnp.float64),
        spec(rows, jnp.bool_), spec(rows, jnp.int64),
        spec((), jnp.int64), spec((), jnp.int64),
        num_series=series, num_buckets=buckets, with_minmax=True, impl=impl,
    )


def test_min_max_over_order_keys(spec):
    """The pushdown's selections: min/max of the i64 order keys the host
    builds from the f64 values (ops/aggregate.py f64_order_keys)."""
    def select(cells, keys, ok):
        return blockagg.sorted_segment_min_max(
            cells, keys, HOSTS * BUCKETS, impl="scatter", valid=ok)

    compile_for_chip(
        jax.jit(select),
        spec(ROWS, jnp.int32), spec(ROWS, jnp.int64), spec(ROWS, jnp.bool_),
    )


# -- sort / merge -------------------------------------------------------------


def test_sort_perm(spec):
    """ops/sort.py `sort_perm`: the write path's pk sort of one request,
    single-key u64 passes over the four pk lanes."""
    keys = tuple(spec(WRITE_ROWS, jnp.uint64) for _ in PK)
    compile_for_chip(sort_ops._sort_perm, keys)


@pytest.mark.parametrize("rows", [LASTPOINT_ROWS, COMPACT_ROWS, ROWS_PADDED])
def test_packed_merge(spec, rows):
    """storage/read.py `packed_merge`: the single-lane u64 merge of a raw
    scan, a compaction of 30 requests, and one metric's whole window."""
    compile_for_chip(
        read_mod._packed_merge_kernel(True),
        spec(rows, jnp.uint64), spec((), jnp.int64), spec((), jnp.uint64),
    )


def test_index_merge_filter(spec):
    """storage/read.py `index_merge_filter`: the raw scan's device route
    when the predicate ships as a template (key lanes only)."""
    names = tuple(sorted(SORT_KEYS))
    dtypes = {k: np.dtype(DATA_LANES[k]) for k in names}
    template, literals = query_template(dtypes)
    kernel = read_mod._build_index_kernel(
        names, SORT_KEYS, PK, template, False, True, False)
    cols = {k: spec(LASTPOINT_ROWS, DATA_LANES[k]) for k in names}
    compile_for_chip(kernel, cols, literal_specs(spec, literals),
                     spec((), jnp.int64))


@pytest.mark.parametrize("presorted", [False, True])
def test_scan_kernel(spec, presorted):
    """storage/read.py `scan_kernel`: filter -> sort -> dedup over every
    numeric lane of the data table plus the dense series id; the f64 value
    lane crosses as its i64 bits (`_fused_pass`)."""
    lanes = dict(DATA_LANES, value=jnp.int64, __sid__=jnp.int32)
    names = tuple(lanes)
    dtypes = {k: np.dtype(v) for k, v in lanes.items()}
    template, literals = query_template(dtypes)
    kernel = read_mod._build_scan_kernel(
        names, SORT_KEYS, PK, template, True, presorted)
    cols = {k: spec(ROWS_PADDED, v) for k, v in lanes.items()}
    compile_for_chip(kernel, cols, literal_specs(spec, literals),
                     spec((), jnp.int64))


# -- decode (encoded sidecars, 4,096-row pages) --------------------------------


def _page():
    n_pad = decode._pad_rows(4096)
    return n_pad, decode._words_for(n_pad, 16)


def test_decode_unpack(spec):
    n_pad, words = _page()
    compile_for_chip(decode._unpack_kernel(16, n_pad), spec(words, jnp.uint32))


def test_decode_dod(spec):
    n_pad, words = _page()
    compile_for_chip(decode._dod_kernel(16, n_pad), spec(words, jnp.uint32),
                     spec((), jnp.uint64), spec((), jnp.uint64))


def test_decode_xor(spec):
    n_pad, words = _page()
    compile_for_chip(decode._xor_kernel(16, n_pad), spec(words, jnp.uint32),
                     spec((), jnp.uint64))


def test_decode_rle(spec):
    n_pad, _ = _page()
    compile_for_chip(decode._rle_kernel(n_pad, 256),
                     spec(256, jnp.uint64), spec(256, jnp.int64))


# -- four chips ---------------------------------------------------------------


def test_sharded_downsample_four_chips(topo):
    """parallel/scan.py `sharded_downsample` on a mesh of the four described
    devices: the only guard the multi-chip code has until a deployment
    needs four chips (no served process installs a mesh today)."""
    from horaedb_tpu.parallel.scan import build_sharded_downsample

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("rows", "series"))
    rows = -(-ROWS // (4 * 8192)) * 4 * 8192

    def on(shape, dtype, pspec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))

    fn = build_sharded_downsample(mesh, HOSTS, BUCKETS, None, True, True)
    compiled = compile_for_chip(
        fn,
        on((rows,), jnp.int64, P("rows")), on((rows,), jnp.int32, P("rows")),
        # the mesh lane narrows values to f32 on accelerators (parallel/mesh.py)
        on((rows,), jnp.float32, P("rows")), on((rows,), jnp.bool_, P("rows")),
        (), on((), jnp.int64, P()), on((), jnp.int64, P()),
    )
    assert "all-reduce" in compiled.as_text()
