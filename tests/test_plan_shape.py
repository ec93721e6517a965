"""HLO plan-shape golden tests — the XLA analog of the reference's
DataFusion plan-display regression net (read.rs:575-617 asserts the indent
string of ParquetExec->FilterExec->SPM->MergeExec; SURVEY §4 calls this 'a
cheap, high-value regression net worth replicating for XLA/HLO plans').

Exact HLO text is compiler-version brittle; these assert the structural
invariants instead: which ops the lowered module must (and must not)
contain.
"""

import numpy as np

from horaedb_tpu.ops import filter as filter_ops
from horaedb_tpu.storage.read import _build_scan_kernel


def lower_scan_kernel(template=None, do_dedup=True, n=1024):
    import jax.numpy as jnp

    cols = {
        "pk": jnp.zeros(n, jnp.int64),
        "__seq__": jnp.zeros(n, jnp.uint64),
        "value": jnp.zeros(n, jnp.float64),
    }
    kernel = _build_scan_kernel(
        ("pk", "__seq__", "value"), ("pk", "__seq__"), ("pk",), template, do_dedup
    )
    lits = ()
    if template is not None:
        _, raw = filter_ops.split_literals(filter_ops.Compare("value", "gt", 0.0))
        lits = filter_ops.literal_arrays(
            template, raw, {k: np.dtype(v.dtype) for k, v in cols.items()}
        )
    return kernel.lower(cols, lits, 10).as_text()


class TestScanKernelPlanShape:
    def test_one_single_key_sort_per_key_lane_and_no_scatter(self):
        """The scan is a sort-based merge: one single-key stable pass per
        key lane (mask, pk, seq: the construction the TPU compiler can
        afford, ops/sort.py), every pass the same u64 computation, and NO
        scatter ops (scatters are the serial op the design avoids on the
        scan path)."""
        hlo = lower_scan_kernel()
        assert hlo.count("stablehlo.sort") == 3, hlo.count("stablehlo.sort")
        # each pass sorts (u64 key, i32 permutation) and nothing wider
        assert hlo.count("(tensor<1024xui64>, tensor<1024xi32>)") >= 3
        assert "stablehlo.scatter" not in hlo
        # dedup mask algebra compiles to compares/selects, not loops
        assert "while" not in hlo

    def test_predicate_fuses_into_the_same_module(self):
        template, _ = filter_ops.split_literals(filter_ops.Compare("value", "gt", 0.0))
        hlo = lower_scan_kernel(template=template)
        assert hlo.count("stablehlo.sort") == 3
        assert "stablehlo.compare" in hlo
        assert "stablehlo.scatter" not in hlo

    def test_append_mode_skips_dedup_ops(self):
        hlo_dedup = lower_scan_kernel(do_dedup=True)
        hlo_plain = lower_scan_kernel(do_dedup=False)
        # append mode (no dedup) lowers to strictly less work
        assert len(hlo_plain) < len(hlo_dedup)


class TestAggregatePlanShape:
    def test_downsample_uses_exactly_two_scatters_without_minmax(self):
        """The mean-downsample kernel pays exactly 2 scatter-adds (sum,
        count); min/max add two more — the scatter budget IS the perf model
        (scatters ~9ns/row on v5e, everything else is bandwidth)."""
        import jax
        from jax.sharding import Mesh

        from horaedb_tpu.parallel.scan import build_sharded_downsample

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("rows", "series"))
        n = 4096
        args = (
            np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.zeros(n, np.float32), np.ones(n, bool),
            (), np.int32(0), np.int32(1000),
        )
        lean = build_sharded_downsample(mesh, 8, 4, None, False).lower(*args).as_text()
        full = build_sharded_downsample(mesh, 8, 4, None, True).lower(*args).as_text()
        # count the op uses ('"stablehlo.scatter"('): the attribute
        # #stablehlo.scatter<...> would double-count each op
        assert lean.count('"stablehlo.scatter"') == 2, lean.count('"stablehlo.scatter"')
        assert full.count('"stablehlo.scatter"') == 4, full.count('"stablehlo.scatter"')


class TestRegistryKernelPlanShape:
    """Lowering-time pins for the registry kernels (ops/agg_registry.py):
    scatter/sort op counts and partials shapes are the perf model — a
    regression is caught here without hardware."""

    def lower_sorted(self, impl, n=131072, cells=8):
        import jax
        import jax.numpy as jnp

        from horaedb_tpu.ops.blockagg import sorted_segment_sum_count

        f = jax.jit(
            lambda k, v: sorted_segment_sum_count(k, v, cells, impl=impl)
        )
        return f.lower(
            jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.float32)
        ).as_text()

    def test_scatter_fused_pays_exactly_one_scatter(self):
        """The fused lane's whole point: sum+count ride ONE stacked
        scatter (the plain sorted scatter pays 2)."""
        hlo = self.lower_sorted("scatter_fused")
        assert hlo.count('"stablehlo.scatter"') == 1, hlo.count(
            '"stablehlo.scatter"'
        )
        plain = self.lower_sorted("scatter")
        assert plain.count('"stablehlo.scatter"') == 2

    def test_block_r32_partials_shape(self):
        """ranks=32 halves the one-hot AND the partials: 256 blocks x 32
        ranks = 8192 partial rows for n=131072 (16x compaction), vs 16384
        at the default ranks=64. Scatter budget unchanged: 2 fast-branch +
        2 fallback-branch."""
        hlo = self.lower_sorted("block_r32")
        assert hlo.count('"stablehlo.scatter"') == 4
        assert "tensor<8192x" in hlo or "tensor<8192>" in hlo, \
            "ranks=32 partials shape missing"
        assert "stablehlo.dot_general" in hlo

    def test_block_bf16_contracts_in_bf16(self):
        """The bf16 lane's dot_general must take bf16 operands (that IS
        the traffic saving) with an f32 accumulator, and ids must NOT ride
        the einsum — no f32 3-feature contraction left."""
        hlo = self.lower_sorted("block_bf16")
        assert "stablehlo.dot_general" in hlo
        assert "bf16" in hlo, "one-hot did not materialize in bf16"
        assert hlo.count('"stablehlo.scatter"') == 4
        # 2-feature contraction (value, weight): the f32 path's 3-feature
        # shape must be absent
        assert "x3xf32" not in hlo, "id column leaked into the bf16 einsum"

    def test_block_scan_keeps_budget(self):
        """The associative_scan prologue changes the rank computation, not
        the scatter budget or the MXU contraction."""
        hlo = self.lower_sorted("block_scan")
        assert hlo.count('"stablehlo.scatter"') == 4
        assert "stablehlo.dot_general" in hlo

    def test_reduceat_refuses_to_trace(self):
        """The host lane must fail LOUDLY at lowering time under jit, not
        silently concretize (the J006 contract)."""
        import jax
        import jax.numpy as jnp
        import pytest

        from horaedb_tpu.common.error import HoraeError
        from horaedb_tpu.ops.blockagg import sorted_segment_sum_count

        f = jax.jit(
            lambda k, v: sorted_segment_sum_count(k, v, 8, impl="reduceat")
        )
        with pytest.raises(HoraeError):
            f.lower(jnp.zeros(64, jnp.int32), jnp.zeros(64, jnp.float32))


class TestSortedBlockPlanShape:
    def test_block_compaction_scatters_over_partials_not_rows(self):
        """The block-rank compaction's perf property, pinned in the HLO:
        its scatter operands are the (blocks x ranks) PARTIALS — 8x fewer
        rows than the raw input at the default block/ranks — while the
        plain sorted path scatters all n rows. Both still pay exactly 2
        scatters (sum, count)."""
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from horaedb_tpu.parallel.scan import build_sharded_downsample

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("rows", "series"))
        n = 64 * 2048  # 64 blocks of the default 2048
        args = (
            np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.zeros(n, np.float32), np.ones(n, bool),
            (), np.int32(0), np.int32(1000),
        )
        block = build_sharded_downsample(
            mesh, 8, 4, None, False, sorted_input=True, sorted_impl="block"
        ).lower(*args).as_text()
        plain = build_sharded_downsample(
            mesh, 8, 4, None, False, sorted_input=True, sorted_impl="scatter"
        ).lower(*args).as_text()
        assert plain.count('"stablehlo.scatter"') == 2
        # block path: 2 partial scatters inside the fast branch + 2 in the
        # lax.cond fallback branch (compiled, not executed when dense)
        assert block.count('"stablehlo.scatter"') == 4, block.count(
            '"stablehlo.scatter"'
        )
        # the fast branch's scatter operands are the compacted partials:
        # 64 blocks x 256 ranks = 16384 rows, 8x fewer than n=131072 — the
        # shape must appear as a scatter update operand, and the MXU
        # contraction (dot_general over the one-hot) must be present
        assert "tensor<16384x" in block or "tensor<16384>" in block, "partials shape missing"
        assert "stablehlo.dot_general" in block
        assert "stablehlo.dot_general" not in plain
