"""Multi-column lexicographic sort on device.

Replaces the reference's per-batch DataFusion SortExec (storage.rs:244-256)
and the sorted-merge ordering contract (pk asc, then __seq__ asc,
read.rs:412-427).

Every device sort here is a chain of SINGLE-key stable sorts over u64 lanes
(least-significant key first, the LSD construction): the TPU compiler takes
about half a minute for one single-key sort past 16 K rows, compiles the
identical passes of one program once, and does not finish a variadic sort
over five 64-bit keys in a quarter of an hour (compile rehearsal,
tests/test_tpu_compile.py). Keys are mapped to u64 in an order-preserving
way first, so every pass is the same computation. All passes are stable,
which preserves the seq tie-break invariant when seq is the least
significant key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from horaedb_tpu.common.xprof import xjit

_SIGN64 = np.uint64(1 << 63)
# smallest padded length of a host-called sort: row counts share compiled
# programs by power-of-two class
_MIN_SORT_ROWS = 1024


def pow2_rows(n: int, floor: int = _MIN_SORT_ROWS) -> int:
    """The power-of-two length class a device sort of n rows is padded to
    (one compiled sort per class, not per row count)."""
    return max(floor, 1 << max(0, int(n) - 1).bit_length())


def f64_order_i64(values):
    """f64 -> i64 whose signed order is the f64 total order (-0.0 before
    0.0): negative floats order by descending magnitude, so their magnitude
    bits flip. numpy in, numpy out; traced in, traced out."""
    xp = np if isinstance(values, np.ndarray) else jnp
    bits = values.astype(xp.float64).view(xp.int64)
    return bits ^ ((bits >> 63) & xp.int64((1 << 63) - 1))


def order_u64(key):
    """An order-preserving u64 view of an integer or bool key lane (numpy
    in, numpy out; traced in, traced out): unsigned widen, signed flip the
    sign bit, floats through `f64_order_i64`."""
    xp = np if isinstance(key, np.ndarray) else jnp
    dt = key.dtype
    if dt == xp.uint64:
        return key
    if dt == xp.bool_ or xp.issubdtype(dt, xp.unsignedinteger):
        return key.astype(xp.uint64)
    if xp.issubdtype(dt, xp.floating):
        key = f64_order_i64(key)
    return key.astype(xp.int64).view(xp.uint64) ^ _SIGN64


def lexsort_perm(keys) -> jax.Array:
    """Stable permutation ordering rows by `keys` (most-significant first),
    as single-key passes from the least significant key up. Traceable."""
    n = keys[0].shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for key in reversed(list(keys)):
        lane = jnp.take(order_u64(key), perm)
        perm = jax.lax.sort((lane, perm), num_keys=1, is_stable=True)[1]
    return perm


@xjit(kernel="sort_perm")
def _sort_perm(keys: tuple[jax.Array, ...]) -> jax.Array:
    return lexsort_perm(keys)


def sort_permutation(keys: list) -> np.ndarray:
    """Stable permutation ordering rows by `keys` (most-significant first),
    on the host.

    Rows pad to their power-of-two class with all-ones keys: pads tie with
    nothing smaller, start behind every real row and every pass is stable,
    so they stay at the tail and the first n entries are the answer. They
    are cut off on the host: a slice of the device array is an eager
    `dynamic_slice` that compiles for every new n (a self-telemetry write's
    series count: five compiles a window, PR 29)."""
    n = int(keys[0].shape[0])
    padded = pow2_rows(n)
    lanes = []
    for key in keys:
        lane = order_u64(key if isinstance(key, np.ndarray) else jnp.asarray(key))
        if padded != n:
            xp = np if isinstance(lane, np.ndarray) else jnp
            lane = xp.concatenate(
                [lane, xp.full(padded - n, np.iinfo(np.uint64).max, dtype=xp.uint64)]
            )
        lanes.append(lane)
    return np.asarray(_sort_perm(tuple(lanes)))[:n]


def apply_permutation(columns: dict[str, jax.Array], perm: jax.Array) -> dict[str, jax.Array]:
    return {k: jnp.take(v, perm, axis=0) for k, v in columns.items()}


def sort_columns(
    columns: dict[str, jax.Array],
    key_names: list[str],
) -> dict[str, jax.Array]:
    """Sort every column by the named key columns (most-significant first).

    ONE variadic lax.sort carries every non-key column along as a payload —
    no permutation materialization, no per-column gathers (measured 5.3x
    the lexsort+gather form on a v5e at the 100-way-merge shape).

    Padding rows must already carry max-sentinel keys (blocks.py) so they
    remain at the tail after the sort.
    """
    other = [k for k in columns if k not in key_names]
    ops = [columns[k] for k in key_names] + [columns[k] for k in other]
    out = jax.lax.sort(tuple(ops), num_keys=len(key_names), is_stable=True)
    return dict(zip(list(key_names) + other, out))
