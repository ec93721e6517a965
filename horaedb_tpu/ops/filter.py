"""Vectorized predicate evaluation.

Replaces DataFusion's FilterExec + pushed-down pruning predicate
(read.rs:459-470). A predicate is a small static expression tree; evaluation
compiles to a fused elementwise mask kernel. Literals are passed as traced
scalars so changing a constant does NOT trigger an XLA recompile — only the
tree *shape* is static.

The same tree drives host-side SST/row-group pruning via min-max statistics
(`prune_range`; `prune_lanes` over all of a footer's row groups at once),
mirroring parquet page pruning in the reference.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Union

import jax.numpy as jnp
import numpy as np

from horaedb_tpu.common.error import HoraeError

# -- predicate tree ----------------------------------------------------------

_OPS = ("eq", "ne", "lt", "le", "gt", "ge")


@dataclass(frozen=True)
class Compare:
    column: str
    op: str  # one of _OPS
    literal: float | int

    def __post_init__(self):
        if self.op not in _OPS:
            raise HoraeError(f"unknown compare op: {self.op}")


@dataclass(frozen=True)
class InSet:
    """column IN (v1, v2, ...) — e.g. TSID membership from the inverted index.
    On device this becomes a broadcast compare against a literal vector
    (the 'device-side set-membership' op of SURVEY §7.7)."""

    column: str
    values: tuple

    def __post_init__(self):
        object.__setattr__(self, "values", tuple(self.values))


@dataclass(frozen=True)
class And:
    children: tuple

    def __init__(self, *children: "Predicate"):
        object.__setattr__(self, "children", tuple(children))


@dataclass(frozen=True)
class Or:
    children: tuple

    def __init__(self, *children: "Predicate"):
        object.__setattr__(self, "children", tuple(children))


@dataclass(frozen=True)
class Not:
    child: "Predicate"


Predicate = Union[Compare, InSet, And, Or, Not]


@dataclass(frozen=True)
class InSetProbe:
    """Template form of InSet after `split_literals`: the membership values
    travel as a dynamic padded array operand (values_slot) plus an active
    mask (mask_slot), so a new TSID set of the same size bucket reuses the
    compiled kernel instead of triggering an XLA recompile per query."""

    column: str
    values_slot: int
    mask_slot: int
    padded_size: int


@dataclass(frozen=True)
class Slot:
    """Placeholder for a literal extracted by `split_literals`. A predicate
    whose Compare literals are Slots is a hashable *template*: jit-compiled
    kernels key their cache on the template, and the literal values flow in
    as traced scalars — new constants, same executable. Carries the column
    name so the cast site needs no second type-dispatched tree walk."""

    idx: int
    column: str = ""


def iter_nodes(pred: Predicate):
    """Generic pre-order walk — the single structural traversal shared by
    every predicate pass (split/cast/eval helpers)."""
    yield pred
    if isinstance(pred, (And, Or)):
        for c in pred.children:
            yield from iter_nodes(c)
    elif isinstance(pred, Not):
        yield from iter_nodes(pred.child)


def pred_columns(pred: Predicate | None) -> set[str]:
    """Column names a predicate references (scan planners use this to decide
    which columns must reach the evaluation site)."""
    if pred is None:
        return set()
    out: set[str] = set()
    for node in iter_nodes(pred):
        c = getattr(node, "column", None)
        if c:
            out.add(c)
    return out


def _pad_bucket(n: int) -> int:
    """Next power of two (min 1): membership arrays pad to size buckets so
    compiled-kernel reuse is per bucket, not per exact set size."""
    return 1 << max(0, n - 1).bit_length() if n > 0 else 1


def is_template(pred: Predicate | None) -> bool:
    """True if `pred` already went through split_literals (contains Slot or
    InSetProbe markers)."""
    if pred is None:
        return False
    for node in iter_nodes(pred):
        if isinstance(node, InSetProbe):
            return True
        if isinstance(node, Compare) and isinstance(node.literal, Slot):
            return True
    return False


def split_literals(pred: Predicate | None) -> tuple[Predicate | None, tuple]:
    """Extract literals into a tuple, leaving dynamic markers behind:
    Compare literals become Slots; InSet value tuples become InSetProbe
    (padded values array + active mask, two slots).

    Idempotent: an already-split template passes through unchanged (with no
    literals — the original split's literals remain authoritative); without
    this, re-splitting would renumber Compare slots into collision with
    InSetProbe value/mask slots."""
    if is_template(pred):
        return pred, ()
    literals: list = []

    def walk(p: Predicate) -> Predicate:
        if isinstance(p, Compare):
            literals.append(p.literal)
            return Compare(p.column, p.op, Slot(len(literals) - 1, p.column))
        if isinstance(p, InSet):
            literals.append(tuple(p.values))
            literals.append(None)  # mask slot, filled by literal_arrays
            return InSetProbe(
                p.column,
                len(literals) - 2,
                len(literals) - 1,
                _pad_bucket(len(p.values)),
            )
        if isinstance(p, And):
            return And(*[walk(c) for c in p.children])
        if isinstance(p, Or):
            return Or(*[walk(c) for c in p.children])
        if isinstance(p, Not):
            return Not(walk(p.child))
        return p

    if pred is None:
        return None, ()
    return walk(pred), tuple(literals)


def _representable_values(vals, dt: np.dtype) -> list:
    """Membership-set values representable in a column dtype. For integer
    columns, equality can never hold for out-of-range or fractional values,
    so they drop from the set — the SINGLE definition shared by the device
    (_eval), numpy (eval_predicate_np), and template (literal_arrays)
    evaluators, keeping set semantics identical across all three."""
    vals_list = list(vals)
    if np.issubdtype(dt, np.integer):
        info = np.iinfo(dt)
        vals_list = [
            int(v) for v in vals_list
            if (not isinstance(v, float) or v.is_integer())
            and info.min <= v <= info.max
        ]
    return vals_list


def _checked_cast(v, dt: np.dtype, column: str):
    """Cast a literal to a column dtype, rejecting values the dtype cannot
    represent (silent wrapping or float truncation would silently change
    lt/ge/eq semantics — and host-side pruning, which compares exactly,
    would then disagree with device evaluation)."""
    if np.issubdtype(dt, np.integer):
        if isinstance(v, float):
            if not v.is_integer():
                raise HoraeError(
                    f"fractional literal {v} on integer column {column!r}; "
                    "rewrite the predicate with an integer bound"
                )
            v = int(v)
        info = np.iinfo(dt)
        if not (info.min <= v <= info.max):
            raise HoraeError(
                f"literal {v} out of range for column {column!r} ({dt})"
            )
    return np.asarray(v, dtype=dt)


def literal_arrays(
    template: Predicate | None, literals: tuple, dtypes: dict
) -> tuple:
    """Cast extracted literals to their columns' dtypes (a u64 id >= 2**63
    overflows the default int64 conversion at the jit boundary)."""
    if template is None:
        return ()
    slot_col: dict[int, str] = {}
    inset_nodes: dict[int, InSetProbe] = {}
    for node in iter_nodes(template):
        if isinstance(node, Compare) and isinstance(node.literal, Slot):
            slot_col[node.literal.idx] = node.literal.column or node.column
        elif isinstance(node, InSetProbe):
            inset_nodes[node.values_slot] = node
    out: list = [None] * len(literals)
    for i, v in enumerate(literals):
        if i in inset_nodes:
            node = inset_nodes[i]
            dt = np.dtype(dtypes.get(node.column, np.int64))
            vals_list = _representable_values(v, dt)
            k = len(vals_list)
            pad_val = vals_list[0] if k else 0
            padded = vals_list + [pad_val] * (node.padded_size - k)
            out[node.values_slot] = np.asarray(padded, dtype=dt)
            mask = np.zeros(node.padded_size, dtype=bool)
            mask[:k] = True
            out[node.mask_slot] = mask
        elif out[i] is None and i in slot_col:
            col = slot_col[i]
            dt = dtypes.get(col)
            out[i] = (
                _checked_cast(v, np.dtype(dt), col) if dt is not None else np.asarray(v)
            )
        elif out[i] is None:
            out[i] = np.asarray(v) if v is not None else np.zeros(0, dtype=bool)
    return tuple(out)


def time_range_pred(ts_column: str, start: int, end: int) -> Predicate:
    """[start, end) range scan predicate."""
    return And(Compare(ts_column, "ge", start), Compare(ts_column, "lt", end))


# -- device evaluation -------------------------------------------------------

def eval_predicate(
    pred: Predicate | None,
    columns: dict[str, jnp.ndarray],
    literals: tuple = (),
) -> jnp.ndarray:
    """Boolean keep-mask over a block. Traceable under jit; `literals` feeds
    Slot placeholders produced by `split_literals`."""
    n = next(iter(columns.values())).shape[0]
    if pred is None:
        return jnp.ones(n, dtype=bool)
    return _eval(pred, columns, literals)


def _eval(pred: Predicate, cols: dict[str, jnp.ndarray], literals: tuple = ()) -> jnp.ndarray:
    if isinstance(pred, Compare):
        c = cols[pred.column]
        if isinstance(pred.literal, Slot):
            lit = jnp.asarray(literals[pred.literal.idx], dtype=c.dtype)
        else:
            lit = jnp.asarray(_checked_cast(pred.literal, np.dtype(c.dtype), pred.column))
        if pred.op == "eq":
            return c == lit
        if pred.op == "ne":
            return c != lit
        if pred.op == "lt":
            return c < lit
        if pred.op == "le":
            return c <= lit
        if pred.op == "gt":
            return c > lit
        return c >= lit
    if isinstance(pred, InSetProbe):
        c = cols[pred.column]
        vals = jnp.asarray(literals[pred.values_slot]).astype(c.dtype)
        active = jnp.asarray(literals[pred.mask_slot])
        if pred.padded_size <= 128:
            # small sets: one broadcast compare, O(n*s) but fully vectorized
            hit = (c[:, None] == vals[None, :]) & active[None, :]
            return jnp.any(hit, axis=1)
        # large sets (engine TSID filters go up to 64K): O(n log s) binary
        # search over the sorted membership array. Padding duplicates a real
        # value so sortedness and equality stay exact; an all-padding (empty)
        # set is rejected by the active.any() guard.
        vals_sorted = jnp.sort(vals)
        pos = jnp.clip(
            jnp.searchsorted(vals_sorted, c), 0, pred.padded_size - 1
        )
        hit = vals_sorted[pos] == c
        return hit & jnp.any(active)
    if isinstance(pred, InSet):
        c = cols[pred.column]
        dt = np.dtype(c.dtype)
        vals_list = _representable_values(pred.values, dt)
        if not vals_list:
            return jnp.zeros(c.shape[0], dtype=bool)
        # Build with the column dtype directly: np.asarray on a mixed-magnitude
        # u64 tuple silently promotes to float64 and corrupts ids > 2**53.
        vals = jnp.asarray(np.asarray(vals_list, dtype=dt))
        return jnp.any(c[:, None] == vals[None, :], axis=1)
    if isinstance(pred, And):
        out = _eval(pred.children[0], cols, literals)
        for ch in pred.children[1:]:
            out = out & _eval(ch, cols, literals)
        return out
    if isinstance(pred, Or):
        out = _eval(pred.children[0], cols, literals)
        for ch in pred.children[1:]:
            out = out | _eval(ch, cols, literals)
        return out
    if isinstance(pred, Not):
        return ~_eval(pred.child, cols, literals)
    raise HoraeError(f"unknown predicate node: {pred!r}")


# -- host-side evaluation (binary-capable) -----------------------------------

def eval_predicate_host(pred: Predicate | None, table) -> np.ndarray:
    """Vectorized predicate evaluation over a pyarrow Table on host —
    supports binary/string columns (bytes literals, ordering via arrow
    compute), used by the binary-primary-key scan path. Returns a boolean
    numpy mask."""
    import pyarrow as pa
    import pyarrow.compute as pc

    n = table.num_rows
    if pred is None:
        return np.ones(n, dtype=bool)

    def ev(p: Predicate) -> np.ndarray:
        if isinstance(p, Compare):
            col = table.column(p.column).combine_chunks()
            lit = p.literal
            try:
                fn = {"eq": pc.equal, "ne": pc.not_equal, "lt": pc.less,
                      "le": pc.less_equal, "gt": pc.greater, "ge": pc.greater_equal}[p.op]
                # pin the scalar to the column type: untyped inference maps
                # a large u64 id (>= 2^63) to int64 and overflows
                out = fn(col, pa.scalar(lit, type=col.type)
                         if not isinstance(lit, (bytes, str)) else pa.scalar(lit))
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError,
                    pa.ArrowTypeError, OverflowError) as e:
                raise HoraeError(
                    f"predicate literal {lit!r} incompatible with column "
                    f"{p.column!r} ({col.type})"
                ) from e
            return pc.fill_null(out, False).to_numpy(zero_copy_only=False)
        if isinstance(p, InSet):
            col = table.column(p.column).combine_chunks()
            try:
                out = pc.is_in(col, value_set=pa.array(list(p.values), type=col.type))
            except (pa.ArrowInvalid, pa.ArrowTypeError, OverflowError) as e:
                raise HoraeError(
                    f"InSet values incompatible with column {p.column!r} ({col.type})"
                ) from e
            return pc.fill_null(out, False).to_numpy(zero_copy_only=False)
        if isinstance(p, And):
            out = ev(p.children[0])
            for c in p.children[1:]:
                out = out & ev(c)
            return out
        if isinstance(p, Or):
            out = ev(p.children[0])
            for c in p.children[1:]:
                out = out | ev(c)
            return out
        if isinstance(p, Not):
            return ~ev(p.child)
        raise HoraeError(f"unsupported predicate node on host path: {p!r}")

    return ev(pred)


def _isin_run_compressed(c: np.ndarray, probe: np.ndarray) -> np.ndarray:
    """np.isin that exploits sorted-scan locality: engine lanes arrive in
    (pk...) order, so tag/series columns are piecewise-constant. Detect the
    runs (one vector diff) and, when the column compresses well, probe only
    the run representatives and expand with repeat — the set probe is the
    scan's costliest host-filter leaf (~7 ns/row via np.isin), and on a
    10K-rows-per-series shape this turns it into ~2 ops/row. Columns that
    don't compress (n_runs > n/8) keep the plain probe."""
    n = len(c)
    if n < 4096:
        return np.isin(c, probe)
    neq = c[1:] != c[:-1]
    n_runs = int(np.count_nonzero(neq)) + 1
    if n_runs > n // 8:
        return np.isin(c, probe)
    starts = np.empty(n_runs, dtype=np.int64)
    starts[0] = 0
    starts[1:] = np.flatnonzero(neq) + 1
    reps = c[starts]
    hit = np.isin(reps, probe)
    lengths = np.empty(n_runs, dtype=np.int64)
    lengths[:-1] = starts[1:] - starts[:-1]
    lengths[-1] = n - starts[-1]
    return np.repeat(hit, lengths)


def eval_predicate_np(pred: Predicate | None, cols: dict[str, np.ndarray]) -> np.ndarray:
    """Vectorized predicate evaluation over numpy host lanes (numeric
    columns only; binary/string predicates go through eval_predicate_host).
    Raw predicates only — Slot/InSetProbe templates are device-side forms."""
    n = len(next(iter(cols.values())))
    if pred is None:
        return np.ones(n, dtype=bool)

    def ev(p: Predicate) -> np.ndarray:
        if isinstance(p, Compare):
            c = cols[p.column]
            if isinstance(p.literal, Slot):
                raise HoraeError("Slot template unsupported on the numpy path")
            lit = _checked_cast(p.literal, c.dtype, p.column)
            if p.op == "eq":
                return c == lit
            if p.op == "ne":
                return c != lit
            if p.op == "lt":
                return c < lit
            if p.op == "le":
                return c <= lit
            if p.op == "gt":
                return c > lit
            return c >= lit
        if isinstance(p, InSet):
            c = cols[p.column]
            vals_list = _representable_values(p.values, c.dtype)
            if not vals_list:
                return np.zeros(len(c), dtype=bool)
            probe = np.asarray(vals_list, dtype=c.dtype)
            return _isin_run_compressed(c, probe)
        if isinstance(p, And):
            out = ev(p.children[0])
            for ch in p.children[1:]:
                out = out & ev(ch)
            return out
        if isinstance(p, Or):
            out = ev(p.children[0])
            for ch in p.children[1:]:
                out = out | ev(ch)
            return out
        if isinstance(p, Not):
            return ~ev(p.child)
        raise HoraeError(f"unsupported predicate node on numpy path: {p!r}")

    return ev(pred)


# -- host-side min/max pruning ----------------------------------------------

def prune_range(pred: Predicate | None, stats: dict[str, tuple]) -> bool:
    """Can any row in a chunk with column [min, max] `stats` match?

    Conservative: returns True (keep) unless the predicate provably rejects
    the whole chunk. Used for SST- and row-group-level pruning, the analog of
    the reference's pruning predicate on ParquetExec (read.rs:459-463).
    """
    if pred is None:
        return True
    return _prune(pred, stats)


def _prune(pred: Predicate, stats: dict[str, tuple]) -> bool:
    if isinstance(pred, Compare):
        if pred.column not in stats:
            return True
        lo, hi = stats[pred.column]
        v = pred.literal
        try:
            if pred.op == "eq":
                return lo <= v <= hi
            if pred.op == "ne":
                return not (lo == hi == v)
            if pred.op == "lt":
                return lo < v
            if pred.op == "le":
                return lo <= v
            if pred.op == "gt":
                return hi > v
            return hi >= v
        except TypeError:
            return True  # mismatched stat/literal types (e.g. bytes stats): keep
    if isinstance(pred, InSet):
        if pred.column not in stats:
            return True
        lo, hi = stats[pred.column]
        try:
            return any(lo <= v <= hi for v in pred.values)
        except TypeError:
            return True
    if isinstance(pred, InSetProbe):
        return True  # membership values are dynamic; stay conservative
    if isinstance(pred, And):
        return all(_prune(c, stats) for c in pred.children)
    if isinstance(pred, Or):
        return any(_prune(c, stats) for c in pred.children)
    if isinstance(pred, Not):
        return True  # can't cheaply invert interval logic; stay conservative
    raise HoraeError(f"unknown predicate node: {pred!r}")


# -- the same over many chunks at once ----------------------------------------

def prune_lanes(pred: Predicate, lanes: dict, n: int, scalar) -> np.ndarray:
    """`prune_range` over `n` chunks in array operations: keep[i] is what
    `prune_range(pred, <chunk i's stats>)` returns, for every i.

    `lanes[column]` is `(lo, hi, usable)`, three arrays of `n`: a chunk's
    min and max in the column's own domain (uint64, int64 or float64, so
    that a u64 id above 2**63 and an int64 timestamp both compare exactly)
    and whether the chunk has them (one without is kept, as a column
    missing from `stats` is). A column not in `lanes` has no statistics in
    any chunk; `lanes[column] is None` says it has some that are not
    numbers. A leaf on such a column, or whose literal the lane's dtype
    cannot hold exactly (a negative number against uint64, a fraction
    against integers, a string), is left to `scalar(node)`: the caller's
    `prune_range` of that node a chunk, as an array."""
    if isinstance(pred, (Compare, InSet)):
        if pred.column not in lanes:
            return np.ones(n, dtype=bool)
        lane = lanes[pred.column]
        if lane is None:
            return scalar(pred)
        lo, hi, usable = lane
        if isinstance(pred, Compare):
            v = _lane_literal(pred.literal, lo.dtype)
            if v is None:
                return scalar(pred)
            if pred.op == "eq":
                hit = (lo <= v) & (v <= hi)
            elif pred.op == "ne":
                hit = ~((lo == hi) & (hi == v))
            elif pred.op == "lt":
                hit = lo < v
            elif pred.op == "le":
                hit = lo <= v
            elif pred.op == "gt":
                hit = hi > v
            else:
                hit = hi >= v
            return hit | ~usable
        vals = [_lane_literal(v, lo.dtype) for v in pred.values]
        if any(v is None for v in vals):
            return scalar(pred)
        vals = np.sort(np.array(vals, dtype=lo.dtype))
        if lo.dtype.kind == "f":
            vals = vals[~np.isnan(vals)]  # equal to nothing, and sorted last
        # some value in [lo, hi]: more values <= hi than values < lo (a NaN
        # bound holds none, and `lo <= hi` is false of it)
        hit = np.searchsorted(vals, lo, "left") < np.searchsorted(vals, hi, "right")
        return (hit & (lo <= hi)) | ~usable
    if isinstance(pred, (InSetProbe, Not)):
        return np.ones(n, dtype=bool)  # as _prune: stay conservative
    if isinstance(pred, And):
        out = np.ones(n, dtype=bool)
        for c in pred.children:
            out &= prune_lanes(c, lanes, n, scalar)
        return out
    if isinstance(pred, Or):
        out = np.zeros(n, dtype=bool)
        for c in pred.children:
            out |= prune_lanes(c, lanes, n, scalar)
        return out
    raise HoraeError(f"unknown predicate node: {pred!r}")


def _lane_literal(v, dt: np.dtype):
    """`v` as a scalar of a lane's dtype, where comparing it there gives
    what Python gives for `v` against the lane's values as Python numbers
    (exact, whatever the magnitudes); else None. Plain ints, floats and
    numpy integers only: a bool, a numpy float or anything else compares
    by rules of its own, which the scalar form keeps."""
    if isinstance(v, np.integer):
        v = int(v)
    elif type(v) is float and dt.kind != "f" and v.is_integer():
        v = int(v)
    if type(v) is int:
        if dt.kind == "f":
            return np.float64(v) if abs(v) <= 1 << 53 else None
        info = np.iinfo(dt)
        return dt.type(v) if info.min <= v <= info.max else None
    if type(v) is float and dt.kind == "f":
        return np.float64(v)
    return None
