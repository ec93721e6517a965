"""Arrow-compatible column blocks: ONE typed, contiguous buffer contract
from memtable to HBM (ROADMAP item 2, the Arrow-native zero-copy spine).

Every data-plane layer used to re-materialize its own private copy of
the same columns — the pooled parser into arena arrays, the memtable
seal into concatenated lanes, the reader through `combine_chunks`, the
device staging through `np.ascontiguousarray`. memtrace (PR 19) made
each of those hand-offs visible as a `copy` event; this module makes
them unnecessary by giving all layers one block type to pass BY
REFERENCE:

- **ColBlock** — named, typed, 1-D column lanes over contiguous
  64-byte-aligned backing with a mutability contract: a block starts
  writable (single owner), `freeze()` bumps its epoch and flips every
  public lane read-only. After the freeze any number of consumers may
  hold the block; sharing it is a `reuse` event, mutating it requires
  the sanctioned `cow()` (a tracked copy) — writes through a frozen
  lane raise. Device staging (`to_device`) exports the internal
  writable backing straight through `jax.device_put`, so the H2D
  transfer is charged exactly once (`device_staged`) with NO
  intermediate host staging copy.
- **GrowableColBlock** — the ingest arena: geometric growth (tracked
  `alloc`), steady-state appends into preallocated capacity (tracked
  `reuse` via adopt_spare), `seal()` detaches the filled prefix as a
  frozen ColBlock of zero-copy views and returns the backing for the
  double-buffer spare pool.
- **ArrowLanes** — chunk-aware lane access over a (possibly chunked)
  pyarrow Table: per-chunk zero-copy numpy views (`chunks`), a
  sorted-index gather that never materializes the full column
  (`gather_sorted`), and a contiguous-lane fallback (`lane`) that is a
  view for single-chunk columns and ONE sanctioned tracked copy
  otherwise. The scan merge consumes lanes chunk-wise, so the four
  per-column `combine_chunks` copies the r19 baseline pinned on
  host_prep disappear.

Constructing a fresh numpy array from a block's data OUTSIDE these
sanctioned APIs in data-plane modules is a jaxlint J025 finding — the
static twin of the memtrace runtime gate.
"""

from __future__ import annotations

import numpy as np

from horaedb_tpu.common import memtrace
from horaedb_tpu.common.error import HoraeError, ensure

# One TPU lane / x86 cacheline: jax.device_put on XLA:CPU can reuse
# aligned contiguous host buffers without an intermediate repack, and
# parquet/dlpack consumers never see a misaligned lane.
ALIGNMENT = 64


def aligned_empty(n: int, dtype) -> np.ndarray:
    """Uninitialized 1-D array whose data pointer is ALIGNMENT-aligned
    (numpy only guarantees 16). Over-allocates one alignment unit of u8
    and slices to the aligned offset; the returned array keeps the raw
    buffer alive via .base."""
    dt = np.dtype(dtype)
    nbytes = int(n) * dt.itemsize
    raw = np.empty(nbytes + ALIGNMENT, dtype=np.uint8)
    off = (-raw.ctypes.data) % ALIGNMENT
    return raw[off:off + nbytes].view(dt)


class ColBlock:
    """Named typed column lanes with a stable memory contract.

    Ownership protocol:

    1. build writable (``alloc`` / ``wrap``), fill lanes in place;
    2. ``freeze()`` — epoch bump, public lanes flip read-only;
    3. hand the block around by reference: ``share()`` records the
       `reuse`, ``lane()`` hands out read-only views, ``to_device()``
       stages via the internal writable backing (one `device_staged`
       charge, no host-side staging copy), ``to_arrow_batch()`` wraps
       the lanes zero-copy for the parquet/.enc writers;
    4. a consumer that must mutate calls ``cow()`` — the ONE sanctioned
       copy, tracked — and gets a fresh writable block at a new epoch.

    Optional per-lane validity rides along as boolean masks (arrow
    semantics: True = valid); lanes without nulls carry None.
    """

    __slots__ = ("_lanes", "_public", "_validity", "_frozen", "_epoch")

    def __init__(
        self,
        lanes: dict[str, np.ndarray],
        validity: dict[str, np.ndarray] | None = None,
    ) -> None:
        n = None
        for name, arr in lanes.items():
            ensure(arr.ndim == 1, f"column lane {name!r} must be 1-D")
            if n is None:
                n = len(arr)
            ensure(
                len(arr) == n,
                f"ragged column block: lane {name!r} has {len(arr)} rows, "
                f"expected {n}",
            )
        self._lanes = dict(lanes)
        self._public: dict[str, np.ndarray] = {}
        self._validity = dict(validity) if validity else None
        self._frozen = False
        self._epoch = 0

    # -- construction -------------------------------------------------------

    @classmethod
    def alloc(
        cls, schema: dict[str, np.dtype], n: int, stage: str
    ) -> "ColBlock":
        """Fresh writable block: one aligned allocation per lane, each a
        tracked `alloc` under `stage`."""
        lanes = {}
        for name, dt in schema.items():
            a = aligned_empty(n, dt)
            memtrace.track(a, stage, "alloc")
            lanes[name] = a
        return cls(lanes)

    @classmethod
    def wrap(cls, lanes: dict[str, np.ndarray]) -> "ColBlock":
        """Adopt existing arrays BY REFERENCE (ownership transfer, not a
        hand-off — no lineage event). The caller must not mutate them
        behind the block's back after freeze()."""
        return cls(lanes)

    # -- contract surface ---------------------------------------------------

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(self._lanes)

    @property
    def n_rows(self) -> int:
        first = next(iter(self._lanes.values()), None)
        return 0 if first is None else len(first)

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self._lanes.values())

    @property
    def frozen(self) -> bool:
        return self._frozen

    @property
    def epoch(self) -> int:
        """Mutability epoch: bumped by freeze() and by every cow(), so a
        consumer that cached derived state can detect it is stale."""
        return self._epoch

    def aligned(self) -> bool:
        return all(
            a.ctypes.data % ALIGNMENT == 0 for a in self._lanes.values()
        )

    def validity(self, name: str) -> np.ndarray | None:
        if self._validity is None:
            return None
        v = self._validity.get(name)
        return None if v is None else self._read_only_of(v)

    # -- mutability protocol ------------------------------------------------

    def writable_lane(self, name: str) -> np.ndarray:
        """The backing lane, writable — single-owner fill phase only."""
        if self._frozen:
            raise HoraeError(
                f"column block is frozen (epoch {self._epoch}); "
                f"mutate through cow(), not writable_lane({name!r})"
            )
        return self._lanes[name]

    def freeze(self) -> "ColBlock":
        """End the fill phase: epoch bump, public lanes flip read-only.
        Idempotent. The internal backing stays writable so dlpack/device
        export never needs a defensive copy."""
        if not self._frozen:
            self._frozen = True
            self._epoch += 1
            self._public.clear()
        return self

    def share(self, stage: str) -> "ColBlock":
        """Hand the frozen block to another consumer by reference — a
        `reuse` event (bytes exist once, a new holder appears)."""
        ensure(self._frozen, "only frozen column blocks may be shared")
        memtrace.track_bytes(self.nbytes, stage, "reuse")
        return self

    def cow(self, stage: str) -> "ColBlock":
        """Copy-on-write: a frozen block yields a fresh WRITABLE block at
        a new epoch (the one sanctioned whole-block copy, tracked per
        lane); an unfrozen block is single-owner and returns itself."""
        if not self._frozen:
            return self
        lanes = {}
        for name, a in self._lanes.items():
            dst = aligned_empty(len(a), a.dtype)
            dst[:] = a
            memtrace.track(dst, stage, "copy")
            lanes[name] = dst
        out = ColBlock(lanes, self._validity)
        out._epoch = self._epoch + 1
        return out

    # -- lane access --------------------------------------------------------

    def _read_only_of(self, arr: np.ndarray) -> np.ndarray:
        v = arr.view()
        v.flags.writeable = False
        return v

    def lane(self, name: str) -> np.ndarray:
        """Zero-copy view of one lane; read-only once frozen (a write
        through it raises), cached per name."""
        got = self._public.get(name)
        if got is None:
            a = self._lanes[name]
            got = self._read_only_of(a) if self._frozen else a
            self._public[name] = got
        return got

    def lanes(self) -> dict[str, np.ndarray]:
        return {name: self.lane(name) for name in self._lanes}

    def copy_lane(self, name: str, stage: str) -> np.ndarray:
        """Sanctioned single-lane materialization — always a tracked
        copy, always writable and aligned."""
        a = self._lanes[name]
        dst = aligned_empty(len(a), a.dtype)
        dst[:] = a
        memtrace.track(dst, stage, "copy")
        return dst

    # -- export -------------------------------------------------------------

    def to_device(
        self, stage: str = "h2d", names: tuple[str, ...] | None = None
    ):
        """Stage lanes to the default device: `jax.device_put` straight
        off the internal WRITABLE backing (numpy refuses dlpack export of
        read-only arrays, so the public frozen views would force exactly
        the defensive copy this type exists to kill). ONE `device_staged`
        charge for the transfer — no intermediate host alloc, no
        double-charged staging bytes."""
        import jax

        picked = self.names if names is None else names
        out = {n: jax.device_put(self._lanes[n]) for n in picked}
        memtrace.device_staged(
            sum(int(self._lanes[n].nbytes) for n in picked), stage
        )
        return out

    def to_arrow_batch(self, schema, stage: str = "flush_encode"):
        """The block as a pyarrow RecordBatch of zero-copy lane views
        (primitive lanes wrap without moving bytes) — the parquet/.enc
        writers' feed. Tracked as one `view` of the block's bytes."""
        import pyarrow as pa

        arrays = []
        for field in schema:
            lane = self._lanes[field.name]
            v = self._validity.get(field.name) if self._validity else None
            arrays.append(pa.array(lane, type=field.type, mask=(
                None if v is None else ~v
            )))
        memtrace.track_bytes(self.nbytes, stage, "view")
        return pa.RecordBatch.from_arrays(arrays, schema=schema)


class GrowableColBlock:
    """The ingest-side arena: appends land in preallocated capacity,
    growth is geometric (tracked `alloc`), and `seal()` detaches the
    filled prefix as a frozen ColBlock of zero-copy views — the memtable
    double-buffer without the recycled-array copy.

    `adopt_spare()` re-issues a previous generation's backing (a `reuse`
    event — the pooled analog of DecodeArena's steady state)."""

    __slots__ = ("_schema", "_stage", "_lanes", "_fill", "_cap")

    def __init__(
        self,
        schema: dict[str, np.dtype],
        capacity: int = 1024,
        stage: str = "append",
    ) -> None:
        self._schema = {k: np.dtype(v) for k, v in schema.items()}
        self._stage = stage
        self._cap = max(int(capacity), 1)
        self._lanes = {
            name: aligned_empty(self._cap, dt)
            for name, dt in self._schema.items()
        }
        for a in self._lanes.values():
            memtrace.track(a, stage, "alloc")
        self._fill = 0

    @classmethod
    def adopt_spare(
        cls, spare: dict[str, np.ndarray], stage: str = "append"
    ) -> "GrowableColBlock":
        """Rebuild an arena over a recycled backing (the flush executor
        returns the previous generation's lanes once its write-out
        lands): capacity already exists, so this is a `reuse`."""
        self = cls.__new__(cls)
        self._schema = {k: a.dtype for k, a in spare.items()}
        self._stage = stage
        self._lanes = dict(spare)
        self._cap = min((len(a) for a in spare.values()), default=0)
        self._fill = 0
        memtrace.track_bytes(
            sum(int(a.nbytes) for a in spare.values()), stage, "reuse"
        )
        return self

    @property
    def n_rows(self) -> int:
        return self._fill

    @property
    def capacity(self) -> int:
        return self._cap

    def reserve(self, n: int) -> None:
        """Ensure room for `n` more rows; geometric growth, filled prefix
        carried over (the ONE copy growth pays, tracked)."""
        need = self._fill + int(n)
        if need <= self._cap:
            return
        cap = max(2 * self._cap, need)
        grown = {}
        for name, a in self._lanes.items():
            g = aligned_empty(cap, a.dtype)
            memtrace.track(g, self._stage, "alloc")
            g[: self._fill] = a[: self._fill]
            grown[name] = g
        self._lanes = grown
        self._cap = cap

    def append(self, rows: dict[str, np.ndarray]) -> None:
        """Append one batch of rows (whole-column slice assignment into
        the preallocated lanes — no per-row work, no new buffers)."""
        n = min((len(a) for a in rows.values()), default=0)
        if n == 0:
            return
        self.reserve(n)
        f = self._fill
        for name, src in rows.items():
            self._lanes[name][f:f + n] = src
        self._fill = f + n

    def writable_lane(self, name: str) -> np.ndarray:
        """The full-capacity backing lane (parsers fill `[fill:fill+n]`
        in place, then commit(n))."""
        return self._lanes[name]

    def commit(self, n: int) -> None:
        """Account rows a caller wrote directly into writable_lane()."""
        ensure(
            self._fill + n <= self._cap,
            "commit() past the reserved arena capacity",
        )
        self._fill += int(n)

    def seal(self) -> tuple[ColBlock, dict[str, np.ndarray]]:
        """Detach the filled prefix as a frozen ColBlock (zero-copy
        views, tracked `seal` view once) and hand back the raw backing
        for the spare pool. The arena is empty afterwards."""
        fill = self._fill
        views = {name: a[:fill] for name, a in self._lanes.items()}
        block = ColBlock.wrap(views).freeze()
        memtrace.track_bytes(block.nbytes, "seal", "view")
        backing = self._lanes
        self._lanes = {
            name: aligned_empty(0, dt) for name, dt in self._schema.items()
        }
        self._cap = 0
        self._fill = 0
        return block, backing


def as_lane(arr, dtype, stage: str) -> np.ndarray:
    """Coerce an array to a contiguous typed lane through the funnel:
    a `view` when the input already satisfies the contract (no bytes
    move), ONE tracked `copy` when a dtype/layout conversion is
    unavoidable — the sanctioned staging-prep spelling (the old
    `tracked_contiguous(np.asarray(...))` pattern mis-filed conversion
    copies as views because the fresh asarray output was already
    contiguous by the time the funnel looked)."""
    a = np.asarray(arr)
    out = np.ascontiguousarray(a, dtype=dtype)
    memtrace.track_bytes(
        int(out.nbytes), stage, "view" if out is a else "copy"
    )
    return out


# ---------------------------------------------------------------------------
# Arrow-side lanes: chunk-aware zero-copy access over pyarrow tables.


def _chunk_to_numpy(chunk) -> tuple[np.ndarray, bool]:
    """One arrow chunk as numpy: (array, was_zero_copy). Null-free
    primitive chunks view the arrow buffer directly; nulls or bit-packed
    bools force a real conversion (arrow_column_to_numpy's fill path)."""
    import pyarrow as pa

    from horaedb_tpu.ops.blocks import arrow_column_to_numpy

    t = chunk.type
    zero_copy = chunk.null_count == 0 and not pa.types.is_boolean(t) and (
        pa.types.is_integer(t)
        or pa.types.is_floating(t)
        or pa.types.is_timestamp(t)
    )
    return arrow_column_to_numpy(chunk), zero_copy


class ArrowLanes:
    """Chunk-aware column access over a (possibly chunked) pyarrow
    Table: the reader's merge consumes lanes chunk-wise instead of
    paying one `combine_chunks` copy per touched column.

    - ``chunks(name)`` — per-chunk zero-copy numpy views, sliced to ONE
      common chunk layout (the first accessed column's); a column whose
      native chunking disagrees is materialized once through the
      sanctioned funnel and re-sliced (views).
    - ``gather_sorted(name, idx)`` — compacted gather for a sorted index
      vector (np.nonzero output) without materializing the column.
    - ``lane(name)`` — full contiguous lane: a view for single-chunk
      columns, ONE tracked copy otherwise (the device-route fallback).

    First access to a column records one lineage event under `stage`:
    `view` when every chunk wrapped zero-copy, `copy` otherwise.
    ``presorted_cache`` memoizes the chunk-aware sortedness probe
    (storage/read.py `_lanes_presorted`) across planner probes."""

    __slots__ = (
        "_table", "_stage", "_chunks", "_lanes", "_bounds",
        "presorted_cache",
    )

    def __init__(self, table, stage: str = "host_prep") -> None:
        self._table = table
        self._stage = stage
        self._chunks: dict[str, list[np.ndarray]] = {}
        self._lanes: dict[str, np.ndarray] = {}
        self._bounds: np.ndarray | None = None
        self.presorted_cache: dict[tuple, bool] = {}

    @property
    def n_rows(self) -> int:
        return self._table.num_rows

    @property
    def bounds(self) -> np.ndarray:
        """Common chunk layout: row offsets of chunk starts + final n."""
        if self._bounds is None:
            if self._table.num_columns == 0:
                self._bounds = np.array([0, self._table.num_rows])
            else:
                lens = [len(c) for c in self._table.column(0).chunks]
                self._bounds = np.concatenate(
                    [[0], np.cumsum(lens, dtype=np.int64)]
                ) if lens else np.array([0, 0])
        return self._bounds

    def chunks(self, name: str) -> list[np.ndarray]:
        got = self._chunks.get(name)
        if got is not None:
            return got
        bounds = self.bounds
        col = self._table.column(name)
        native = [len(c) for c in col.chunks]
        common = list(np.diff(bounds))
        if native == common:
            views, all_zero_copy = [], True
            for ch in col.chunks:
                a, zc = _chunk_to_numpy(ch)
                all_zero_copy &= zc
                views.append(a)
        else:
            # layout disagrees with the common one: materialize once
            # through the funnel, re-slice into aligned views
            full = self._materialize(name)
            views = [
                full[int(bounds[i]):int(bounds[i + 1])]
                for i in range(len(bounds) - 1)
            ]
            self._chunks[name] = views
            return views
        memtrace.track_bytes(
            int(col.nbytes), self._stage,
            "view" if all_zero_copy else "copy",
        )
        self._chunks[name] = views
        return views

    def chunk_edges(self, names) -> list[tuple[tuple, tuple]]:
        """(first row, last row) over `names` of every non-empty chunk of
        the common layout, read as arrow scalars: no lane is wrapped or
        copied for it."""
        cols = [self._table.column(n) for n in names]
        b = self.bounds
        return [
            (tuple(c[int(b[i])].as_py() for c in cols),
             tuple(c[int(b[i + 1]) - 1].as_py() for c in cols))
            for i in range(len(b) - 1) if b[i + 1] > b[i]
        ]

    def _materialize(self, name: str) -> np.ndarray:
        from horaedb_tpu.ops.blocks import arrow_column_to_numpy

        a = arrow_column_to_numpy(
            memtrace.tracked_combine(self._table.column(name), self._stage)
        )
        self._lanes[name] = a
        return a

    def lane(self, name: str) -> np.ndarray:
        """Full contiguous lane — the fallback for consumers that need
        one flat array (device staging, lexsort). Single-chunk columns
        come back as the existing chunk view; multi-chunk columns pay
        ONE sanctioned copy, cached."""
        got = self._lanes.get(name)
        if got is not None:
            return got
        if name not in self._chunks and self._table.column(name).num_chunks > 1:
            # one arrow combine, not a numpy view a chunk and a concat:
            # the same one copy in two calls instead of three a chunk
            return self._materialize(name)
        views = self.chunks(name)
        if len(views) == 1:
            a = views[0]
        elif len(views) == 0:
            a = np.empty(0, dtype=object)
        else:
            a = memtrace.tracked_concat(views, self._stage)
        self._lanes[name] = a
        return a

    def gather_sorted(self, name: str, idx: np.ndarray) -> np.ndarray:
        """Gather `lane[idx]` for a SORTED index vector (np.nonzero
        order) chunk-by-chunk — derived compute, no full-column
        materialization."""
        views = self.chunks(name)
        if len(views) == 1:
            return views[0][idx]
        bounds = self.bounds
        out = np.empty(
            len(idx),
            dtype=views[0].dtype if views else np.int64,
        )
        lo = 0
        for i, v in enumerate(views):
            hi = int(np.searchsorted(idx, int(bounds[i + 1]), side="left"))
            if hi > lo:
                out[lo:hi] = v[idx[lo:hi] - int(bounds[i])]
            lo = hi
        return out

    def eval_chunked(self, fn, names: list[str]) -> np.ndarray:
        """Evaluate `fn({name: chunk_lane})` per chunk, concatenating
        the (derived, boolean) results into one mask — the predicate
        path's chunk-wise spelling."""
        bounds = self.bounds
        nch = len(bounds) - 1
        if nch <= 1:
            return fn({c: self.lane(c) for c in names})
        per = {c: self.chunks(c) for c in names}
        out = np.empty(int(bounds[-1]), dtype=bool)
        for i in range(nch):
            lo, hi = int(bounds[i]), int(bounds[i + 1])
            if hi > lo:
                out[lo:hi] = fn({c: per[c][i] for c in names})
        return out
