"""Mergeable partial-sketch accumulators — the heart of the streaming engine.

Every sketch S in ``repro.core.sketch`` is linear in the rows of A, so
``SA`` decomposes over any row tiling and partial sketches from disjoint
tiles combine associatively.  A :class:`SketchAccumulator` holds that
partial state:

    acc = make_accumulator(op, ncols)
    for offset, tile in source.tiles():
        acc.update(tile, offset)       # O(tile) work, O(state) memory
    B = acc.finalize()                 # == op.apply(A) for the full A

``merge`` combines accumulators built over disjoint row ranges (different
tiles, different hosts) and is associative, so partial sketches
tree-reduce; :func:`sharded_sketch` is the collective (shard_map + psum)
form of the same merge for a row-sharded in-memory A.

Exactness (what the property tests pin):

- **countsketch / uniform_sparse** — updates scatter-add *into the state*
  in row order, which is exactly the fold XLA's ``segment_sum`` performs;
  sequential streaming is bit-for-bit equal to the monolithic apply.
- **sparse_sign** — the monolithic apply sums k independent scatter
  passes *before* scaling, so the state keeps the (k, d, ncols) per-pass
  partials and reproduces that exact reduction at finalize: bitwise too.
- **srht** — the Hadamard transform couples every row, so the state is
  the (m_pad, ncols) D-signed row buffer (placement, no summation) and
  FWHT + subsample + 1/√d run once at finalize: bitwise equal, by
  construction, to the reference apply.  Note the buffer is O(m_pad·n) —
  SRHT streams *compute* (single pass, mergeable) but not *memory*;
  prefer the scatter kinds for out-of-core data.
- **gaussian / uniform_dense** — each tile contributes one (d, t)×(t, n)
  block product.  The realized S blocks are bitwise identical to slicing
  the monolithic S (counter-based regeneration for Gaussian), but summing
  block products groups the fp additions differently from one big GEMM,
  so the product agrees to accumulation-order rounding only (same caveat
  as swapping sketch backends).

``merge`` adds partial states, which for the additive kinds introduces the
same accumulation-order rounding; only SRHT merges exactly (disjoint row
placements).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import backend as backend_lib
from ..core import sketch as sketch_lib
__all__ = [
    "SketchAccumulator",
    "make_accumulator",
    "accumulate_source",
    "merge_all",
    "sharded_sketch",
]


class SketchAccumulator:
    """Partial sketch of a row-streamed A: update / merge / finalize.

    ``ncols`` is the column count of the streamed tiles (n, or n+1 when
    the right-hand side rides along as an extra column).  ``rows_seen``
    tracks coverage; ``finalize`` refuses to produce a sketch from a
    stream that missed rows (merge first, then finalize).
    """

    def __init__(self, op, ncols: int, dtype=jnp.float64, backend="auto"):
        self.op = op
        self.ncols = int(ncols)
        self.dtype = jnp.dtype(dtype)
        self.backend = backend_lib.resolve(backend).name
        self.rows_seen = 0
        self.tiles_seen = 0
        self.state = self._init_state()

    # ---------------------------------------------------- per-kind state
    def _init_state(self):
        op = self.op
        if isinstance(op, sketch_lib.SRHTSketch):
            # Placement buffer for the finalize-time Hadamard transform.
            # Kept host-side (numpy) so per-tile updates are in-place
            # writes, not O(m_pad·ncols) device-buffer copies.
            return np.zeros((op.m_pad, self.ncols), np.dtype(self.dtype))
        if isinstance(op, sketch_lib.SparseSignSketch):
            return jnp.zeros((op.k, op.d, self.ncols), self.dtype)
        return jnp.zeros((op.d, self.ncols), self.dtype)

    # ----------------------------------------------------------- update
    def update(self, tile, row_offset: int) -> "SketchAccumulator":
        """Fold rows [row_offset, row_offset + t) of A into the state."""
        op = self.op
        t, ncols = tile.shape
        if ncols != self.ncols:
            raise ValueError(f"tile has {ncols} columns, expected {self.ncols}")
        if row_offset < 0 or row_offset + t > op.m:
            raise ValueError(
                f"tile rows [{row_offset}, {row_offset + t}) outside "
                f"[0, {op.m})"
            )
        sl = slice(row_offset, row_offset + t)
        if isinstance(op, sketch_lib.SRHTSketch):
            self.state[sl] += np.asarray(op.apply_rows(tile, row_offset))
        elif isinstance(op, sketch_lib.CountSketch):
            tile = jnp.asarray(tile)
            contrib = op.signs[sl][:, None].astype(tile.dtype) * tile
            self.state = self.state.at[op.buckets[sl]].add(contrib)
        elif isinstance(op, sketch_lib.UniformSparseSketch):
            tile = jnp.asarray(tile)
            contrib = op.values[sl][:, None].astype(tile.dtype) * tile
            self.state = self.state.at[op.buckets[sl]].add(contrib)
        elif isinstance(op, sketch_lib.SparseSignSketch):
            tile = jnp.asarray(tile)
            contrib = op.signs[:, sl, None].astype(tile.dtype) * tile[None]
            self.state = jax.vmap(lambda s, h, c: s.at[h].add(c))(
                self.state, op.buckets[:, sl], contrib
            )
        else:  # dense-S kinds: one (d, t) × (t, ncols) block product
            self.state = self.state + op.apply_rows(
                jnp.asarray(tile), row_offset, backend=self.backend
            )
        self.rows_seen += t
        self.tiles_seen += 1
        return self

    # ------------------------------------------------------------ merge
    def merge(self, other: "SketchAccumulator") -> "SketchAccumulator":
        """Combine with a partial sketch over a DISJOINT row range.

        Associative (tree-reduce freely across tiles/hosts); both sides
        must have been built from the same operator draw.
        """
        same_shape = type(self.op) is type(other.op) and (
            self.op.d,
            self.op.m,
            self.ncols,
        ) == (other.op.d, other.op.m, other.ncols)
        if same_shape and self.op is not other.op:
            # distinct objects (e.g. independently deserialized per host):
            # verify it is the SAME draw, not merely the same shape —
            # merging two different S's silently poisons the sketch
            def leaf_eq(a, b):
                if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
                    a, b = jax.random.key_data(a), jax.random.key_data(b)
                return a.shape == b.shape and bool(jnp.array_equal(a, b))

            la, lb = jax.tree.leaves(self.op), jax.tree.leaves(other.op)
            same_shape = len(la) == len(lb) and all(
                leaf_eq(a, b) for a, b in zip(la, lb)
            )
        if not same_shape:
            raise ValueError(
                "can only merge partial sketches of the same operator draw; "
                f"got {type(self.op).__name__}(d={self.op.d}, m={self.op.m}) "
                f"x{self.ncols} vs "
                f"{type(other.op).__name__}(d={other.op.d}, m={other.op.m}) "
                f"x{other.ncols}"
            )
        out = make_accumulator(
            self.op, self.ncols, dtype=self.dtype, backend=self.backend
        )
        out.state = self.state + other.state
        out.rows_seen = self.rows_seen + other.rows_seen
        out.tiles_seen = self.tiles_seen + other.tiles_seen
        return out

    # --------------------------------------------------------- finalize
    def finalize(self) -> jax.Array:
        """The assembled sketch B = S·A — equals ``op.apply`` on the full A."""
        if self.rows_seen != self.op.m:
            raise ValueError(
                f"stream covered {self.rows_seen} of m={self.op.m} rows; "
                "merge the remaining partial sketches before finalize"
            )
        op = self.op
        if isinstance(op, sketch_lib.SRHTSketch):
            HDx = sketch_lib.fwht(jnp.asarray(self.state))
            return HDx[op.rows] / jnp.sqrt(jnp.asarray(op.d, self.dtype))
        if isinstance(op, sketch_lib.SparseSignSketch):
            return self.state.sum(0) / jnp.sqrt(jnp.asarray(op.k, self.dtype))
        return self.state


def make_accumulator(op, ncols: int, dtype=jnp.float64, backend="auto"):
    """Fresh accumulator for one operator draw (see module docstring)."""
    return SketchAccumulator(op, ncols, dtype=dtype, backend=backend)


def accumulate_source(
    op, source, *, base_offset: int = 0, backend="auto", acc=None
) -> SketchAccumulator:
    """Stream every tile of ``source`` into an accumulator.

    ``base_offset`` shifts the source's local offsets into the global row
    space — accumulating shard i of a ``ShardedSource`` uses
    ``base_offset=source.shard_offsets[i]`` so the per-shard partials
    merge into the same global sketch.
    """
    m, ncols = source.shape
    if acc is None:
        acc = make_accumulator(
            op, ncols, dtype=jnp.dtype(source.dtype), backend=backend
        )
    for offset, tile in source.tiles():
        acc.update(tile, base_offset + offset)
    return acc


def merge_all(accs) -> SketchAccumulator:
    """Pairwise tree-reduction of partial accumulators (associative)."""
    accs = list(accs)
    if not accs:
        raise ValueError("nothing to merge")
    while len(accs) > 1:
        nxt = [
            accs[i].merge(accs[i + 1]) if i + 1 < len(accs) else accs[i]
            for i in range(0, len(accs), 2)
        ]
        accs = nxt
    return accs[0]


def sharded_sketch(A, op, *, mesh, axes=("data",), backend="auto"):
    """S·A for a row-sharded in-memory A in ONE collective.

    The shard_map form of :meth:`SketchAccumulator.merge`: every device
    restricts S to its global row slice (``op.restrict_cols``), sketches
    its local rows, and a single psum tree-reduces the (d, n) partial
    sketches across ``axes``.  Communication is O(d·n), independent of m —
    the same assembly ``repro.core.distributed.sketched_lstsq`` performs
    inside its solver.

    Additive kinds only: SRHT couples rows through the Hadamard transform
    and has no independent column restriction — stream it through the
    padded-buffer accumulator instead.
    """
    if op.stream_semantics != "add":
        raise ValueError(
            f"{type(op).__name__} cannot be assembled by per-shard "
            "restriction (stream_semantics="
            f"{op.stream_semantics!r}); use make_accumulator instead"
        )
    backend = backend_lib.resolve(backend).name
    if isinstance(axes, str):
        axes = (axes,)
    idx = jnp.arange(op.m, dtype=jnp.int32)

    def local(A_i, idx_i):
        sub = op.restrict_cols(idx_i)
        return lax.psum(sub.apply(A_i, backend=backend), axes)

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(P(axes, None), P(axes)), out_specs=P(),
        check_vma=False,
    )
    return fn(A, idx)
