"""CountSketch (Clarkson–Woodruff) apply as a TPU Pallas kernel.

GPU implementations scatter-add rows (`SA[h[i]] += s[i]·A[i]`) with atomics.
A TPU core runs one program at a time, so no atomics are needed: the kernel
keeps every bucket row of its output block resident in VMEM (a v5e has
128 MiB; fig3.fresh's d = 4000, n = 1000 takes 16 MB in float32) and adds
each row of A into its bucket's row, in A's own row order:

    SA[h[i], n_blk] += s[i] · A[i, n_blk]

The grid is (n_blocks, d_blocks, m_tiles), m innermost: the output block
stays put while the row tiles of A stream past it.  Buckets and signs of a
tile sit in SMEM, one scalar per row; the row add is a read-modify-write of
one sublane row of the output at a dynamic offset.  Work: A read once, SA
written once, one signed add per entry of A, in the accumulation dtype
(float32 for a half-precision A; no MXU pass, so nothing is rounded to
bfloat16).  Where the output rows do not fit VMEM (d beyond ~98k buckets
at 128 columns) the grid gains d-blocks; each walks every row and adds
those of its buckets.

A is read in the order XLA stores it (``ops.stored_by_columns``): a tile of
Aᵀ is transposed in VMEM, so no relayout copy of A precedes the kernel.  A
is not padded: rows past the end of a partial last tile are not visited.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def masked_rows(a, mi, m):
    """Zero the rows of a (bm, ·) tile at or beyond row m.

    The rows past the end of a partial last tile hold whatever the buffer
    held (possibly NaN), so they are selected away rather than multiplied
    by a zero sign.  ``m=None`` means every tile is full.
    """
    if m is None:
        return a
    bm = a.shape[0]
    row = mi * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    return jnp.where(row < m, a, jnp.zeros_like(a))


def signed_onehot(h, s, di, bd, dtype):
    """P (bd, bm): P[k, i] = s[i] where bucket h[i] is row di·bd + k.

    ``h`` (1, bm) int32 global bucket ids, ``s`` (1, bm) float32 signs.
    The select runs in 32 bits: Mosaic cannot relayout a 16-bit select
    mask, so P is cast to ``dtype`` afterwards (±1 and 0 are exact).
    """
    bm = h.shape[1]
    rows = di * bd + jax.lax.broadcasted_iota(jnp.int32, (bd, bm), 0)
    return jnp.where(rows == h, s, jnp.zeros_like(s)).astype(dtype)


def countsketch_kernel(buckets_ref, signs_ref, a_ref, out_ref, *scratch,
                       m=None, d_blocked=False, staged=False,
                       transposed=False):
    """Grid: (n_blocks, d_blocks, m_tiles) — m innermost (accumulation).

    ``buckets_ref``/``signs_ref`` (bm,) in SMEM, ``a_ref`` the tile of A
    (tm, bn), or of Aᵀ (bn, tm) where ``transposed``, with tm <= bm;
    ``out_ref`` (bd, bn) in the accumulation dtype.  Where ``staged``,
    ``scratch`` starts with a (tm, bn) tile in that dtype that the rows are
    read from: A's tile transposed, or widened from half precision.  The
    rest of ``scratch`` are extra accumulators: row r adds into accumulator
    r mod (1 + extra), and the last tile folds them into ``out_ref``.  The
    adds of successive rows into one buffer wait on each other (no two are
    known to miss), while adds into different buffers overlap.  ``m`` is
    the row count when the last tile is partial (else None); ``d_blocked``
    that the output holds only some of the buckets.
    """
    di = pl.program_id(1)
    mi = pl.program_id(2)
    tm = a_ref.shape[1 if transposed else 0]
    bd = out_ref.shape[0]
    rows_ref = a_ref
    if staged:
        rows_ref, *scratch = scratch
        tile = a_ref[...].astype(rows_ref.dtype)
        rows_ref[...] = tile.T if transposed else tile
    accs = (out_ref, *scratch)

    @pl.when(mi == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    def add_row(r, copy=0):
        acc = accs[copy]
        k = buckets_ref[r] - di * bd
        row = rows_ref[pl.ds(r, 1), :].astype(acc.dtype)

        def add():
            acc[pl.ds(k, 1), :] += signs_ref[r].astype(acc.dtype) * row

        if d_blocked:
            pl.when((k >= 0) & (k < bd))(add)
        else:
            add()

    n_rows = tm if m is None else jnp.minimum(tm, m - mi * tm)
    _each_row(n_rows, add_row, len(accs))

    if len(accs) > 1:
        @pl.when(mi == pl.num_programs(2) - 1)
        def _fold():
            out_ref[...] = sum((acc[...] for acc in accs[1:]), out_ref[...])


def _each_row(n_rows, fn, copies: int = 1, unroll: int = 8):
    """fn(r, r mod copies) for r in [0, n_rows), unrolled by ``unroll``
    (a multiple of ``copies``) with r's offset within each group static: an
    aligned sublane group of the tile.  Rows after the last whole group
    (a partial last tile) take fn(r, 0)."""
    def group(j, carry):
        base = pl.multiple_of(j * unroll, unroll)
        for k in range(unroll):
            fn(base + k, k % copies)
        return carry

    full = n_rows // unroll
    jax.lax.fori_loop(0, full, group, 0)
    jax.lax.fori_loop(full * unroll, n_rows,
                      lambda r, carry: (fn(r), carry)[1], 0)
