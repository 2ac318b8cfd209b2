"""CountSketch (Clarkson–Woodruff) apply as a TPU Pallas kernel.

GPU implementations scatter-add rows (`SA[h[i]] += s[i]·A[i]`) with atomics.
TPUs have neither fast VMEM scatter nor atomics, but they have an MXU that
eats 128-aligned tiles — so we recast the bucket scatter as a **blocked
signed one-hot matmul**:

    SA[d_blk, n_blk] += P(h[m_blk], s[m_blk], d_blk) · A[m_blk, n_blk]

where P[k, i] = s[i] if h[i] = k else 0.  The (bd, bm) tile P is built in
VMEM from an iota-compare against the bucket row vector (never touches
HBM), and the grid's innermost dimension runs over m-blocks so each (d,n)
output tile is accumulated in place across sequential grid steps (TPU grids
are sequential, which makes revisiting an output block a legal accumulation
pattern via ``pl.when(first_step)`` initialization).

Buckets (int32) and signs (float32) arrive as (1, m) rows: as (m, 1)
columns each would be laid out 128 lanes wide in HBM (512 MiB apiece at
m = 2^20).  A is not padded: a partial last tile (m or n not a multiple of
the block) is masked inside the kernel instead.

HBM traffic: A read once (m·n), SA written once (d·n) — same as the scatter
formulation.  Extra MXU flops (m·d·n vs m·n scattered adds) are free in the
paper's regime d ≈ 4n ≪ m where the apply is memory-bound.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import mxu_dot


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


def countsketch_kernel(buckets_ref, signs_ref, a_ref, out_ref, *, m=None):
    """Grid: (n_blocks, d_blocks, m_blocks) — m innermost (accumulation).

    ``m`` is the row count when the last m-tile is partial (else None).
    """
    di = pl.program_id(1)
    mi = pl.program_id(2)
    bd = out_ref.shape[0]

    @pl.when(mi == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = masked_rows(a_ref[...], mi, m)  # (bm, bn)
    p = signed_onehot(buckets_ref[...], signs_ref[...], di, bd, a.dtype)
    out_ref[...] += mxu_dot(p, a, out_ref.dtype)
