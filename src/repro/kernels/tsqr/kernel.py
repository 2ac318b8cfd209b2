"""Fused sketch→Gram Pallas kernels — the HBM-free half of ``sketch_qr``.

The unfused pipeline round-trips B = SA through HBM twice: the sketch
kernel writes B, then the QR reads it back.  These kernels keep each
(bd, n) panel of B resident in VMEM while it is being accumulated over the
m-grid and, on the panel's LAST accumulation step, immediately fold it
into the Gram matrix G = BᵀB — the only n×n quantity the CholeskyQR
finisher (``ops.cholqr_finish``) needs to produce R.  B is still emitted
once (Q-formation and the certified escalation path store it), but it is
never *re-read*: HBM traffic drops from 2·d·n reads + d·n writes to a
single d·n write, and the Gram GEMM runs at MXU rate on tiles that are
already resident.

Grid convention: ``(d_blocks, m_blocks)`` with m innermost, so each B
panel is revisited across sequential m-steps (legal TPU accumulation via
``pl.when(mi == 0)`` init).  The Gram output block is revisited across the
WHOLE grid (index map constant), initialized at the first grid step and
accumulated at every panel's last m-step.  n is not blocked: the fused
path targets the paper's tall-skinny regime n ≤ a few hundred, where one
(bd, n_pad) panel plus the (n_pad, n_pad) Gram fit VMEM comfortably
(``ops.py`` guards the limit and falls back to the unfused path beyond
it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import bits_to_gaussian, mxu_dot, threefry2x32
from ..countsketch.kernel import masked_rows, signed_onehot


def _accumulate_gram(b_ref, g_ref, di, mi, m_blocks):
    """Fold the finished B panel into G once per d-block (last m-step)."""

    @pl.when((di == 0) & (mi == 0))
    def _init_gram():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(mi == m_blocks - 1)
    def _fold():
        b = b_ref[...]
        g_ref[...] += mxu_dot(b, b, g_ref.dtype, contract=((0,), (0,)))  # bᵀ·b


def panel_gram_kernel(b_ref, g_ref):
    """G = BᵀB accumulated over row panels.  Grid: (p_blocks,)."""
    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    b = b_ref[...]
    g_ref[...] += mxu_dot(b, b, g_ref.dtype, contract=((0,), (0,)))


def countsketch_gram_kernel(buckets_ref, signs_ref, a_ref, b_ref, g_ref, *, m=None):
    """Fused CountSketch apply + Gram.  Grid: (d_blocks, m_blocks).

    Same signed one-hot matmul as ``countsketch.kernel`` (padded rows
    carry sign 0 and a partial last m-tile is masked, padded d rows
    receive no bucket — all Gram-neutral).  ``m`` is the row count when
    the last m-tile is partial (else None).
    """
    di = pl.program_id(0)
    mi = pl.program_id(1)
    m_blocks = pl.num_programs(1)
    bd = b_ref.shape[0]

    @pl.when(mi == 0)
    def _init():
        b_ref[...] = jnp.zeros_like(b_ref)

    a = masked_rows(a_ref[...], mi, m)  # (bm, n_pad)
    p = signed_onehot(buckets_ref[...], signs_ref[...], di, bd, a.dtype)
    b_ref[...] += mxu_dot(p, a, b_ref.dtype)
    _accumulate_gram(b_ref, g_ref, di, mi, m_blocks)


def matmul_gram_kernel(s_ref, a_ref, b_ref, g_ref):
    """Fused dense-sketch apply + Gram.  Grid: (d_blocks, m_blocks).

    Padded rows of S are zero, so padded d rows of B are zero and
    Gram-neutral.
    """
    di = pl.program_id(0)
    mi = pl.program_id(1)
    m_blocks = pl.num_programs(1)

    @pl.when(mi == 0)
    def _init():
        b_ref[...] = jnp.zeros_like(b_ref)

    b_ref[...] += mxu_dot(s_ref[...], a_ref[...], b_ref.dtype)
    _accumulate_gram(b_ref, g_ref, di, mi, m_blocks)


def make_gaussian_gram_kernel(d: int):
    """Fused in-kernel-PRNG Gaussian apply + Gram (d is static).

    Unlike the CountSketch/matmul variants, padded d rows WOULD hold
    garbage Gaussians times real data — they are masked to zero before
    the MAC so the Gram stays exact.  Counter scheme identical to
    ``sketch_matmul.fused_gaussian_kernel`` (element (i, j) ← pair
    (i, j)), so B matches the unfused kernel bit-for-bit per element.
    """

    def gaussian_gram_kernel(k0_ref, k1_ref, scale_ref, a_ref, b_ref, g_ref):
        di = pl.program_id(0)
        mi = pl.program_id(1)
        m_blocks = pl.num_programs(1)

        @pl.when(mi == 0)
        def _init():
            b_ref[...] = jnp.zeros_like(b_ref)

        a = a_ref[...]
        bm = a.shape[0]
        bd = b_ref.shape[0]

        rows = di * bd + jax.lax.broadcasted_iota(jnp.int32, (bd, bm), 0)
        cols = mi * bm + jax.lax.broadcasted_iota(jnp.int32, (bd, bm), 1)
        b0, b1 = threefry2x32(
            k0_ref[0, 0], k1_ref[0, 0],
            rows.astype(jnp.uint32), cols.astype(jnp.uint32),
        )
        s_blk = bits_to_gaussian(b0, b1, jnp.float32) * scale_ref[0, 0]
        s_blk = jnp.where(rows < d, s_blk, 0.0)

        b_ref[...] += mxu_dot(s_blk.astype(a.dtype), a, b_ref.dtype)
        _accumulate_gram(b_ref, g_ref, di, mi, m_blocks)

    return gaussian_gram_kernel
