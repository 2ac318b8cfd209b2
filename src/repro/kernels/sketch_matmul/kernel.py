"""Dense-sketch apply kernels.

Two variants:

1. ``matmul_kernel`` — classic VMEM-tiled S·A with MXU-aligned blocks and
   in-place accumulation over the innermost (contraction) grid dimension.
   This is the paper-faithful dense Gaussian/uniform apply: S is read from
   HBM, so HBM traffic is O(d·m + m·n + d·n) — dominated by the d·m sketch
   matrix itself in the overdetermined regime m ≫ n ≈ d.

2. ``fused_gaussian_kernel`` — beyond-paper optimization: S is never
   materialized.  Each (bd, bm) tile of S is *generated inside the kernel*
   from a counter-based threefry2x32 PRNG (uint32 add/xor/rotate only —
   bit-identical to the jnp oracle in ref.py) + Box–Muller, then immediately
   consumed by the MXU.  HBM traffic drops to O(m·n + d·n): the memory-
   roofline term of the dense sketch collapses by a factor ≈ d·m/(m·n) = d/n.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import bits_to_gaussian, mxu_dot, threefry2x32


def matmul_kernel(s_ref, a_ref, o_ref):
    """Grid (d_blocks, n_blocks, m_blocks); m innermost accumulates."""
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += mxu_dot(s_ref[...], a_ref[...], o_ref.dtype)


def fused_gaussian_kernel(k0_ref, k1_ref, scale_ref, a_ref, o_ref):
    """Generate the S tile on the fly (threefry2x32 + Box–Muller), then MAC.

    Counter scheme: element (i, j) of S uses the uint32 pair (i, j) — unique
    per element and independent of the block decomposition, so any tiling
    produces bitwise-identical S.
    """
    di = pl.program_id(0)
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]
    bm = a.shape[0]
    bd = o_ref.shape[0]

    rows = (di * bd + jax.lax.broadcasted_iota(jnp.int32, (bd, bm), 0)).astype(
        jnp.uint32
    )
    cols = (mi * bm + jax.lax.broadcasted_iota(jnp.int32, (bd, bm), 1)).astype(
        jnp.uint32
    )
    b0, b1 = threefry2x32(k0_ref[0, 0], k1_ref[0, 0], rows, cols)
    s_blk = bits_to_gaussian(b0, b1, jnp.float32) * scale_ref[0, 0]

    o_ref[...] += mxu_dot(s_blk.astype(a.dtype), a, o_ref.dtype)
