"""jit'd wrapper for the CountSketch Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import cdiv, mosaic_context, pad_to, resolve_interpret
from .kernel import countsketch_kernel

__all__ = ["countsketch_apply"]


@partial(
    jax.jit,
    static_argnames=("d", "block_m", "block_d", "block_n", "interpret"),
)
def countsketch_apply(
    A: jax.Array,
    buckets: jax.Array,
    signs: jax.Array,
    d: int,
    *,
    block_m: int = 256,
    block_d: int = 256,
    block_n: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """SA for the CountSketch (buckets, signs); A is (m, n) or (m,).

    Returns (d, n) in f32 accumulation dtype, cast back to A.dtype.
    ``interpret=None`` resolves via ``repro.core.backend.default_interpret``
    (real Mosaic on TPU, interpret mode elsewhere).
    """
    interpret = resolve_interpret(interpret, A)
    vec = A.ndim == 1
    if vec:
        A = A[:, None]
    m, n = A.shape
    acc_dtype = jnp.float32 if A.dtype in (jnp.bfloat16, jnp.float16) else A.dtype

    bm = min(block_m, max(8, m))
    bd = min(block_d, max(8, d))
    bn = min(block_n, max(128, n)) if n >= 128 else 128

    # A is not padded: a partial last tile is masked in the kernel.  Only
    # inputs narrower than one tile (vectors, m < 8) are padded.
    A_p = pad_to(A, (bm if m < bm else 1, 128 if n < 128 else 1))
    m_p, n_p = A_p.shape
    # Padded rows get sign 0 -> contribute nothing (bucket 0 is fine).
    h_p = pad_to(buckets.astype(jnp.int32)[None, :], (1, bm))
    s_p = pad_to(signs.astype(jnp.float32)[None, :], (1, bm))
    d_p = cdiv(d, bd) * bd
    kernel = partial(countsketch_kernel, m=m_p if m_p % bm else None)

    grid = (cdiv(n_p, bn), d_p // bd, cdiv(m_p, bm))
    with mosaic_context(interpret):
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm), lambda ni, di, mi: (0, mi)),
                pl.BlockSpec((1, bm), lambda ni, di, mi: (0, mi)),
                pl.BlockSpec((bm, bn), lambda ni, di, mi: (mi, ni)),
            ],
            out_specs=pl.BlockSpec((bd, bn), lambda ni, di, mi: (di, ni)),
            out_shape=jax.ShapeDtypeStruct((d_p, n_p), acc_dtype),
            interpret=interpret,
            # The kernel's name in a profile, whatever wraps this call.
            name="countsketch_apply",
        )(h_p, s_p, A_p)
    # half-precision inputs keep the f32 accumulator dtype (mixed-precision
    # contract: bf16 data, >= f32 sketch output for the QR/refinement stages)
    out = out[:d, :n]
    return out[:, 0] if vec else out
