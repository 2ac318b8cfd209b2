"""jit'd wrappers for the dense-sketch kernels."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import cdiv, key_to_u32, mosaic_context, pad_to, resolve_interpret
from .kernel import fused_gaussian_kernel, matmul_kernel

__all__ = ["sketch_matmul", "fused_gaussian_sketch"]


@partial(
    jax.jit, static_argnames=("block_d", "block_m", "block_n", "interpret")
)
def sketch_matmul(
    S: jax.Array,
    A: jax.Array,
    *,
    block_d: int = 256,
    block_m: int = 512,
    block_n: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """S (d, m) @ A (m, n) with VMEM-tiled accumulation.

    ``interpret=None`` resolves via ``repro.core.backend.default_interpret``.
    """
    interpret = resolve_interpret(interpret, S, A)
    vec = A.ndim == 1
    A2 = A[:, None] if vec else A
    d, m = S.shape
    n = A2.shape[1]
    acc = jnp.float32 if A2.dtype in (jnp.bfloat16, jnp.float16) else A2.dtype

    bd = min(block_d, max(8, d))
    bm = min(block_m, max(8, m))
    bn = min(block_n, max(128, n)) if n >= 128 else 128

    S_p = pad_to(S, (bd, bm))
    A_p = pad_to(A2, (bm, bn))
    d_p, m_p = S_p.shape
    n_p = A_p.shape[1]

    with mosaic_context(interpret):
        out = pl.pallas_call(
            matmul_kernel,
            grid=(d_p // bd, n_p // bn, m_p // bm),
            in_specs=[
                pl.BlockSpec((bd, bm), lambda di, ni, mi: (di, mi)),
                pl.BlockSpec((bm, bn), lambda di, ni, mi: (mi, ni)),
            ],
            out_specs=pl.BlockSpec((bd, bn), lambda di, ni, mi: (di, ni)),
            out_shape=jax.ShapeDtypeStruct((d_p, n_p), acc),
            interpret=interpret,
        )(S_p, A_p)
    # half-precision inputs keep the f32 accumulator dtype (mixed-precision
    # contract: bf16 data, >= f32 sketch output for the QR/refinement stages)
    out = out[:d, :n]
    return out[:, 0] if vec else out


@partial(
    jax.jit,
    static_argnames=("d", "block_d", "block_m", "block_n", "interpret"),
)
def fused_gaussian_sketch(
    A: jax.Array,
    key: jax.Array,
    d: int,
    *,
    scale: float | None = None,
    block_d: int = 256,
    block_m: int = 512,
    block_n: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(1/√d)·G·A with G ~ N(0,1)^{d×m} generated inside the kernel.

    G is never materialized in HBM.  Bitwise-reproducible from ``key`` (see
    ref.py for the matching oracle — ``repro.core.sketch.GaussianSketch``
    draws its S from the same stream, so this kernel IS its pallas backend).
    ``interpret=None`` resolves via ``repro.core.backend.default_interpret``.
    """
    interpret = resolve_interpret(interpret, A)
    vec = A.ndim == 1
    A2 = A[:, None] if vec else A
    m, n = A2.shape
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    acc = jnp.float32 if A2.dtype in (jnp.bfloat16, jnp.float16) else A2.dtype

    bd = min(block_d, max(8, d))
    bm = min(block_m, max(8, m))
    bn = min(block_n, max(128, n)) if n >= 128 else 128

    # NOTE: rows beyond m would multiply garbage Gaussians into padded-zero
    # rows of A — padding A with zeros makes those contributions vanish.
    A_p = pad_to(A2, (bm, bn))
    m_p, n_p = A_p.shape
    d_p = cdiv(d, bd) * bd

    k0, k1 = key_to_u32(key)
    k0 = k0.reshape(1, 1)
    k1 = k1.reshape(1, 1)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1, 1)

    with mosaic_context(interpret):
        out = pl.pallas_call(
            fused_gaussian_kernel,
            grid=(d_p // bd, n_p // bn, m_p // bm),
            in_specs=[
                pl.BlockSpec((1, 1), lambda di, ni, mi: (0, 0)),
                pl.BlockSpec((1, 1), lambda di, ni, mi: (0, 0)),
                pl.BlockSpec((1, 1), lambda di, ni, mi: (0, 0)),
                pl.BlockSpec((bm, bn), lambda di, ni, mi: (mi, ni)),
            ],
            out_specs=pl.BlockSpec((bd, bn), lambda di, ni, mi: (di, ni)),
            out_shape=jax.ShapeDtypeStruct((d_p, n_p), acc),
            interpret=interpret,
        )(k0, k1, scale_arr, A_p)
    out = out[:d, :n]  # keep the f32 accumulator dtype for half inputs
    return out[:, 0] if vec else out
