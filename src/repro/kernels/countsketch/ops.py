"""jit'd wrapper for the CountSketch Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv, mosaic_context, pad_to, resolve_interpret
from .kernel import countsketch_kernel

__all__ = ["countsketch_apply"]

# Rows per tile.  The buckets and signs of a tile are an SMEM block, and a
# 1-D 32-bit array is laid out in tiles of 1024 elements.
_BLOCK_M = 1024
# VMEM for the resident output: both buffers of the output block and the
# extra accumulators.  fig3.fresh's d = 4000, n = 1000 in float32 takes
# 15.6 MiB a copy; serving's d = 8000 at n = 1000 takes 31 MiB.
_OUT_VMEM = 96 << 20


def _lanes(n: int) -> int:
    return cdiv(n, 128) * 128


def stored_by_columns(m: int, n: int) -> bool:
    """Whether XLA lays an (m, n) array out column by column on a TPU.

    Of row-major and column-major (8, 128) tilings it takes the one that
    pads less, rows on a tie: f32[2^20, 1000] and every vector are stored by
    columns, f32[2^20, 1024] and f32[4000, 1000] by rows (checked against
    compiles for a described v5e).  A kernel operand that asks for the other
    order costs a relayout copy of A.
    """
    by_rows = cdiv(m, 8) * 8 * _lanes(n)
    by_cols = cdiv(n, 8) * 8 * _lanes(m)
    return by_cols < by_rows


def _blocks(d: int, n: int, acc_bytes: int) -> tuple[int, int, int]:
    """(bd, bn, copies): every bucket row at the full width where they
    fit, else the widest multiple of 128 columns that fits, else d-blocks;
    then as many accumulators as fit beside the output's two buffers, up
    to 4 (a power of two: the rows of an unrolled group of 8 rotate over
    them, see ``countsketch_kernel``)."""
    d_p = cdiv(d, 8) * 8
    if 2 * d_p * _lanes(n) * acc_bytes <= _OUT_VMEM:
        bd, bn = d_p, n
    elif (lanes := _OUT_VMEM // (2 * d_p * acc_bytes) // 128 * 128) >= 128:
        bd, bn = d_p, lanes
    else:
        bd, bn = _OUT_VMEM // (2 * 128 * acc_bytes) // 8 * 8, 128
    fit = _OUT_VMEM // (bd * _lanes(bn) * acc_bytes) - 1
    return bd, bn, next(c for c in (4, 2, 1) if c <= max(fit, 1))


@partial(jax.jit, static_argnames=("d", "interpret"))
def countsketch_apply(
    A: jax.Array,
    buckets: jax.Array,
    signs: jax.Array,
    d: int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """SA for the CountSketch (buckets, signs); A is (m, n) or (m,).

    ``buckets`` must lie in [0, d): the kernel adds each row at its
    bucket's offset unchecked.  Returns (d, n) in the accumulation dtype:
    float32 for a half-precision A, else A's dtype.  ``interpret=None``
    resolves via ``repro.core.backend.default_interpret`` (real Mosaic on
    TPU, interpret mode elsewhere).  The blocks follow from the shape alone
    (``_blocks``).
    """
    interpret = resolve_interpret(interpret, A)
    vec = A.ndim == 1
    if vec:
        A = A[:, None]
    m, n = A.shape
    half = A.dtype in (jnp.bfloat16, jnp.float16)
    acc_dtype = jnp.float32 if half else A.dtype

    tm = min(_BLOCK_M, m)  # a short A is one whole tile
    # Padded entries are never read: the kernel visits rows below m only.
    h_p = pad_to(buckets.astype(jnp.int32), (_BLOCK_M,))
    s_p = pad_to(signs.astype(jnp.float32), (_BLOCK_M,))
    acc_bytes = jnp.dtype(acc_dtype).itemsize
    # The kernel reads A in the order XLA stores it: Aᵀ's rows are A's
    # columns, and each tile is transposed in VMEM.  A narrow A stored by
    # rows is widened to one lane tile.
    transposed = stored_by_columns(m, n)
    A_p = A.T if transposed else pad_to(A, (1, 128 if n < 128 else 1))
    n_p = A_p.shape[0 if transposed else 1]
    bd, bn, copies = _blocks(d, n_p, acc_bytes)
    d_p = cdiv(d, bd) * bd
    staged = half or transposed
    kernel = partial(countsketch_kernel, m=m if m % tm else None,
                     d_blocked=bd < d_p, staged=staged, transposed=transposed)
    scratch = [pltpu.VMEM((tm, bn), acc_dtype)] * staged
    scratch += [pltpu.VMEM((bd, bn), acc_dtype)] * (copies - 1)
    vmem = ((copies + 1) * bd + staged * tm) * _lanes(bn) * acc_bytes
    vmem += 2 * tm * _lanes(bn) * A.dtype.itemsize  # A's tile, two buffers
    smem = partial(pl.BlockSpec, (_BLOCK_M,), lambda ni, di, mi: (mi,),
                   memory_space=pltpu.SMEM)
    a_spec = (pl.BlockSpec((bn, tm), lambda ni, di, mi: (ni, mi)) if transposed
              else pl.BlockSpec((tm, bn), lambda ni, di, mi: (mi, ni)))
    with mosaic_context(interpret):
        out = pl.pallas_call(
            kernel,
            grid=(cdiv(n_p, bn), d_p // bd, cdiv(m, tm)),
            in_specs=[smem(), smem(), a_spec],
            out_specs=pl.BlockSpec((bd, bn), lambda ni, di, mi: (di, ni)),
            out_shape=jax.ShapeDtypeStruct((d_p, n_p), acc_dtype),
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem + (8 << 20)),
            interpret=interpret,
            # The kernel's name in a profile, whatever wraps this call.
            name="countsketch_apply",
        )(h_p, s_p, A_p)
    # half-precision inputs keep the f32 accumulator dtype (mixed-precision
    # contract: bf16 data, >= f32 sketch output for the QR/refinement stages)
    out = out[:d, :n]
    return out[:, 0] if vec else out
