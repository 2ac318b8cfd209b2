"""jit'd wrappers: blocked Hadamard transform + full SRHT apply."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import cdiv, mosaic_context, pad_to, resolve_interpret
from .kernel import hadamard_axis_kernel

__all__ = ["hadamard_transform", "srht_apply", "hadamard_matrix"]


def hadamard_matrix(k: int, dtype=jnp.float32) -> jax.Array:
    """Sylvester Hadamard H_k (k a power of two) via parity of popcount(i&j)."""
    i = jnp.arange(k, dtype=jnp.uint32)
    par = jnp.bitwise_count(i[:, None] & i[None, :]) & 1
    return (1 - 2 * par.astype(jnp.int32)).astype(dtype)


# Largest Hadamard factor one stage multiplies by: H_c stays resident in
# VMEM (double-buffered), so c = 1024 costs 8 MiB in f32.
_MAX_FACTOR_BITS = 10
# Scoped-VMEM budget of one stage (the v5e limit is 16 MiB): the resident
# H_c, double-buffered input and output tiles, and the f32 dot result.
_VMEM_BUDGET = 12 * 1024 * 1024


def _factor_bits(m: int) -> tuple[int, ...]:
    """log2 of the Kronecker factors of H_m: as few stages as the 2^10 cap
    allows, with the bits spread evenly (fewer MACs than one big factor)."""
    p = m.bit_length() - 1
    k = -(-p // _MAX_FACTOR_BITS)
    return tuple(p // k + (i < p % k) for i in range(k))


def _lane_block(c: int, b: int, itemsize: int, cap: int) -> int:
    """Column tile of one stage: the whole b when it fits, else the largest
    multiple of 128 (≤ cap) whose working set fits ``_VMEM_BUDGET``."""
    resident = 2 * c * c * itemsize
    per_col = c * (4 * itemsize + 4)
    fit = (_VMEM_BUDGET - resident) // per_col // 128 * 128
    bk = max(128, min(cap // 128 * 128, fit))
    if resident + per_col * bk > _VMEM_BUDGET:
        raise ValueError(
            f"Hadamard factor {c} does not fit the VMEM budget in "
            f"{itemsize}-byte elements"
        )
    return b if b <= bk else bk


def _hadamard_stage(x3, h, block_n, interpret, alias):
    """H_c along axis 1 of the (a, c, b) array x3."""
    a, c, b = x3.shape
    # float64 only runs in interpret mode; tile it as f32 would be on the chip.
    bk = _lane_block(c, b, min(x3.dtype.itemsize, 4), block_n)
    with mosaic_context(interpret):
        return pl.pallas_call(
            hadamard_axis_kernel,
            grid=(a, cdiv(b, bk)),
            in_specs=[
                pl.BlockSpec((c, c), lambda i, j: (0, 0)),
                pl.BlockSpec((1, c, bk), lambda i, j: (i, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, c, bk), lambda i, j: (i, 0, j)),
            out_shape=jax.ShapeDtypeStruct(x3.shape, x3.dtype),
            # Each tile is read and written in place, so an intermediate
            # stage's buffer can be reused for its output.
            input_output_aliases={1: 0} if alias else {},
            interpret=interpret,
        )(h, x3)


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def hadamard_transform(
    x: jax.Array, *, block_n: int = 512, interpret: bool | None = None
) -> jax.Array:
    """Unnormalized Walsh–Hadamard transform along axis 0 (m a power of 2).

    Half-precision inputs accumulate in f32 and are rounded back after each
    stage.  ``block_n`` caps the column tile; it shrinks further to fit VMEM.
    ``interpret=None`` resolves via ``repro.core.backend.default_interpret``.
    """
    interpret = resolve_interpret(interpret, x)
    vec = x.ndim == 1
    if vec:
        x = x[:, None]
    m, n = x.shape
    if m & (m - 1):
        raise ValueError(f"m must be a power of two, got {m}")
    # Columns are independent, so a partial last column tile needs no
    # padding; only narrow inputs are widened to one full lane tile.
    y = pad_to(x, (1, 128)) if n < 128 else x
    n_p = y.shape[1]
    rest = m * n_p
    before = 1
    for i, bits in enumerate(_factor_bits(m)):
        c = 1 << bits
        rest //= c
        h = hadamard_matrix(c, y.dtype)
        y = _hadamard_stage(
            y.reshape(before, c, rest), h, block_n, interpret, alias=i > 0
        )
        before *= c
    out = y.reshape(m, n_p)[:, :n]
    return out[:, 0] if vec else out


@partial(jax.jit, static_argnames=("d", "block_n", "interpret"))
def srht_apply(
    A: jax.Array,
    signs: jax.Array,
    rows: jax.Array,
    d: int,
    *,
    block_n: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """SRHT sketch S·A = (1/√d) · P · H · D · A.

    ``signs`` has length m_pad (power of two ≥ m); ``rows`` are d sampled
    row indices.  The Hadamard transform runs in the Pallas kernels; the
    D-scaling and P-gather stay in XLA (memory-bound, fusable).
    ``interpret=None`` resolves via ``repro.core.backend.default_interpret``.
    """
    interpret = resolve_interpret(interpret, A)
    vec = A.ndim == 1
    A2 = A[:, None] if vec else A
    m, n = A2.shape
    m_pad = signs.shape[0]
    if m_pad != m:
        A2 = jnp.pad(A2, ((0, m_pad - m), (0, 0)))
    HDx = hadamard_transform(
        signs[:, None].astype(A2.dtype) * A2, block_n=block_n, interpret=interpret
    )
    out = HDx[rows] / jnp.sqrt(jnp.asarray(d, A2.dtype))
    return out[:, 0] if vec else out
