"""Pure-jnp oracles for the dense-sketch kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import bits_to_gaussian, key_to_u32, matmul, threefry2x32

__all__ = [
    "sketch_matmul_ref",
    "gaussian_matrix_ref",
    "gaussian_cols_ref",
    "fused_gaussian_ref",
]


def sketch_matmul_ref(S: jax.Array, A: jax.Array) -> jax.Array:
    return matmul(S, A)


def gaussian_matrix_ref(
    key: jax.Array, d: int, m: int, dtype=jnp.float32, *, col_offset=0
):
    """The exact S the fused kernel generates (same counters, same bits).

    ``col_offset`` shifts the column counters: element (i, j) of the result
    is generated from counter pair (i, col_offset + j), so
    ``gaussian_matrix_ref(key, d, t, col_offset=o)`` is bitwise identical to
    ``gaussian_matrix_ref(key, d, m)[:, o:o+t]`` — the streaming sketch
    engine regenerates per-tile column blocks of S from ``key`` alone
    without ever materializing the full (d, m) matrix.
    """
    return gaussian_cols_ref(
        key, d, col_offset + jnp.arange(m, dtype=jnp.uint32), dtype
    )


def gaussian_cols_ref(key: jax.Array, d: int, cols: jax.Array, dtype=jnp.float32):
    """Arbitrary column subset S[:, cols] of the fused kernel's matrix.

    Counter-based generation makes column gather free: the (d, len(cols))
    block is drawn directly from the (row, cols[j]) counters, bit-identical
    to slicing the fully materialized S.
    """
    cols = jnp.asarray(cols, jnp.uint32)
    (t,) = cols.shape
    k0, k1 = key_to_u32(key)
    rows = jnp.broadcast_to(jnp.arange(d, dtype=jnp.uint32)[:, None], (d, t))
    cols = jnp.broadcast_to(cols[None, :], (d, t))
    b0, b1 = threefry2x32(k0, k1, rows, cols)
    return bits_to_gaussian(b0, b1, jnp.float32).astype(dtype)


def fused_gaussian_ref(A: jax.Array, key: jax.Array, d: int, scale=None):
    vec = A.ndim == 1
    A2 = A[:, None] if vec else A
    m = A2.shape[0]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    S = gaussian_matrix_ref(key, d, m, A2.dtype) * jnp.asarray(scale, A2.dtype)
    out = matmul(S, A2)
    return out[:, 0] if vec else out
