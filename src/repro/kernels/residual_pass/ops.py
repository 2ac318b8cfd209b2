"""jit'd wrapper for the one-pass residual kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv, mosaic_context, resolve_interpret
from ..countsketch.ops import stored_by_columns
from .kernel import SLOTS_BY_COLUMNS, SLOTS_BY_ROWS, residual_pass_kernel

__all__ = ["residual_pass", "fits"]

# A tile of A is about this many bytes (two buffers of it stream through
# VMEM): at fig3 width, 1000 columns by 2048 rows.
_TILE_BYTES = 8 << 20
# The tile is walked in blocks of this many lanes (eight vregs a row of
# sublanes), and holds at most this many rows.
_LANES = 1024
_MAX_ROWS = 4096
# VMEM a tile's buffers may take; wider A takes the two XLA products.
_VMEM_CAP = 96 << 20


def _pad(x: int, to: int) -> int:
    return cdiv(x, to) * to


def _tile_rows(m: int, n: int, transposed: bool) -> int:
    """Rows of A per grid step, from the shape alone: about _TILE_BYTES of
    A, a whole number of lane blocks (by columns) or of lane tiles (by
    rows: b's block is a row of tm lanes), or all of a short A."""
    if transposed:
        step, row_bytes = _LANES, _pad(n, 8) * 4
    else:
        step, row_bytes = 128, _pad(n, 128) * 4
    tm = min(max(_TILE_BYTES // (row_bytes * step), 1) * step, _MAX_ROWS)
    return m if m <= tm else tm


def _vmem_bytes(m: int, n: int, transposed: bool) -> int:
    tm = _tile_rows(m, n, transposed)
    if transposed:
        a = _pad(n, 8) * _pad(tm, 128)
        small = 2 * _pad(n, 8) * 128 + 8 * _pad(tm, 128)  # x, g; r
    else:
        a = _pad(tm, 8) * _pad(n, 128)
        small = 3 * 8 * _pad(n, 128) + _pad(tm, 8) * 128  # x, g, acc; b
    b = 8 * _pad(tm, 128)
    return 4 * (2 * (a + b + small) + 8 * 128)


def fits(m: int, n: int) -> bool:
    """Whether a tile of an (m, n) A fits the kernel's VMEM."""
    return _vmem_bytes(m, n, stored_by_columns(m, n)) <= _VMEM_CAP


@partial(jax.jit, static_argnames=("interpret",))
def residual_pass(A: jax.Array, x: jax.Array, b: jax.Array, *,
                  interpret: bool | None = None):
    """(g, rsq) = (Aᵀ(b − A x), ‖b − A x‖²) from one read of A.

    ``A`` is (m, n) float32, ``x`` (n,) and ``b`` (m,) of the same dtype.
    The tiles follow from the shape alone (``_tile_rows``); ``fits`` says
    whether they fit VMEM.  ``interpret=None`` resolves via
    ``repro.core.backend.default_interpret`` (real Mosaic on TPU,
    interpret mode elsewhere).
    """
    interpret = resolve_interpret(interpret, A, x, b)
    m, n = A.shape
    if x.shape != (n,) or b.shape != (m,):
        raise ValueError(
            f"residual_pass: A {A.shape} needs x ({n},) and b ({m},), got "
            f"{x.shape} and {b.shape}")
    transposed = stored_by_columns(m, n)
    tm = _tile_rows(m, n, transposed)
    tiles = cdiv(m, tm)
    # Lane blocks: of a tile's rows by columns, of A's columns by rows.
    lanes = tm if transposed and tm % _LANES else _LANES
    kernel = partial(residual_pass_kernel, m=m if m % tm else None,
                     lanes=lanes, transposed=transposed)
    step = lambda i: (0, i)  # noqa: E731
    # b is read as a row: a vector is stored like one, so b[None] is free.
    b_spec = pl.BlockSpec((1, tm), step)
    rsq_spec = pl.BlockSpec((1, 128), lambda i: (0, i // SLOTS_BY_COLUMNS))
    rsq_shape = (1, _pad(tiles, SLOTS_BY_COLUMNS))
    if transposed:
        # XLA stores A by columns: its tiles are those of Aᵀ, read as stored.
        operand, x_in = A.T, x[:, None]
        x_spec = pl.BlockSpec((n, 1), lambda i: (0, 0))
        a_spec = pl.BlockSpec((n, tm), step)
        g_spec = pl.BlockSpec((n, 128), lambda i: (0, i // SLOTS_BY_COLUMNS))
        g_shape = (n, _pad(tiles, SLOTS_BY_COLUMNS))
        scratch = [pltpu.VMEM((8, tm), jnp.float32)]  # r, on every sublane
    else:
        operand, x_in = A, x[None, :]
        x_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
        a_spec = pl.BlockSpec((tm, n), lambda i: (i, 0))
        g_spec = pl.BlockSpec((8, n), lambda i: (i // SLOTS_BY_ROWS, 0))
        g_shape = (_pad(tiles, SLOTS_BY_ROWS), n)
        scratch = [pltpu.VMEM((tm, 1), jnp.float32),  # b as a column
                   pltpu.VMEM((8, n), jnp.float32)]  # g accumulator
    with mosaic_context(interpret):
        g_parts, rsq_parts = pl.pallas_call(
            kernel,
            grid=(tiles,),
            in_specs=[x_spec, b_spec, a_spec],
            out_specs=[g_spec, rsq_spec],
            out_shape=[jax.ShapeDtypeStruct(g_shape, A.dtype),
                       jax.ShapeDtypeStruct(rsq_shape, A.dtype)],
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vmem_bytes(m, n, transposed) + (8 << 20)),
            interpret=interpret,
            # The kernel's name in a profile, whatever wraps this call.
            name="residual_pass",
        )(x_in, b[None, :], operand)
    # One partial per tile, summed here by XLA's tree reduction.
    return g_parts.sum(axis=1 if transposed else 0), rsq_parts.sum()
