"""One pass over A for a least-squares iteration: r = b − A x, Aᵀr, ‖r‖².

The heavy-ball refinement needs g = Aᵀ(b − A x) and ‖b − A x‖ every
iteration.  As two XLA products that is two reads of A; both are row-local,
so a kernel that holds a tile of A's rows in VMEM takes the tile's rows of
r first and then the tile's share of g, and never reads the tile again.

Both products are float32 multiply-adds on the VPU (no MXU pass, so
nothing is rounded to bfloat16).  Each grid step writes its own partial of
g and of ‖r‖² into one slot of a small output; the caller sums the slots,
so no float32 accumulator runs over all m rows.

A is read in the order XLA stores it (``countsketch.ops.stored_by_columns``):

- by columns, the kernel takes (n, tm) tiles of Aᵀ (A's rows along lanes).
  r is a reduction over sublanes (x broadcast along lanes), then Aᵀr a
  reduction over lanes (r broadcast along sublanes);
- by rows, it takes (tm, n) tiles of A: the mirror image, eight rows at a
  time, the g partial accumulated over sublanes in VMEM.

Rows at or beyond m in a partial last tile hold whatever the buffer held,
so they are selected away, not multiplied by zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SUB = 8  # sublanes of a 32-bit vreg
# Row chunks per iteration of the kernel's loops.
_UNROLL = 2
# Partials per output block: lanes of a row (by columns), sublanes of a
# column (by rows).
SLOTS_BY_COLUMNS, SLOTS_BY_ROWS = 128, _SUB


def _slot_mask(shape, axis, slot):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis) == slot


def _walk(count: int, fn, carry):
    """carry = fn(c, carry) for c in [0, count), ``_UNROLL`` chunks to an
    iteration of the loop (Mosaic unrolls a loop wholly or not at all)."""
    groups = count // _UNROLL

    def group(j, carry):
        for k in range(_UNROLL):
            carry = fn(j * _UNROLL + k, carry)
        return carry

    carry = jax.lax.fori_loop(0, groups, group, carry)
    return jax.lax.fori_loop(groups * _UNROLL, count, fn, carry)


def _lane_sum(p):
    """Sum of a (rows, lanes) value over its lanes, as (rows, 1): halves
    are added vreg by vreg while the width is a multiple of 256 (a tree of
    adds, not a chain), then one cross-lane reduce."""
    while p.shape[1] % 256 == 0:
        half = p.shape[1] // 2
        p = p[:, :half] + p[:, half:]
    return jnp.sum(p, axis=1, keepdims=True)


def _spans(size: int, step: int):
    """The whole chunks of ``step`` in ``size``, walked by a loop, and the
    (start, length) of what is left over, if anything, taken statically."""
    full, tail = divmod(size, step)
    return full, ((full * step, tail),) if tail else ()


def _by_columns(x_ref, b_ref, a_ref, g_ref, r_ref, *, lanes, valid, slot):
    """Tile (n, tm) of Aᵀ.  Returns this tile's ‖r‖² as (1, 1); adds its
    g partial, an (n, 1) column, into lane ``slot`` of ``g_ref``."""
    n, tm = a_ref.shape
    blocks = tm // lanes
    full, tail = _spans(n, _SUB)

    def lane_ok(k):
        lane = k * lanes + jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
        return lane < valid

    # r = b - sum over sublanes of (tile * x), one block of lanes at a time.
    rsq = jnp.zeros((1, 1), jnp.float32)
    for k in range(blocks):
        cols = pl.ds(k * lanes, lanes)

        def rows(c, acc, cols=cols):
            at = pl.ds(pl.multiple_of(c * _SUB, _SUB), _SUB)
            return acc + a_ref[at, cols] * x_ref[at, :]

        acc = _walk(full, rows, jnp.zeros((_SUB, lanes), jnp.float32))
        s = jnp.sum(acc, axis=0, keepdims=True)
        for start, size in tail:
            at = pl.ds(start, size)
            s += jnp.sum(a_ref[at, cols] * x_ref[at, :], axis=0, keepdims=True)
        r = b_ref[:, cols] - s
        if valid is not None:
            r = jnp.where(lane_ok(k), r, 0.0)
        # kept on every sublane, so that the g loop loads it as it is used
        r_ref[:, cols] = jnp.broadcast_to(r, (_SUB, lanes))
        rsq += jnp.sum(r * r, axis=1, keepdims=True)

    # g = sum over lanes of (tile * r), eight of A's columns at a time.
    def g_rows(at, size):
        p = None
        for k in range(blocks):
            cols = pl.ds(k * lanes, lanes)
            t = a_ref[at, cols]
            if valid is not None:
                t = jnp.where(lane_ok(k), t, 0.0)
            tr = t * r_ref[pl.ds(0, size), cols]
            p = tr if p is None else p + tr
        g = _lane_sum(p)
        g_ref[at, :] += jnp.where(_slot_mask((size, 128), 1, slot), g, 0.0)

    def chunk(c, carry):
        g_rows(pl.ds(pl.multiple_of(c * _SUB, _SUB), _SUB), _SUB)
        return carry

    _walk(full, chunk, 0)
    for start, size in tail:
        g_rows(pl.ds(start, size), size)
    return rsq


def _by_rows(x_ref, b_ref, a_ref, g_ref, bcol_ref, acc_ref, *, lanes, valid,
             slot):
    """Tile (tm, n) of A.  Returns this tile's ‖r‖² as (1, 1); adds its g
    partial, a (1, n) row, into sublane ``slot`` of ``g_ref``."""
    tm, n = a_ref.shape
    full, tail = _spans(tm, _SUB)
    col_blocks = [(k * lanes, min(lanes, n - k * lanes))
                  for k in range(-(-n // lanes))]
    bcol_ref[...] = b_ref[...].T  # b arrives as a row; r is a column here
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def rows(at, start, size):
        tiles = []
        s = jnp.zeros((size, 1), jnp.float32)
        for lo, width in col_blocks:
            t = a_ref[at, pl.ds(lo, width)]
            if valid is not None:
                row = start + jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)
                t = jnp.where(row < valid, t, 0.0)
            tiles.append(t)
            s += _lane_sum(t * x_ref[:, pl.ds(lo, width)])
        r = bcol_ref[at, :] - s
        if valid is not None:
            row = start + jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)
            r = jnp.where(row < valid, r, 0.0)
        for (lo, width), t in zip(col_blocks, tiles):
            cols = pl.ds(lo, width)
            acc_ref[pl.ds(0, size), cols] += t * r
        return jnp.sum(r * r, axis=0, keepdims=True)

    def chunk(c, rsq):
        start = pl.multiple_of(c * _SUB, _SUB)
        return rsq + rows(pl.ds(start, _SUB), start, _SUB)

    rsq = _walk(full, chunk, jnp.zeros((1, 1), jnp.float32))
    for start, size in tail:
        rsq += rows(pl.ds(start, size), start, size)
    g = jnp.sum(acc_ref[...], axis=0, keepdims=True)
    g_ref[...] += jnp.where(_slot_mask(g_ref.shape, 0, slot), g, 0.0)
    return rsq


def residual_pass_kernel(x_ref, b_ref, a_ref, g_ref, rsq_ref, *scratch,
                         m=None, lanes, transposed):
    """Grid: (row tiles,).

    ``x_ref`` is x as a column (n, 1) where ``transposed``, else as a row
    (1, n); ``b_ref`` the tile's (1, tm) slice of b as a row; ``a_ref`` the
    tile of Aᵀ (n, tm) or of A (tm, n).  ``g_ref`` is the output block of
    g partials, (n, 128) or (8, n), and ``rsq_ref`` the (1, 128) block of
    ‖r‖² partials: grid step i writes slot i mod (slots of the block), and
    the first step of a block zeroes it.  ``m`` is the row count when the
    last tile is partial (else None); ``lanes`` the width of the lane blocks
    the tile is walked in.
    """
    i = pl.program_id(0)
    slots = SLOTS_BY_COLUMNS if transposed else SLOTS_BY_ROWS
    tm = a_ref.shape[1 if transposed else 0]

    @pl.when(i % slots == 0)
    def _zero_g():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(i % SLOTS_BY_COLUMNS == 0)
    def _zero_rsq():
        rsq_ref[...] = jnp.zeros_like(rsq_ref)

    body = _by_columns if transposed else _by_rows

    def run(valid):
        rsq = body(x_ref, b_ref, a_ref, g_ref, *scratch, lanes=lanes,
                   valid=valid, slot=i % slots)
        rsq_ref[...] += jnp.where(
            _slot_mask(rsq_ref.shape, 1, i % SLOTS_BY_COLUMNS), rsq, 0.0)

    if m is None:
        run(None)
    else:
        last = pl.num_programs(0) - 1
        pl.when(i < last)(lambda: run(None))
        pl.when(i == last)(lambda: run(m - last * tm))
