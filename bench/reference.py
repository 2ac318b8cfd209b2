"""The plain reference that decides ``correct``, and its bf16 control.

Householder QR of A by row blocks (TSQR: the R of each block of rows, then
the R of the stacked Rs, then across chips), then the semi-normal equations
R^T R x = A^T b corrected by a fixed number of refinement sweeps
x <- x + (R^T R)^{-1} A^T (b - A x).  With R the triangular factor of A
itself, A R^{-1} has singular values within about cond * eps32 of 1, so each
sweep shrinks the error by some 1e-3 and a few sweeps reach the f32 floor
of the residual.  It is written in plain ``jax.numpy`` at HIGHEST matmul
precision and imports nothing of the program.

``control=True`` is the same computation with A and b held in bfloat16 (the
products accumulate in f32; the small n x n work stays f32): the reference
one precision step down, which the comparison must refuse.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "ref_rows"


def _block_rows(rows: int, n: int) -> int:
    """Largest power-of-two block of at most 2^16 rows (and >= 2n) that
    divides ``rows``; ``rows`` itself when none does."""
    block = 1 << 16
    while block >= 2 * n:
        if rows % block == 0:
            return block
        block //= 2
    return rows


def _local_solve(A, B, *, sweeps):
    """This chip's rows of A and B; every pass over them goes by blocks of
    rows, so the temporaries stay a block's size, not A's."""
    rows, n = A.shape
    block = _block_rows(rows, n)
    blocks = rows // block
    work = A.dtype

    def mm(x, y):
        return jnp.matmul(x, y, precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    def rows_of(X, i):
        return lax.dynamic_slice_in_dim(X, i * block, block, axis=0)

    def block_r(i):
        return jnp.linalg.qr(rows_of(A, i).astype(jnp.float32), mode="r")

    Rs = lax.map(block_r, jnp.arange(blocks))
    R_local = jnp.linalg.qr(Rs.reshape(-1, n), mode="r")
    R_all = lax.all_gather(R_local, AXIS)
    R = jnp.linalg.qr(R_all.reshape(-1, n), mode="r")

    def normal_solve(G):
        Y = solve_triangular(R, G, trans="T", lower=False)
        return solve_triangular(R, Y, lower=False)

    def gradient(X):
        """A^T (B - A X), summed over the blocks and the chips."""
        Xw = None if X is None else X.astype(work)

        def add_block(i, G):
            a, b = rows_of(A, i), rows_of(B, i).astype(jnp.float32)
            resid = b if Xw is None else b - mm(a, Xw)
            return G + mm(a.T, resid.astype(work))

        G = lax.fori_loop(0, blocks, add_block,
                          jnp.zeros((n, B.shape[1]), jnp.float32))
        return lax.psum(G, AXIS)

    X = normal_solve(gradient(None))
    for _ in range(sweeps):
        X = X + normal_solve(gradient(X))
    return X


@partial(jax.jit, static_argnames=("mesh", "sweeps"))
def _solve(A, B, *, mesh, sweeps):
    with jax.default_matmul_precision("highest"):
        return jax.shard_map(
            partial(_local_solve, sweeps=sweeps),
            mesh=mesh, in_specs=(P(AXIS, None), P(AXIS, None)),
            out_specs=P(), check_vma=False,
        )(A, B)


def solve(A, bs, devices, *, sweeps: int = 6, control: bool = False):
    """x_j = argmin |A x - b_j| for every b_j of ``bs``, as an (n x len(bs))
    host array.  A's rows may lie on one device or be sharded over
    ``devices`` in order; the columns of B are stacked here."""
    import numpy as np

    mesh = Mesh(np.array(devices), (AXIS,))
    rows = NamedSharding(mesh, P(AXIS, None))
    A = jax.device_put(A, rows)
    B = jax.device_put(_stack(bs), rows)
    if control:  # the data held one step down: bfloat16
        A, B = _to_bf16(A), _to_bf16(B)
    X = _solve(A, B, mesh=mesh, sweeps=sweeps)
    return np.asarray(jax.device_get(X), dtype=np.float64)


@jax.jit
def _to_bf16(x):
    return x.astype(jnp.bfloat16)


@jax.jit
def _stack_cols(bs):
    return jnp.stack(bs, axis=1)


def _stack(bs):
    return _stack_cols(tuple(bs))
