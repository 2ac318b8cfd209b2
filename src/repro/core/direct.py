"""Deterministic direct least-squares solvers (ground truth for tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ..kernels.common import matmul

__all__ = ["qr_solve", "svd_solve", "normal_equations"]


@jax.jit
def qr_solve(A: jax.Array, b: jax.Array) -> jax.Array:
    """x = R⁻¹ Qᵀ b via reduced Householder QR of A."""
    Q, R = jnp.linalg.qr(A, mode="reduced")
    return solve_triangular(R, matmul(Q.T, b), lower=False)


@jax.jit
def svd_solve(A: jax.Array, b: jax.Array, rcond: float | None = None) -> jax.Array:
    """Minimum-norm LS solution via SVD (most robust, most expensive)."""
    x, *_ = jnp.linalg.lstsq(A, b, rcond=rcond)
    return x


@jax.jit
def normal_equations(A: jax.Array, b: jax.Array) -> jax.Array:
    """Cholesky on AᵀA — fast, squares the condition number (for comparison)."""
    G = matmul(A.T, A)
    return jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(G), matmul(A.T, b))
