"""Ill-conditioned least-squares problem generator (paper §5.1, after [1]).

  A = U₁ Σ Vᵀ with Haar U₁ ∈ R^{m×n}, Haar V ∈ R^{n×n},
  Σ log-equispaced in [1, 1/κ];  x = w/‖w‖;  r ⟂ range(A), ‖r‖ = β;
  b = A x + r.   Then x is exactly argmin‖Ax−b‖ with residual norm β.

``method='haar'`` draws U₁ via QR of a Gaussian (exact Haar on the Stiefel
manifold; O(mn²)).  ``method='fast'`` skips the orthonormalization of the
left factor (Gaussian G in place of U₁) — condition number is then κ up to a
Marchenko–Pastur factor ≈ (1+√(n/m))/(1−√(n/m)) ≈ 1 for m ≫ n; used for the
large runtime sweeps where the QR itself would dominate generation time.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels.common import matmul

__all__ = ["generate", "Problem"]


class Problem(NamedTuple):
    A: jax.Array
    b: jax.Array
    x_true: jax.Array
    r_true: jax.Array
    cond: float
    beta: float


@partial(jax.jit, static_argnames=("m", "n", "dtype", "method"))
def generate(
    key: jax.Array,
    m: int,
    n: int,
    *,
    cond: float = 1e10,
    beta: float = 1e-10,
    dtype=jnp.float64,
    method: str = "haar",
) -> Problem:
    if not m > n:
        raise ValueError(f"overdetermined problems need m > n, got {m}x{n}")
    k_u, k_v, k_w, k_z = jax.random.split(key, 4)

    G1 = jax.random.normal(k_u, (m, n), dtype)
    if method == "haar":
        U1, _ = jnp.linalg.qr(G1, mode="reduced")
    elif method == "fast":
        U1 = G1 / jnp.sqrt(jnp.asarray(m, dtype))  # ≈ orthonormal columns
    else:
        raise ValueError(f"unknown method {method!r}")

    V, _ = jnp.linalg.qr(jax.random.normal(k_v, (n, n), dtype), mode="reduced")
    log_k = jnp.log10(jnp.asarray(cond, dtype))
    sigma = jnp.logspace(0.0, -log_k, n, dtype=dtype)
    A = matmul(U1 * sigma, V.T)

    w = jax.random.normal(k_w, (n,), dtype)
    x = w / jnp.linalg.norm(w)

    # r = β · (component of a Gaussian orthogonal to range(A)).  For
    # method='haar', range(A) = range(U1) exactly so the projection makes x
    # the exact minimizer.  For 'fast' (runtime sweeps only, where x_true is
    # not consumed) we skip the O(mn²) projection: r is just a scaled
    # Gaussian and x_true is the minimizer only up to O(β).
    g = jax.random.normal(k_z, (m,), dtype)
    if method == "haar":
        v = g - matmul(U1, matmul(U1.T, g))
    else:
        v = g
    r = beta * v / jnp.linalg.norm(v)

    b = matmul(A, x) + r
    return Problem(A=A, b=b, x_true=x, r_true=r, cond=cond, beta=beta)
