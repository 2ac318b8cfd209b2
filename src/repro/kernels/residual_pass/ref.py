"""Pure-jnp oracle for the one-pass residual kernel: the two products."""
from __future__ import annotations

import jax

from ..common import matmul, vdot

__all__ = ["residual_pass_ref"]


def residual_pass_ref(A: jax.Array, x: jax.Array, b: jax.Array):
    """(Aᵀ(b − A x), ‖b − A x‖²), as the kernel returns them."""
    r = b - matmul(A, x)
    return matmul(A.T, r), vdot(r, r)
