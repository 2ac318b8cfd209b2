"""jnp oracles for the TSQR package — same contracts, no Pallas."""
from __future__ import annotations

import jax.numpy as jnp

from ..common import matmul


def panel_gram_ref(B: jnp.ndarray) -> jnp.ndarray:
    acc = jnp.float32 if B.dtype in (jnp.bfloat16, jnp.float16) else B.dtype
    Bf = B.astype(acc)
    return matmul(Bf.T, Bf)


def tsqr_ref(B: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Householder QR with the package's diag(R) ≥ 0 sign convention."""
    Q, R = jnp.linalg.qr(B, mode="reduced")
    sgn = jnp.where(jnp.diag(R) < 0, -1.0, 1.0).astype(R.dtype)
    return Q * sgn[None, :], R * sgn[:, None]
