"""Tall-skinny QR: tree-Householder panels, CholeskyQR finisher, fused Gram.

Two TSQR modes, both returning B = QR with Q (s, n) orthonormal and R
(n, n) upper triangular with a non-negative diagonal (the deterministic
sign convention — ``jnp.linalg.qr`` is free to flip row signs, this
factorization is not):

- ``mode="tree"`` — blocked Householder panels + a binary-tree R-merge:
  each row panel is QR'd independently (vmapped Householder, stable at
  any κ), then the per-panel R factors merge pairwise up a binary tree.
  Only the R factors ever travel between levels; Q is recovered at the
  end as B·R⁻¹ plus one CholeskyQR correction round (κ(B·R⁻¹) ≈ 1, so
  the correction Cholesky is unconditionally safe).
- ``mode="cholqr"`` — shifted CholeskyQR3 (Fukaya et al. 2020): one Gram
  G = BᵀB (the Pallas ``panel_gram`` kernel, or the fused sketch→Gram
  kernels that never re-read B from HBM), a shifted Cholesky for R₁, and
  two correction rounds.  All GEMM-rate math — this is the fast path the
  fused ``sketch_qr`` pipeline uses; the shift keeps the first Cholesky
  positive definite up to κ(B) ≈ 1/√(c·ε) and the correction rounds
  restore full orthogonality (validated at κ = 1e10 in the tests).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.scipy.linalg import solve_triangular

from ..common import cdiv, matmul, mosaic_context, pad_to, resolve_interpret
from .kernel import panel_gram_kernel

__all__ = ["panel_gram", "cholqr_finish", "tsqr"]

# The fused kernels keep one (block_d, n_pad) B panel plus the
# (n_pad, n_pad) Gram resident in VMEM; beyond this column count the
# working set outgrows the budget and ``sketch_qr`` falls back to the
# unfused apply + panel_gram path.
MAX_FUSED_COLS = 512


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def panel_gram(
    B: jax.Array,
    *,
    block_rows: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """G = BᵀB accumulated over (block_rows, n) panels in VMEM.

    One read of B, no n×s intermediate.  ``interpret=None`` resolves via
    ``repro.core.backend.default_interpret``.
    """
    interpret = resolve_interpret(interpret, B)
    s, n = B.shape
    acc = jnp.float32 if B.dtype in (jnp.bfloat16, jnp.float16) else B.dtype

    br = min(block_rows, max(8, s))
    bn = max(128, n) if n < 128 else n
    B_p = pad_to(B, (br, bn))
    s_p, n_p = B_p.shape

    with mosaic_context(interpret):
        G = pl.pallas_call(
            panel_gram_kernel,
            grid=(s_p // br,),
            in_specs=[pl.BlockSpec((br, n_p), lambda pi: (pi, 0))],
            out_specs=pl.BlockSpec((n_p, n_p), lambda pi: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((n_p, n_p), acc),
            interpret=interpret,
        )(B_p)
    return G[:n, :n]


def _positive_diag(Q, R):
    """Flip row signs of R (and matching column signs of Q) so diag(R) ≥ 0."""
    sgn = jnp.where(jnp.diag(R) < 0, -1.0, 1.0).astype(R.dtype)
    return Q * sgn[None, :], R * sgn[:, None]


@partial(jax.jit, static_argnames=("rounds",))
def cholqr_finish(
    B: jax.Array, G: jax.Array, *, rounds: int = 2
) -> tuple[jax.Array, jax.Array]:
    """Shifted CholeskyQR with ``rounds`` correction passes: B = QR from a
    precomputed Gram G = BᵀB.

    The shift σ = 11(sn + n(n+1))·ε·tr(G)/n (Fukaya et al.'s bound with
    the trace as the ‖G‖₂ proxy) guarantees the first Cholesky succeeds
    even when κ(G) overflows 1/ε; each correction round re-orthogonalizes
    Q ← Q·chol(QᵀQ)⁻¹ and absorbs the factor into R, so ``rounds=2``
    (CholeskyQR3 overall) delivers Householder-grade Q and R up to
    κ(B) ≈ 1e10 in f64.  All cost is Gram GEMMs + n×n triangular solves —
    BLAS3-rate, the reason the fused path beats Householder QR.
    """
    s, n = B.shape
    dtype = B.dtype
    eps = jnp.finfo(dtype).eps
    shift = 11.0 * (s * n + n * (n + 1)) * eps * jnp.trace(G) / n
    R = jnp.linalg.cholesky(G + shift * jnp.eye(n, dtype=dtype)).T
    Q = solve_triangular(R, B.T, trans=1, lower=False).T
    for _ in range(rounds):
        G2 = matmul(Q.T, Q)
        R2 = jnp.linalg.cholesky(G2).T
        Q = solve_triangular(R2, Q.T, trans=1, lower=False).T
        R = matmul(R2, R)
    return _positive_diag(Q, R)


def _tree_r(B_p: jax.Array, block_rows: int) -> jax.Array:
    """R of B via per-panel Householder QR + binary-tree pairwise merges."""
    s_p, n = B_p.shape
    panels = B_p.reshape(s_p // block_rows, block_rows, n)
    _, Rs = jax.vmap(partial(jnp.linalg.qr, mode="reduced"))(panels)
    while Rs.shape[0] > 1:
        p = Rs.shape[0]
        if p % 2:  # odd level: carry the last R up unmerged
            odd, Rs = Rs[-1:], Rs[:-1]
        else:
            odd = None
        pairs = Rs.reshape(p // 2, 2 * Rs.shape[1], n)
        _, Rs = jax.vmap(partial(jnp.linalg.qr, mode="reduced"))(pairs)
        if odd is not None:
            pad = jnp.zeros(
                (1, Rs.shape[1] - odd.shape[1], n), Rs.dtype
            )
            Rs = jnp.concatenate([Rs, jnp.concatenate([odd, pad], axis=1)])
    return Rs[0][:n]


@partial(jax.jit, static_argnames=("mode", "block_rows", "interpret"))
def tsqr(
    B: jax.Array,
    *,
    mode: str = "tree",
    block_rows: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Tall-skinny QR of B (s ≥ n): returns (Q, R), diag(R) ≥ 0.

    ``mode="tree"`` is the stability-first default (Householder panels,
    exact at any κ); ``mode="cholqr"`` routes through the Pallas
    ``panel_gram`` kernel + shifted CholeskyQR3 (GEMM-rate, stable to
    κ ≈ 1e10 in f64 — the same finisher the fused ``sketch_qr`` uses).
    """
    s, n = B.shape
    if s < n:
        raise ValueError(f"tsqr needs a tall matrix, got shape {(s, n)}")
    if mode == "cholqr":
        G = panel_gram(B, block_rows=block_rows, interpret=interpret)
        # half-precision B factors in the f32 accumulation dtype of the Gram
        return cholqr_finish(B.astype(G.dtype), G)
    if mode != "tree":
        raise ValueError(f"unknown tsqr mode {mode!r}; have ('tree', 'cholqr')")

    br = max(min(block_rows, s), n)
    B_p = pad_to(B, (br, 1))
    R = _tree_r(B_p, br)
    _, R = _positive_diag(jnp.empty((0, n), B.dtype), R)
    # Q = B·R⁻¹ (orthogonal to O(κ(B)·ε)) + ONE CholeskyQR correction:
    # κ(B·R⁻¹) ≈ 1, so the correction Cholesky is unconditionally safe and
    # restores ‖QᵀQ − I‖ ≈ ε while keeping QR = B to rounding.
    Q = solve_triangular(R, B.T, trans=1, lower=False).T
    R2 = jnp.linalg.cholesky(matmul(Q.T, Q)).T
    Q = solve_triangular(R2, Q.T, trans=1, lower=False).T
    Q, R = _positive_diag(Q, matmul(R2, R))
    return Q, R
