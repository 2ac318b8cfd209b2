"""LSQR (Paige & Saunders 1982) in JAX.

Operator-form least-squares solver: minimizes ``‖Ax − b‖₂`` given
``matvec(x) = A x`` and ``rmatvec(u) = Aᵀ u``.  Runs under ``jax.jit`` via
``lax.while_loop`` and inside ``shard_map`` (all reductions go through an
injectable ``dot``/norm so the distributed driver can psum them).

Supports a warm start ``x0`` (used by SAA-SAS with ``z₀ = Qᵀc``) by solving
for the correction ``dx`` against the residual ``b − A x₀``.

Returns the unified :class:`repro.core.result.SolveResult`; ``history=True``
additionally records the per-iteration residual norms into a fixed-length
``(iter_lim,)`` array (nan-padded past the final iteration).

istop codes follow SciPy's convention:
  0 x=0 is the exact solution;  1 residual-level convergence (btol/atol);
  2 least-squares convergence (AᵀR small);  7 iteration limit;
  8 (ours) step-size floor — three consecutive relative updates below
    ``steptol``.  This is the right test for SAA-SAS's *whitened* inner
    system, where the residual saturates at ‖r_opt‖ = β immediately (test1
    fires spuriously) and ‖Yᵀr‖/(‖Y‖‖r‖) has a rounding floor ≫ atol
    (test2 never fires); forward error instead tracks the z-step size,
    which decays geometrically because Y is a near-isometry.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.common import vdot
from .result import SolveResult

__all__ = ["lsqr", "lsqr_dense", "lsqr_operator", "LSQRResult"]

# Superseded by the unified result type.  The alias keeps attribute access
# for the shared fields working; the old anorm/acond/xnorm diagnostics and
# the old positional field order are gone.
LSQRResult = SolveResult


class _State(NamedTuple):
    itn: jax.Array
    istop: jax.Array
    x: jax.Array
    u: jax.Array
    v: jax.Array
    w: jax.Array
    alfa: jax.Array
    rhobar: jax.Array
    phibar: jax.Array
    anorm2: jax.Array  # running ‖A‖_F² estimate
    acond: jax.Array
    ddnorm: jax.Array
    xnorm: jax.Array
    arnorm: jax.Array
    n_small_steps: jax.Array  # consecutive relative steps below steptol
    rhist: jax.Array  # (iter_lim,) residual history, or (0,) when disabled


def _sym_ortho(a, b):
    """Stable Givens rotation (c, s, r) with r = hypot(a, b)."""
    r = jnp.hypot(a, b)
    safe = jnp.where(r == 0, 1.0, r)
    c = jnp.where(r == 0, 1.0, a / safe)
    s = jnp.where(r == 0, 0.0, b / safe)
    return c, s, r


def lsqr(
    matvec: Callable,
    rmatvec: Callable,
    b: jax.Array,
    *,
    x0: jax.Array | None = None,
    n: int | None = None,
    atol: float = 1e-8,
    btol: float = 1e-8,
    conlim: float = 1e8,
    iter_lim: int | None = None,
    steptol: float = 0.0,
    vdot: Callable = vdot,
    udot: Callable = vdot,
    history: bool = False,
) -> SolveResult:
    """Minimize ‖Ax − b‖₂.

    ``udot`` is the inner product for m-space vectors (u, b) and ``vdot`` for
    n-space vectors — the distributed driver overrides ``udot`` with a
    psum-reducing dot when u/b are sharded across devices.  ``history=True``
    records per-iteration residual norms (fixed ``(iter_lim,)`` shape).
    """
    dtype = b.dtype

    def unorm(u):
        return jnp.sqrt(udot(u, u))

    def vnorm(v):
        return jnp.sqrt(vdot(v, v))

    # Warm start: iterate on the correction dx against r0 = b − A x0, but
    # keep the ORIGINAL ‖b‖ and ‖x0 + dx‖ in the stopping tests (the residual
    # ‖A(x0+dx) − b‖ is identical, so test1/test2 keep their usual meaning —
    # shifting bnorm to ‖r0‖ would make relative tolerances unreachable when
    # the warm start is already good).
    bnorm = unorm(b)
    if x0 is not None:
        x_base = x0
        b = b - matvec(x0)
        n = x0.shape[0]
    else:
        x_base = None

    v0 = rmatvec(b)
    if n is None:
        n = v0.shape[0]
    if iter_lim is None:
        iter_lim = 2 * n

    eps = jnp.finfo(dtype).eps
    beta = unorm(b)
    u = b / jnp.where(beta > 0, beta, 1.0)
    v_raw = rmatvec(u)
    alfa = vnorm(v_raw)
    v = v_raw / jnp.where(alfa > 0, alfa, 1.0)

    init = _State(
        itn=jnp.asarray(0, jnp.int32),
        istop=jnp.asarray(0, jnp.int32),
        x=jnp.zeros_like(v),
        u=u,
        v=v,
        w=v,
        alfa=alfa,
        rhobar=alfa,
        phibar=beta,
        anorm2=jnp.asarray(0.0, dtype),
        acond=jnp.asarray(0.0, dtype),
        ddnorm=jnp.asarray(0.0, dtype),
        xnorm=jnp.asarray(0.0, dtype),
        arnorm=alfa * beta,
        n_small_steps=jnp.asarray(0, jnp.int32),
        rhist=jnp.full((iter_lim if history else 0,), jnp.nan, dtype),
    )
    ctol = 0.0 if conlim <= 0 else 1.0 / conlim

    def cond(s: _State):
        return (s.istop == 0) & (s.itn < iter_lim)

    def body(s: _State):
        itn = s.itn + 1
        # Golub–Kahan bidiagonalization step.
        u_raw = matvec(s.v) - s.alfa * s.u
        beta_k = unorm(u_raw)
        u = u_raw / jnp.where(beta_k > 0, beta_k, 1.0)
        anorm2 = s.anorm2 + s.alfa**2 + beta_k**2
        v_raw = rmatvec(u) - beta_k * s.v
        alfa_k = vnorm(v_raw)
        v = v_raw / jnp.where(alfa_k > 0, alfa_k, 1.0)

        # Givens rotation to zero out beta_k of the bidiagonal system.
        c, sn, rho = _sym_ortho(s.rhobar, beta_k)
        theta = sn * alfa_k
        rhobar = -c * alfa_k
        phi = c * s.phibar
        phibar = sn * s.phibar

        t1 = phi / jnp.where(rho == 0, 1.0, rho)
        t2 = -theta / jnp.where(rho == 0, 1.0, rho)
        x = s.x + t1 * s.w
        dk = s.w / jnp.where(rho == 0, 1.0, rho)
        ddnorm = s.ddnorm + vdot(dk, dk)
        w = v + t2 * s.w

        anorm = jnp.sqrt(anorm2)
        acond = anorm * jnp.sqrt(ddnorm)
        rnorm = phibar
        arnorm = alfa_k * jnp.abs(sn * s.phibar)  # ‖Aᵀr‖ estimate
        x_full = x if x_base is None else x + x_base
        xnorm = jnp.sqrt(vdot(x_full, x_full))

        # Stopping tests (SciPy-compatible).
        test1 = rnorm / jnp.where(bnorm > 0, bnorm, 1.0)
        denom = jnp.where(anorm * rnorm > 0, anorm * rnorm, 1.0)
        test2 = arnorm / denom
        test3 = 1.0 / jnp.where(acond > 0, acond, 1.0)
        rtol = btol + atol * anorm * xnorm / jnp.where(bnorm > 0, bnorm, 1.0)

        # Step-size floor test (istop=8): relative z-update below steptol
        # for three consecutive iterations.
        step = jnp.abs(t1) * jnp.sqrt(vdot(s.w, s.w))
        relstep = step / jnp.maximum(xnorm, jnp.finfo(dtype).tiny)
        n_small = jnp.where(
            (steptol > 0) & (relstep <= steptol), s.n_small_steps + 1, 0
        ).astype(jnp.int32)

        istop = jnp.asarray(0, jnp.int32)
        istop = jnp.where(itn >= iter_lim, 7, istop)
        istop = jnp.where(n_small >= 3, 8, istop)
        istop = jnp.where(1 + test3 <= 1, 6, istop)
        istop = jnp.where(1 + test2 <= 1, 5, istop)
        istop = jnp.where(1 + test1 <= 1, 4, istop)
        istop = jnp.where(test3 <= ctol, 3, istop)
        istop = jnp.where(test2 <= atol, 2, istop)
        istop = jnp.where(test1 <= rtol, 1, istop)

        rhist = s.rhist.at[itn - 1].set(rnorm) if history else s.rhist

        return _State(
            itn=itn,
            istop=istop.astype(jnp.int32),
            x=x,
            u=u,
            v=v,
            w=w,
            alfa=alfa_k,
            rhobar=rhobar,
            phibar=phibar,
            anorm2=anorm2,
            acond=acond,
            ddnorm=ddnorm,
            xnorm=xnorm,
            arnorm=arnorm,
            n_small_steps=n_small,
            rhist=rhist,
        )

    final = lax.while_loop(cond, body, init)
    istop = jnp.where((bnorm == 0) | (init.arnorm == 0), 0, final.istop)
    x_out = final.x if x_base is None else final.x + x_base
    return SolveResult(
        x=x_out,
        istop=istop,
        itn=final.itn,
        rnorm=final.phibar,
        arnorm=final.arnorm,
        used_fallback=jnp.asarray(False),
        history=final.rhist if history else None,
    )


def lsqr_operator(A, b: jax.Array, **kw) -> SolveResult:
    """LSQR on ``jax.Array | BCOO | linop.LinearOperator`` inputs.

    The Golub–Kahan recurrence only takes products with A, so this is the
    natural entry point for sparse and matrix-free problems (and the only
    sketch-free iterative path, hence ``lstsq``'s keyless fallback).
    """
    from . import linop  # local import: linop is dependency-free, lsqr is hot

    A = linop.as_operator(A)
    return lsqr(A.matvec, A.rmatvec, b, n=A.shape[1], **kw)


def lsqr_dense(A, b: jax.Array, **kw) -> SolveResult:
    """LSQR with an explicit A (the paper's baseline configuration).

    Historical name — accepts everything :func:`lsqr_operator` does."""
    return lsqr_operator(A, b, **kw)
