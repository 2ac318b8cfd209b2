"""SAA-SAS — Sketch-and-Apply (paper Algorithm 1).

  1. Draw S ∈ R^{s×m} (Clarkson–Woodruff by default, the paper's choice).
  2. B = SA, c = Sb.
  3. Householder QR of B (jnp.linalg.qr is Householder-based).
  4. Y = A R⁻¹ via triangular substitution (the "apply" step).
  5. Warm start z₀ = Qᵀ c.
  6. LSQR on min‖Y z − b‖ (Y has cond ≈ O(1) w.h.p. — fast convergence).
  7. Converged → x = R⁻¹ z (back substitution).
  8. Fallback (paper lines 10–17): perturb Ã = A + σG/√m with σ = 10‖A‖₂u,
     re-sketch, re-factor and re-solve.  (The paper's line 12 literally says
     "B = SA"; we sketch the perturbed Ã, which is the mathematically
     consistent reading — noted in DESIGN.md.)

Steps 2–5 and 7 are the shared :class:`repro.core.precond.SketchedFactor`:
``build`` (sketch + QR), ``warm_start`` (z₀ = Qᵀc), ``whiten_mv/rmv`` or
``materialize_whitened`` (the apply step), ``precondition`` (x = R⁻¹z).

The sketch apply (step 2) is the compute hot path and dispatches through
``repro.core.backend``: ``backend="reference"`` runs the pure-jnp operator
paths, ``backend="pallas"`` the TPU Pallas kernels in ``repro.kernels``
(interpret mode off-TPU), ``backend="auto"`` resolves per platform.
``backend`` is a static argument, so each choice compiles its own
executable and the dispatch is free at runtime.

``materialize_y=False`` gives the operator-form variant (computes R⁻¹v on the
fly inside LSQR) — same math, O(mn) less memory; this is the at-scale path
used by ``repro.core.distributed``.

``saa_sas_batch`` is the serving front-end: one operator draw + one QR
factor amortized across stacked right-hand sides (A (m,n), b (m,k)) or
across a batch of equally-shaped problems (A (batch,m,n), b (batch,m)).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.common import matmul
from . import linop
from . import sketch as sketch_lib
from .backend import resolve_backend_arg
from .linop import estimate_2norm
from .lsqr import lsqr
from .precond import SketchedFactor, default_sketch_size
from .result import SolveResult

__all__ = ["saa_sas", "saa_sas_batch", "SAAResult", "default_sketch_size"]

# LSQR stop codes (SciPy's) that mean the sketch did not embed the problem:
# the whitened system's condition estimate passed conlim (3) or 1/eps (6).
# The iteration limit (7) is left out: it also stops lanes that only
# converge slowly, and a redraw would solve the whole batch twice.
_EMBEDDING_FAILED = (3, 6)

# Superseded by the unified result type.  The alias keeps attribute access
# (res.x, res.itn, ...) working; field ORDER changed (arnorm inserted), so
# positional unpacking of the old 5-tuple is not preserved.
SAAResult = SolveResult


def _solve_with_factor(
    A, b, factor: SketchedFactor, c, *,
    materialize_y, atol, btol, iter_lim, steptol, history=False,
):
    """Steps 4–7 of Algorithm 1 given the sketched factor and c = Sb."""
    z0 = factor.warm_start(c)
    if materialize_y:
        Y = factor.materialize_whitened(A)
        mv, rmv = (lambda z: matmul(Y, z)), (lambda u: matmul(Y.T, u))
    else:
        mv = partial(factor.whiten_mv, A)
        rmv = partial(factor.whiten_rmv, A)
    res = lsqr(
        mv, rmv, b, x0=z0, atol=atol, btol=btol, iter_lim=iter_lim,
        steptol=steptol, history=history,
    )
    x = factor.precondition(res.x)  # back substitution
    return x, res


@resolve_backend_arg
@partial(
    jax.jit,
    static_argnames=(
        "sketch",
        "sketch_size",
        "materialize_y",
        "iter_lim",
        "use_fallback",
        "steptol",
        "atol",
        "btol",
        "backend",
        "precision",
        "fused",
        "history",
    ),
)
def saa_sas(
    A,
    b: jax.Array,
    key: jax.Array,
    *,
    sketch: str = "clarkson_woodruff",
    sketch_size: int | None = None,
    atol: float = 0.0,
    btol: float = 0.0,
    steptol: float | None = None,
    iter_lim: int = 100,
    materialize_y: bool | None = None,
    use_fallback: bool = True,
    backend: str = "auto",
    precision: str = "full",
    fused: bool | None = None,
    history: bool = False,
) -> SolveResult:
    """Solve min‖Ax − b‖ by Sketch-and-Apply (paper Algorithm 1).

    ``A`` may be a dense array, a BCOO sparse matrix or a
    ``repro.core.linop`` operator.  ``materialize_y=None`` resolves to True
    for dense inputs and False otherwise (the operator-form path never
    densifies A or Y).  The perturbation fallback (paper lines 10–17) adds
    dense Gaussian noise to A, so it only exists on the dense path; for
    matrix-free inputs the first solve's result is returned as-is.
    """
    A = linop.as_operator(A)
    dense_input = isinstance(A, linop.DenseOperator)
    if materialize_y is None:
        materialize_y = dense_input
    m, n = A.shape
    if steptol is None:
        # z-space numerical floor of the whitened system (see lsqr docstring)
        steptol = 32 * float(jnp.finfo(A.dtype).eps)
    k_sketch, k_pert, k_norm = jax.random.split(key, 3)
    kw = dict(
        materialize_y=materialize_y, atol=atol, btol=btol,
        iter_lim=iter_lim, steptol=steptol, history=history,
    )

    factor, op = SketchedFactor.build(
        A, k_sketch, sketch=sketch, sketch_size=sketch_size, backend=backend,
        precision=precision, fused=fused,
    )
    c = op.apply(b, backend=backend)
    x, res = _solve_with_factor(A, b, factor, c, **kw)
    converged = (res.istop > 0) & (res.istop != 7)

    if not (use_fallback and dense_input):
        return res._replace(x=x, used_fallback=jnp.asarray(False))

    def ok_branch(_):
        return res._replace(x=x, used_fallback=jnp.asarray(False))

    def fallback_branch(_):
        # Lines 10–17: Ã = A + σ G/√m, σ = 10‖A‖₂u.
        u_round = jnp.asarray(jnp.finfo(A.dtype).eps / 2, A.dtype)
        sigma = 10.0 * estimate_2norm(A, k_norm) * u_round
        G = jax.random.normal(k_pert, A.shape, A.dtype)
        A_t = A.A + sigma * G / jnp.sqrt(jnp.asarray(m, A.dtype))
        factor2 = SketchedFactor.from_sketch(op.apply(A_t, backend=backend))
        x2, res2 = _solve_with_factor(A_t, b, factor2, c, **kw)
        return res2._replace(x=x2, used_fallback=jnp.asarray(True))

    return lax.cond(converged, ok_branch, fallback_branch, operand=None)


@resolve_backend_arg
@partial(
    jax.jit,
    static_argnames=(
        "sketch",
        "sketch_size",
        "materialize_y",
        "iter_lim",
        "steptol",
        "atol",
        "btol",
        "backend",
    ),
)
def saa_sas_batch(
    A,
    b: jax.Array,
    key: jax.Array,
    *,
    sketch: str = "clarkson_woodruff",
    sketch_size: int | None = None,
    atol: float = 0.0,
    btol: float = 0.0,
    steptol: float | None = None,
    iter_lim: int = 100,
    materialize_y: bool | None = None,
    backend: str = "auto",
) -> SolveResult:
    """Batched SAA-SAS: one operator draw amortized over many solves.

    Two layouts (the serving-style multi-query front-ends):

    - ``A (m, n), b (m, k)`` — one design matrix, k stacked right-hand
      sides.  The sketch, QR factor and (if ``materialize_y``) the whitened
      Y = A R⁻¹ are computed ONCE and shared; only the LSQR iterations run
      per-query (vmapped over columns of b).  Returns x of shape (n, k) and
      per-column istop/itn/rnorm.
    - ``A (batch, m, n), b (batch, m)`` — a batch of equally-shaped
      problems sharing ONE operator draw S.  The whole factor+solve is
      vmapped over the batch (``SketchedFactor`` is a pytree, so the factor
      itself vmaps).  Returns x of shape (batch, n).

    The perturbation fallback of ``saa_sas`` is a per-problem control-flow
    feature and is not taken here.  In problem-batch mode a lane whose
    LSQR stops on the condition limit (istop 3 or 6: the shared draw did
    not embed its problem) is solved again under one independent draw
    and reports ``used_fallback``; its ``istop`` is then the redraw's, so
    a lane that still fails shows it.  The redraw solves the whole batch
    again.  In multi-RHS mode ``used_fallback`` is always False.  Note
    vmap-of-while semantics: all lanes keep iterating until every lane's
    stopping test fires (extra LSQR iterations past convergence are benign —
    the whitened system's updates just stall at the numerical floor).
    """
    if steptol is None:
        steptol = 32 * float(jnp.finfo(A.dtype).eps)
    kw = dict(atol=atol, btol=btol, iter_lim=iter_lim, steptol=steptol)

    if getattr(A, "ndim", 2) == 2:
        # Multi-RHS mode accepts dense, BCOO or linop-operator design
        # matrices (the problem-batch mode below stays array-only).
        A = linop.as_operator(A)
        if materialize_y is None:
            materialize_y = isinstance(A, linop.DenseOperator)
        if b.ndim != 2 or b.shape[0] != A.shape[0]:
            raise ValueError(
                f"multi-RHS mode needs b of shape ({A.shape[0]}, k), got {b.shape}"
            )
        factor, op = SketchedFactor.build(
            A, key, sketch=sketch, sketch_size=sketch_size, backend=backend
        )
        C = op.apply(b, backend=backend)  # (s, k)
        Z0 = factor.warm_start(C)  # (n, k) warm starts

        if materialize_y:
            Y = factor.materialize_whitened(A)
            mv, rmv = (lambda z: matmul(Y, z)), (lambda u: matmul(Y.T, u))
        else:
            mv = partial(factor.whiten_mv, A)
            rmv = partial(factor.whiten_rmv, A)

        def solve_one(b_i, z0_i):
            return lsqr(mv, rmv, b_i, x0=z0_i, **kw)

        res = jax.vmap(solve_one, in_axes=(1, 1))(b, Z0)
        X = factor.precondition(res.x.T)  # (n, k)
        return res._replace(x=X, used_fallback=jnp.zeros(b.shape[1], bool))

    if A.ndim == 3:
        if materialize_y is None:
            materialize_y = True
        if b.ndim != 2 or b.shape[0] != A.shape[0] or b.shape[1] != A.shape[1]:
            raise ValueError(
                f"problem-batch mode needs b of shape {A.shape[:2]}, got {b.shape}"
            )
        batch, m, n = A.shape
        s = sketch_size if sketch_size is not None else default_sketch_size(n, m)

        def solve_all(op_key):
            op = sketch_lib.sample(sketch, op_key, s, m, dtype=A.dtype)

            def solve_one(A_i, b_i):
                factor = SketchedFactor.from_sketch(
                    op.apply(A_i, backend=backend)
                )
                c = op.apply(b_i, backend=backend)
                x, res = _solve_with_factor(
                    A_i, b_i, factor, c, materialize_y=materialize_y, **kw
                )
                return res._replace(x=x)

            return jax.vmap(solve_one)(A, b)

        res = solve_all(key)
        # A lane whose whitened LSQR stopped on the condition limit was
        # not embedded by the shared draw (a sparse sketch can
        # hash two of a padded problem's identity rows into one bucket and
        # lose rank).  Those lanes are solved again under one independent
        # draw, and report used_fallback.
        failed = jnp.isin(res.istop, jnp.asarray(_EMBEDDING_FAILED))

        def redraw(_):
            res2 = solve_all(jax.random.fold_in(key, 1))

            def pick(first, second):
                lane = failed.reshape(failed.shape + (1,) * (first.ndim - 1))
                return jnp.where(lane, second, first)

            return jax.tree.map(pick, res, res2)._replace(used_fallback=failed)

        def keep(_):
            return res._replace(used_fallback=jnp.zeros(batch, bool))

        return lax.cond(jnp.any(failed), redraw, keep, None)

    raise ValueError(f"A must be (m, n) or (batch, m, n), got shape {A.shape}")
