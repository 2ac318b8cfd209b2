"""Forward-stable iterative solvers on the shared sketched factor.

Plain sketch-and-solve (and sketch-and-precondition with a sketch-and-solve
warm start) is *not* forward stable: on ill-conditioned problems with a
non-negligible residual its forward error stagnates a κ(A)-dependent factor
above what Householder QR delivers.  Epperly ("Fast and forward stable
randomized algorithms for linear least-squares problems", 2024) and
Epperly–Meier–Nakatsukasa ("Fast randomized least-squares solvers can be
just as accurate and stable as classical direct solvers", 2024) give two
fixes, both powered by the SAME :class:`repro.core.precond.SketchedFactor`
that SAA-SAS already computes:

- :func:`iterative_sketching` — heavy-ball iteration in x-space.  Each step
  solves the *sketched* normal equations (RᵀR) d = Aᵀ(b − Ax) (two
  triangular solves) and updates x with damping α = (1 − ε²)² and momentum
  β = ε², where ε ≈ √(n/s) is the embedding distortion.  These are the
  optimal Polyak coefficients for a spectrum in [1/(1+ε)², 1/(1−ε)²], the
  whitened operator's range — so the error contracts by ≈ ε per iteration
  independent of κ(A).
- :func:`fossils` — sketch-and-precondition with iterative refinement.
  Starting from the sketch-and-solve estimate, each refinement step solves
  the *residual* system min‖A d − r‖ in the whitened coordinates z = R d by
  the same damped/momentum iteration, then adds R⁻¹z back.  Two refinement
  steps recover direct-method forward error (the FOSSILS scheme).

Both are jit/while_loop-native like ``lsqr``, dispatch their sketch applies
through ``repro.core.backend``, and return the unified
:class:`repro.core.result.SolveResult` (``history=True`` records residual
norms for diagnostics).
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import linop
from .backend import resolve_backend_arg
from .precond import SketchedFactor, default_sketch_size, distortion
from .result import SolveResult

__all__ = [
    "iterative_sketching",
    "fossils",
    "damping_momentum",
    "heavy_ball_refine",
    "fossils_refine",
]


def damping_momentum(sketch_size: int, n: int) -> tuple[float, float]:
    """Optimal heavy-ball (damping, momentum) for distortion ε ≈ √(n/s).

    α = (1 − ε²)², β = ε² — Polyak's coefficients for an operator whose
    squared singular values lie in [1/(1+ε)², 1/(1−ε)²] (Epperly 2024).
    """
    eps = distortion(sketch_size, n)
    return (1.0 - eps**2) ** 2, eps**2


# The error contracts geometrically while the iteration makes progress, so a
# new step-norm minimum appears every couple of iterations (heavy-ball
# steps oscillate with period ~2).  Once the step size stops reaching new
# minima for this many iterations, the iterate is bouncing around its
# numerical floor — declare convergence (istop=8).  This matters because the
# floor of the UNwhitened x-space steps is κ-dependent and cannot be given a
# universal ``steptol`` the way lsqr's whitened z-steps can.  The minimum is
# tracked on the ABSOLUTE step ‖Δx‖: the relative step ‖Δx‖/‖x‖ is
# scale-confounded while ‖x‖ itself is still collapsing from a far-off warm
# start (both shrink geometrically, so their ratio plateaus mid-convergence).
_STALL_LIMIT = 10
_IMPROVE_FACTOR = 0.99  # a step must beat the running min by ≥1% to count


class _StepFloor(NamedTuple):
    """Carry for the two-signal step-floor test shared by both solvers:
    consecutive relative steps below ``steptol``, OR step-norm stagnation
    (no new minimum for ``_STALL_LIMIT`` iterations)."""

    n_small: jax.Array
    min_step: jax.Array
    n_stall: jax.Array

    @classmethod
    def init(cls, dtype) -> "_StepFloor":
        return cls(
            n_small=jnp.asarray(0, jnp.int32),
            min_step=jnp.asarray(jnp.inf, dtype),
            n_stall=jnp.asarray(0, jnp.int32),
        )

    def update(self, stepnorm, relstep, steptol):
        """Returns (next_state, floor_reached)."""
        n_small = jnp.where(
            (steptol > 0) & (relstep <= steptol), self.n_small + 1, 0
        ).astype(jnp.int32)
        improved = stepnorm < _IMPROVE_FACTOR * self.min_step
        min_step = jnp.minimum(self.min_step, stepnorm)
        n_stall = jnp.where(improved, 0, self.n_stall + 1).astype(jnp.int32)
        nxt = _StepFloor(n_small=n_small, min_step=min_step, n_stall=n_stall)
        return nxt, (n_small >= 3) | (n_stall >= _STALL_LIMIT)


class _IterState(NamedTuple):
    itn: jax.Array
    istop: jax.Array
    x: jax.Array
    x_prev: jax.Array
    rnorm: jax.Array
    arnorm: jax.Array
    floor: _StepFloor
    rhist: jax.Array  # (iter_lim,) or (0,)


@resolve_backend_arg
@partial(
    jax.jit,
    static_argnames=(
        "atol", "btol", "steptol", "iter_lim", "backend", "history",
    ),
)
def heavy_ball_refine(
    A,
    b: jax.Array,
    factor: SketchedFactor,
    x0: jax.Array,
    alpha,
    beta,
    *,
    atol: float = 0.0,
    btol: float = 0.0,
    steptol: float,
    iter_lim: int = 100,
    backend: str = "auto",
    history: bool = False,
) -> SolveResult:
    """The damped/momentum iteration of :func:`iterative_sketching` against
    a PREBUILT factor.

    Factoring this out of the one-shot solver lets the certified adaptive
    driver (``repro.core.lstsq``) re-run the refinement after escalating an
    existing factor — the sketch is extended, never redrawn, and only this
    loop repeats.  Same stopping semantics as ``iterative_sketching``.

    Each step's r = b − A x and Aᵀr come from ``A.residual_gradient``:
    under ``backend="pallas"`` a dense float32 A with a vector b is read
    once per step (``linop.one_pass``), otherwise they are two products.
    """
    # The loop and the residual of the returned iterate, named for the
    # profile: their device ops carry "refine" in their op_name.
    with jax.named_scope("refine"):
        A = linop.as_operator(A)
        dtype = A.dtype
        tiny = jnp.finfo(dtype).tiny
        bnorm = jnp.linalg.norm(b)
        anorm = jnp.linalg.norm(factor.R)  # ‖R‖_F = ‖SA‖_F ≈ ‖A‖_F

        init = _IterState(
            itn=jnp.asarray(0, jnp.int32),
            istop=jnp.asarray(0, jnp.int32),
            x=x0,
            x_prev=x0,
            rnorm=jnp.asarray(jnp.inf, dtype),
            arnorm=jnp.asarray(jnp.inf, dtype),
            floor=_StepFloor.init(dtype),
            rhist=jnp.full((iter_lim if history else 0,), jnp.nan, dtype),
        )

        def cond(st: _IterState):
            return (st.istop == 0) & (st.itn < iter_lim)

        def body(st: _IterState):
            itn = st.itn + 1
            # the true gradient (up to sign) and the residual's norm
            g, rsq = A.residual_gradient(st.x, b, backend=backend)
            rnorm = jnp.sqrt(rsq)
            arnorm = jnp.linalg.norm(g)
            d = factor.normal_solve(g)  # sketched-Hessian solve
            dx = alpha * d + beta * (st.x - st.x_prev)
            x = st.x + dx

            xnorm = jnp.linalg.norm(x)
            stepnorm = jnp.linalg.norm(dx)
            relstep = stepnorm / jnp.maximum(xnorm, tiny)
            floor, floor_reached = st.floor.update(stepnorm, relstep, steptol)

            test1 = rnorm / jnp.where(bnorm > 0, bnorm, 1.0)
            denom = jnp.where(anorm * rnorm > 0, anorm * rnorm, 1.0)
            test2 = arnorm / denom
            rtol = btol + atol * anorm * xnorm / jnp.where(
                bnorm > 0, bnorm, 1.0)

            istop = jnp.asarray(0, jnp.int32)
            istop = jnp.where(itn >= iter_lim, 7, istop)
            istop = jnp.where(floor_reached, 8, istop)
            istop = jnp.where(test2 <= atol, 2, istop)
            istop = jnp.where(test1 <= rtol, 1, istop)

            rhist = st.rhist.at[itn - 1].set(rnorm) if history else st.rhist
            return _IterState(
                itn=itn,
                istop=istop.astype(jnp.int32),
                x=x,
                x_prev=st.x,
                rnorm=rnorm,
                arnorm=arnorm,
                floor=floor,
                rhist=rhist,
            )

        final = lax.while_loop(cond, body, init)
        # Report the residual of the RETURNED iterate (the loop's
        # rnorm/arnorm lag one update behind final.x).
        g, rsq = A.residual_gradient(final.x, b, backend=backend)
        return SolveResult(
            x=final.x,
            istop=jnp.where(bnorm == 0, 0, final.istop),
            itn=final.itn,
            rnorm=jnp.sqrt(rsq),
            arnorm=jnp.linalg.norm(g),
            used_fallback=jnp.asarray(False),
            history=final.rhist if history else None,
        )


@resolve_backend_arg
@partial(
    jax.jit,
    static_argnames=(
        "sketch", "sketch_size", "damping", "momentum", "atol", "btol",
        "steptol", "iter_lim", "backend", "precision", "fused", "history",
    ),
)
def iterative_sketching(
    A,
    b: jax.Array,
    key: jax.Array,
    *,
    sketch: str = "clarkson_woodruff",
    sketch_size: int | None = None,
    damping: float | None = None,
    momentum: float | None = None,
    atol: float = 0.0,
    btol: float = 0.0,
    steptol: float | None = None,
    iter_lim: int = 100,
    backend: str = "auto",
    precision: str = "full",
    fused: bool | None = None,
    history: bool = False,
) -> SolveResult:
    """Iterative sketching with damping + momentum (forward stable).

    x₀ = sketch-and-solve; then
    x_{i+1} = x_i + α (RᵀR)⁻¹ Aᵀ(b − A x_i) + β (x_i − x_{i−1}).

    Stops on the step floor (istop=8) — either three consecutive relative
    steps below ``steptol`` or the step-norm stagnation test (no new
    minimum for ``_STALL_LIMIT`` iterations; the gradient is computed from
    the TRUE residual each iteration, so stagnation means the numerical
    floor, not sketch bias) — on residual tolerances (istop=1/2, SciPy
    semantics), or at ``iter_lim`` (istop=7).

    ``A`` may be a dense array, a BCOO sparse matrix or a
    ``repro.core.linop`` operator — only products with A are ever taken,
    so the solve is fully matrix-free.
    """
    A = linop.as_operator(A)
    m, n = A.shape
    s = sketch_size if sketch_size is not None else default_sketch_size(n, m)
    if steptol is None:
        steptol = 32 * float(jnp.finfo(A.dtype).eps)
    alpha, beta = damping_momentum(s, n)
    if damping is not None:
        alpha = damping
    if momentum is not None:
        beta = momentum

    factor, op = SketchedFactor.build(
        A, key, sketch=sketch, sketch_size=s, backend=backend,
        precision=precision, fused=fused,
    )
    x0 = factor.sketch_and_solve(op.apply(b, backend=backend))
    return heavy_ball_refine(
        A, b, factor, x0, alpha, beta,
        atol=atol, btol=btol, steptol=steptol, iter_lim=iter_lim,
        backend=backend, history=history,
    )


class _InnerState(NamedTuple):
    itn: jax.Array
    done: jax.Array  # bool: step floor reached
    z: jax.Array
    z_prev: jax.Array
    floor: _StepFloor


def _whitened_heavy_ball(
    factor: SketchedFactor, A, r, z0, *, alpha, beta, iter_lim, steptol
):
    """Heavy ball on min‖Y z − r‖, Y = A R⁻¹: the FOSSILS inner solve.

    Returns (z, iterations, hit_floor).  Runs as a while_loop, stopping on
    the z-space step floor (``steptol``, whitened coordinates) or on step
    stagnation — the same two-signal test as ``iterative_sketching``.
    """
    dtype = r.dtype
    tiny = jnp.finfo(dtype).tiny

    init = _InnerState(
        itn=jnp.asarray(0, jnp.int32),
        done=jnp.asarray(False),
        z=z0,
        z_prev=z0,
        floor=_StepFloor.init(dtype),
    )

    def cond(st: _InnerState):
        return (~st.done) & (st.itn < iter_lim)

    def body(st: _InnerState):
        g = factor.whiten_rmv(A, r - factor.whiten_mv(A, st.z))
        dz = alpha * g + beta * (st.z - st.z_prev)
        z = st.z + dz
        stepnorm = jnp.linalg.norm(dz)
        relstep = stepnorm / jnp.maximum(jnp.linalg.norm(z), tiny)
        floor, floor_reached = st.floor.update(stepnorm, relstep, steptol)
        return _InnerState(
            itn=st.itn + 1,
            done=floor_reached,
            z=z,
            z_prev=st.z,
            floor=floor,
        )

    final = lax.while_loop(cond, body, init)
    return final.z, final.itn, final.done


@partial(
    jax.jit,
    static_argnames=(
        "refine_steps", "inner_iter_lim", "steptol", "backend", "history",
    ),
)
def fossils_refine(
    A,
    b: jax.Array,
    factor: SketchedFactor,
    op,
    x0: jax.Array,
    alpha,
    beta,
    *,
    refine_steps: int = 2,
    inner_iter_lim: int,
    steptol: float,
    backend: str = "auto",
    history: bool = False,
) -> SolveResult:
    """The FOSSILS refinement passes against a PREBUILT (factor, op) pair.

    The factor-reusing core of :func:`fossils`, exposed for the certified
    adaptive driver: after a sketch escalation the same refinement re-runs
    on the extended factor, warm-starting each residual solve with the
    SAME (extended) operator — no fresh draw, no full re-sketch.
    """
    A = linop.as_operator(A)
    x = x0
    itn_total = jnp.asarray(0, jnp.int32)
    # refine_steps=0 means the raw sketch-and-solve estimate goes out
    # unrefined — never certify that as converged-to-floor.
    hit_floor = jnp.asarray(refine_steps > 0)
    rhist = []
    for _ in range(refine_steps):  # static unroll (refine_steps is tiny)
        r = b - A.matvec(x)
        rhist.append(jnp.linalg.norm(r))
        z0 = factor.warm_start(op.apply(r, backend=backend))
        z, itn, done = _whitened_heavy_ball(
            factor, A, r, z0,
            alpha=alpha, beta=beta, iter_lim=inner_iter_lim, steptol=steptol,
        )
        x = x + factor.precondition(z)
        itn_total = itn_total + itn
        hit_floor = hit_floor & done

    r = b - A.matvec(x)
    rnorm = jnp.linalg.norm(r)
    rhist.append(rnorm)
    g = A.rmatvec(r)

    istop = jnp.where(hit_floor, 8, 7).astype(jnp.int32)
    istop = jnp.where(jnp.linalg.norm(b) == 0, 0, istop)
    return SolveResult(
        x=x,
        istop=istop,
        itn=itn_total,
        rnorm=rnorm,
        arnorm=jnp.linalg.norm(g),
        used_fallback=jnp.asarray(False),
        history=jnp.stack(rhist) if history else None,
    )


def default_inner_iter_lim(beta: float, dtype=jnp.float64) -> int:
    """FOSSILS inner-iteration budget: error contracts by ≈ √β per step;
    budget to the numerical floor, with margin for the stall detector to
    certify it (istop=8)."""
    eps_mach = float(jnp.finfo(dtype).eps)
    rate = max(math.sqrt(beta), 1e-3)
    return min(int(math.log(eps_mach) / math.log(rate)) + 30, 500)


@resolve_backend_arg
@partial(
    jax.jit,
    static_argnames=(
        "sketch", "sketch_size", "refine_steps", "inner_iter_lim", "damping",
        "momentum", "steptol", "backend", "precision", "fused", "history",
    ),
)
def fossils(
    A,
    b: jax.Array,
    key: jax.Array,
    *,
    sketch: str = "clarkson_woodruff",
    sketch_size: int | None = None,
    refine_steps: int = 2,
    inner_iter_lim: int | None = None,
    damping: float | None = None,
    momentum: float | None = None,
    steptol: float | None = None,
    backend: str = "auto",
    precision: str = "full",
    fused: bool | None = None,
    history: bool = False,
) -> SolveResult:
    """FOSSILS-style sketch-and-precondition with iterative refinement.

    x₀ = sketch-and-solve; each of the ``refine_steps`` refinement passes
    solves the residual system min‖A d − r‖ in whitened coordinates with the
    damped/momentum inner iteration (warm-started from the *sketched*
    residual system, z₀ = Qᵀ(Sr), reusing the same operator S), then updates
    x ← x + R⁻¹z.  Two passes give direct-method forward error.

    ``history=True`` records the outer residual norms — a
    ``(refine_steps + 1,)`` array, entry 0 being the sketch-and-solve
    residual.  ``itn`` counts total inner iterations.

    Accepts dense arrays, BCOO matrices and ``repro.core.linop`` operators
    (matrix-free: only products with A are taken).
    """
    A = linop.as_operator(A)
    m, n = A.shape
    s = sketch_size if sketch_size is not None else default_sketch_size(n, m)
    if steptol is None:
        steptol = 32 * float(jnp.finfo(A.dtype).eps)
    alpha, beta = damping_momentum(s, n)
    if damping is not None:
        alpha = damping
    if momentum is not None:
        beta = momentum
    if inner_iter_lim is None:
        inner_iter_lim = default_inner_iter_lim(beta, A.dtype)

    factor, op = SketchedFactor.build(
        A, key, sketch=sketch, sketch_size=s, backend=backend,
        precision=precision, fused=fused,
    )
    x0 = factor.sketch_and_solve(op.apply(b, backend=backend))
    return fossils_refine(
        A, b, factor, op, x0, alpha, beta,
        refine_steps=refine_steps, inner_iter_lim=inner_iter_lim,
        steptol=steptol, backend=backend, history=history,
    )
