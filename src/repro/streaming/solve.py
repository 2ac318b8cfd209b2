"""Two-pass streaming solvers: least squares without ever holding A.

Pass 1 streams the row tiles once and assembles the sketch B = S·A (and
c = S·b from the same stream — the right-hand side rides along as an
extra column), then QR-factors the small (s, n) B into the shared
:class:`repro.core.precond.SketchedFactor`.  Pass 2 re-streams the tiles
to run the iteration's products with A blockwise — ``A@v`` by placing
per-tile products, ``Aᵀ@u`` by accumulating per-tile adjoint products —
so peak data-matrix memory is one tile, never m·n.

Methods (``stream_lstsq(source, b, key, method=...)``):

- ``"saa"``              — preconditioned LSQR on the whitened operator
  Y = A R⁻¹ with the z₀ = Qᵀ(Sb) warm start; the streaming form of
  ``saa_sas`` (2 streams per iteration: one for Y z, one for Yᵀ u).
- ``"iterative"``        — iterative sketching with damping + momentum
  (Epperly 2024), the forward-stable default: each iteration needs only
  the true gradient Aᵀ(b − Ax), which a single FUSED pass accumulates
  (residual tile → adjoint product tile, 1 stream per iteration).
- ``"sketch_and_solve"`` — pass 1 only: x̂ = R⁻¹Qᵀ(Sb).  True single-pass
  mode for O(ε)-accuracy pipelines; no residual diagnostics are computed
  (that would take a second pass — ``rnorm``/``arnorm`` are nan).

``method="auto"`` picks ``"iterative"``.  ``reg=λ`` solves the ridge
problem through the structured ``[B; √λI]`` / ``[c; 0]`` augmentation of
the *sketched* system (the streaming form of ``sketch.AugmentedSketch`` —
the identity block is exact, never streamed) with diagnostics recomputed
for the original system, matching ``lstsq(reg=...)``.

:class:`StreamingSolver` is the session form (mirroring
``repro.core.session.SketchedSolver``): one pass-1 sketch + QR amortized
over many ``solve``/``solve_many`` calls, with observable ``stats``
counters (``sketches``, ``qr_factorizations``, ``solves``, ``passes``,
``tiles``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core import certify as certify_lib
from ..core import sketch as sketch_lib
from ..core.backend import resolve as resolve_backend
from ..core.iterative import _IMPROVE_FACTOR, _STALL_LIMIT, damping_momentum
from ..core.precond import SketchedFactor, default_sketch_size
from ..core.result import SolveResult
from ..kernels.common import matmul
from ..obs import trace as obs_trace
from ..obs.metrics import REGISTRY
from .accumulate import make_accumulator
from .sources import RowSource, as_source

__all__ = ["stream_lstsq", "stream_sketch", "StreamingSolver", "STREAM_METHODS"]

STREAM_METHODS = ("saa", "iterative", "sketch_and_solve")
_ALIASES = {"sketch": "sketch_and_solve", "single_pass": "sketch_and_solve"}


# --------------------------------------------------------------------------
# Pass 1: streamed sketch assembly
# --------------------------------------------------------------------------


def stream_sketch(
    source,
    key=None,
    *,
    op=None,
    sketch: str = "clarkson_woodruff",
    sketch_size: int | None = None,
    backend: str = "auto",
    rhs: jax.Array | None = None,
):
    """One pass over the tiles → ``(B, op, c)`` with B = S·A, c = S·rhs.

    Draws the operator from ``key`` exactly as the in-memory solvers do
    (same key ⇒ bit-identical S), or reuses a given ``op``.  The Gaussian
    operator is drawn UNmaterialized — its (d, m) matrix is as unstorable
    as A at out-of-core m, and the accumulator regenerates each (d, t)
    column block from the key's counter stream instead.  ``rhs`` (the
    right-hand side) is streamed as an extra column of the same pass, so
    a full sketch-and-solve estimate costs exactly one pass over A.
    """
    source = as_source(source)
    m, n = source.shape
    if op is None:
        if key is None:
            raise ValueError("stream_sketch needs a PRNG key (or an op=)")
        s = sketch_size if sketch_size is not None else default_sketch_size(n, m)
        kw = {"materialize": False} if sketch == "gaussian" else {}
        op = sketch_lib.sample(sketch, key, s, m, **kw)
    if op.m != m:
        raise ValueError(f"operator over m={op.m} rows, source has m={m}")
    ncols = n + (1 if rhs is not None else 0)
    if rhs is not None and rhs.shape != (m,):
        raise ValueError(f"rhs must have shape ({m},), got {rhs.shape}")
    cluster_sketch = getattr(source, "cluster_sketch", None)
    if callable(cluster_sketch):
        # a ClusterEngine source: pass 1 fans out over the worker pool
        # (checkpointed, fault-tolerant) and merges to the same sketch
        with obs_trace.span("stream.pass1", mode="cluster", rows=m):
            Bc = cluster_sketch(op, rhs=rhs, backend=backend)
            obs_trace.maybe_block(Bc)
    else:
        with obs_trace.span("stream.pass1", mode="serial", rows=m):
            acc = make_accumulator(op, ncols, dtype=jnp.dtype(source.dtype),
                                   backend=backend)
            for offset, tile in source.tiles():
                with obs_trace.span("stream.tile", offset=offset):
                    tile = jnp.asarray(tile)
                    if rhs is not None:
                        t = tile.shape[0]
                        tile = jnp.concatenate(
                            [tile,
                             rhs[offset : offset + t][:, None].astype(
                                 tile.dtype
                             )],
                            axis=1,
                        )
                    acc.update(tile, offset)
                    obs_trace.maybe_block(tile)
            Bc = acc.finalize()
            obs_trace.maybe_block(Bc)
    if rhs is None:
        return Bc, op, None
    return Bc[:, :n], op, Bc[:, n]


def _maybe_cluster(source, cluster, backend, counters=None):
    """Wrap ``source`` in a ClusterEngine when a spec/engine was given.

    Returns ``(source, owned)`` where ``owned`` is the engine THIS call
    constructed (the caller must ``close()`` it when done — its worker
    threads and temp checkpoint dir outlive the solve otherwise), or
    ``None`` when the source passed through or the engine was
    caller-provided (caller-provided engines stay open for reuse).

    Lazy import: ``repro.cluster`` imports the streaming layer, so the
    dependency must point one way at module-import time.
    """
    if cluster is None:
        return source, None
    from ..cluster.coordinator import ClusterEngine

    if isinstance(cluster, ClusterEngine):
        if counters is not None and cluster.counters is None:
            cluster.counters = counters
        return cluster, None
    engine = ClusterEngine(source, cluster, backend=backend,
                           counters=counters)
    return engine, engine


# --------------------------------------------------------------------------
# Pass 2: blocked products with A
# --------------------------------------------------------------------------


def _stream_matvec(source, x):
    """A @ x by placing per-tile products (exact placement, no summation).

    Sources that distribute the product themselves (``ClusterEngine``)
    expose a ``matvec`` method, which takes precedence over the serial
    tile loop — same for ``rmatvec`` / ``residual_grad`` below.
    """
    mv = getattr(source, "matvec", None)
    with obs_trace.span("stream.pass2", op="matvec"):
        if callable(mv):
            return obs_trace.maybe_block(mv(x))
        parts = [matmul(jnp.asarray(tile), x) for _, tile in source.tiles()]
        return obs_trace.maybe_block(jnp.concatenate(parts, axis=0))


def _stream_rmatvec(source, u):
    """Aᵀ @ u by accumulating per-tile adjoint products."""
    rmv = getattr(source, "rmatvec", None)
    with obs_trace.span("stream.pass2", op="rmatvec"):
        if callable(rmv):
            return obs_trace.maybe_block(rmv(u))
        n = source.shape[1]
        g = jnp.zeros((n,) + u.shape[1:], u.dtype)
        for offset, tile in source.tiles():
            tile = jnp.asarray(tile)
            g = g + matmul(tile.T, u[offset : offset + tile.shape[0]])
        return obs_trace.maybe_block(g)


def _stream_residual_grad(source, b, x):
    """ONE fused pass: (‖b − Ax‖², Aᵀ(b − Ax)).

    The residual tile feeds the adjoint product before the next tile is
    read — the iterative-sketching step touches A exactly once per
    iteration.  Generic over stacked right-hand sides (b (m, k), x (n, k)):
    the squared norms come back per column.
    """
    rg = getattr(source, "residual_grad", None)
    with obs_trace.span("stream.pass2", op="residual_grad"):
        if callable(rg):
            out = rg(b, x)
            obs_trace.maybe_block(out)
            return out
        n = source.shape[1]
        g = jnp.zeros((n,) + b.shape[1:], b.dtype)
        rn2 = jnp.zeros(b.shape[1:], b.dtype)
        for offset, tile in source.tiles():
            tile = jnp.asarray(tile)
            r_t = b[offset : offset + tile.shape[0]] - matmul(tile, x)
            g = g + matmul(tile.T, r_t)
            rn2 = rn2 + jnp.sum(r_t * r_t, axis=0)
        obs_trace.maybe_block(g)
        return rn2, g


# --------------------------------------------------------------------------
# Host-loop solvers (the per-iteration products are streamed, so the
# iteration itself is a Python loop — each tile op is a normal jax
# dispatch; there is no while_loop to close A into)
# --------------------------------------------------------------------------


class _StepFloor:
    """Host-side twin of ``repro.core.iterative._StepFloor``: converged when
    three consecutive relative steps sit below ``steptol`` OR the absolute
    step norm stops reaching new minima (numerical-floor stagnation)."""

    def __init__(self):
        self.n_small = 0
        self.min_step = math.inf
        self.n_stall = 0

    def update(self, stepnorm: float, relstep: float, steptol: float) -> bool:
        self.n_small = self.n_small + 1 if (steptol > 0 and relstep <= steptol) else 0
        if stepnorm < _IMPROVE_FACTOR * self.min_step:
            self.n_stall = 0
        else:
            self.n_stall += 1
        self.min_step = min(self.min_step, stepnorm)
        return self.n_small >= 3 or self.n_stall >= _STALL_LIMIT


def _lsqr_streamed(mv, rmv, b, x0, *, atol, btol, steptol, iter_lim,
                   history=False):
    """Column-batched Golub–Kahan LSQR with streamed products.

    Host-loop form of ``repro.core.lsqr.lsqr`` (same stopping tests
    1/2/7/8, warm-started on the correction against r₀ = b − A x₀),
    generalized to stacked right-hand sides: all the bidiagonalization
    scalars become per-column (k,) arrays while the two products per
    iteration stay SHARED matmuls — k solves for the streams of one.
    Converged columns keep iterating harmlessly (their updates are ~0)
    until the slowest column stops; per-column ``istop`` records each
    column's own stopping reason.

    1-D ``b`` is the k = 1 case and returns scalars.
    """
    vec = b.ndim == 1
    B = b[:, None] if vec else b
    X0 = x0[:, None] if vec else x0
    k = B.shape[1]
    dtype = B.dtype
    tiny = float(jnp.finfo(dtype).tiny)

    def cnorm(M):
        return jnp.sqrt(jnp.sum(M * M, axis=0))  # per-column norms (k,)

    def safe(s):
        return jnp.where(s > 0, s, 1.0)

    bnorm = cnorm(B)
    R0 = B - mv(X0)
    beta = cnorm(R0)
    U = R0 / safe(beta)
    V_raw = rmv(U)
    alfa = cnorm(V_raw)
    V = V_raw / safe(alfa)
    W = V
    X = jnp.zeros_like(V)
    rhobar, phibar = alfa, beta
    anorm2 = jnp.zeros((k,), dtype)
    arnorm = alfa * beta
    rnorm = beta

    istop = np.zeros(k, np.int32)
    # columns that are trivially solved (b = 0 or already at the optimum)
    istop[np.asarray((bnorm == 0) | (arnorm == 0))] = -1
    itn = 0
    n_small = np.zeros(k, np.int64)
    min_step = np.full(k, np.inf)
    n_stall = np.zeros(k, np.int64)
    rhist = []
    while (istop == 0).any() and itn < iter_lim:
        itn += 1
        with obs_trace.span("stream.iter", itn=itn, method="saa"):
            U_raw = mv(V) - alfa * U
            beta_k = cnorm(U_raw)
            U = U_raw / safe(beta_k)
            anorm2 = anorm2 + alfa**2 + beta_k**2
            V_raw = rmv(U) - beta_k * V
            alfa_k = cnorm(V_raw)
            V = V_raw / safe(alfa_k)

            rho = jnp.hypot(rhobar, beta_k)
            c = jnp.where(rho > 0, rhobar / safe(rho), 1.0)
            sn = jnp.where(rho > 0, beta_k / safe(rho), 0.0)
            theta = sn * alfa_k
            phi = c * phibar
            arnorm = alfa_k * jnp.abs(sn * phibar)  # pre-update phibar
            t1 = jnp.where(rho > 0, phi / safe(rho), 0.0)
            t2 = jnp.where(rho > 0, -theta / safe(rho), 0.0)
            step = jnp.abs(t1) * cnorm(W)
            X = X + t1 * W
            W = V + t2 * W
            rhobar = -c * alfa_k
            phibar = sn * phibar
            alfa = alfa_k

            rnorm = phibar
            anorm = jnp.sqrt(anorm2)
            xnorm = cnorm(X + X0)
            test1 = np.asarray(rnorm / safe(bnorm))
            test2 = np.asarray(arnorm / safe(anorm * rnorm))
            rtol = np.asarray(btol + atol * anorm * xnorm / safe(bnorm))
            relstep = np.asarray(step / jnp.maximum(xnorm, tiny))
            stepn = np.asarray(step)
            if history:
                rhist.append(float(rnorm[0]) if vec else rnorm)

            n_small = np.where(
                (steptol > 0) & (relstep <= steptol), n_small + 1, 0
            )
            n_stall = np.where(
                stepn < _IMPROVE_FACTOR * min_step, 0, n_stall + 1
            )
            min_step = np.minimum(min_step, stepn)

            new = np.zeros(k, np.int32)
            new[:] = 7 if itn >= iter_lim else 0
            new = np.where((n_small >= 3) | (n_stall >= _STALL_LIMIT), 8, new)
            new = np.where(test2 <= atol, 2, new)
            new = np.where(test1 <= rtol, 1, new)
            istop = np.where(istop == 0, new, istop)

    X = X + X0
    istop = np.where(istop == -1, 0, istop)  # trivial columns: scipy's code 0
    if vec:
        return (
            X[:, 0], int(istop[0]), itn, float(rnorm[0]), float(arnorm[0]),
            rhist,
        )
    return X, istop, itn, rnorm, arnorm, rhist


def _iterative_streamed(source, b, factor, x0, *, alpha, beta, reg, atol,
                        btol, steptol, iter_lim, history=False):
    """Heavy-ball iterative sketching, one fused stream per iteration
    (host-loop form of ``repro.core.iterative.iterative_sketching``)."""
    dtype = b.dtype
    lam = None if reg is None else jnp.asarray(reg, dtype)
    bnorm = float(jnp.linalg.norm(b))
    anorm = float(jnp.linalg.norm(factor.R))  # ‖R‖_F ≈ ‖A‖_F
    tiny = float(jnp.finfo(dtype).tiny)
    x, x_prev = x0, x0
    istop, itn = 0, 0
    floor = _StepFloor()
    rhist = []
    if bnorm == 0.0:
        z = jnp.zeros_like(x0)
        return z, 0, 0, bnorm, 0.0, rhist
    while istop == 0 and itn < iter_lim:
        itn += 1
        with obs_trace.span("stream.iter", itn=itn, method="iterative"):
            rn2, g = _stream_residual_grad(source, b, x)
            if lam is not None:
                # augmented system [A; √λI]x ≈ [b; 0]: the tail contributes
                # −λx to the gradient and λ‖x‖² to the squared residual
                rn2 = rn2 + lam * jnp.sum(x * x, axis=0)
                g = g - lam * x
            # block mode (stacked RHS): all norms are Frobenius — the
            # iteration runs until the slowest column's floor
            rnorm = float(jnp.sqrt(jnp.sum(rn2)))
            arnorm = float(jnp.linalg.norm(g))
            d = factor.normal_solve(g)
            dx = alpha * d + beta * (x - x_prev)
            x_prev, x = x, x + dx

            xnorm = float(jnp.linalg.norm(x))
            stepnorm = float(jnp.linalg.norm(dx))
            relstep = stepnorm / max(xnorm, tiny)
            test1 = rnorm / bnorm if bnorm > 0 else rnorm
            denom = anorm * rnorm if anorm * rnorm > 0 else 1.0
            test2 = arnorm / denom
            rtol = btol + atol * anorm * xnorm / (bnorm if bnorm > 0 else 1.0)
            if history:
                rhist.append(rnorm)
            if itn >= iter_lim:
                istop = 7
            if floor.update(stepnorm, relstep, steptol):
                istop = 8
            if test2 <= atol:
                istop = 2
            if test1 <= rtol:
                istop = 1
    return x, istop, itn, None, None, rhist


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------


def _final_diagnostics(source, b, x, reg):
    """(rnorm, arnorm) of the ORIGINAL system at x — one fused pass."""
    rn2, g = _stream_residual_grad(source, b, x)
    if reg is not None:
        g = g - jnp.asarray(reg, b.dtype) * x
    return jnp.sqrt(rn2), jnp.linalg.norm(g)


def _certify_streamed(source, b, x, factor, key, *, lam, sketch_rows,
                      n_probes=8, target=None):
    """Streamed posterior certificate — the pass-1 sketch is REUSED.

    The factor built from the single sketching pass over [A|b] already
    holds everything the estimators need except products with A, which
    stream: one pass evaluates all ``n_probes`` whitened distortion
    probes as a blocked matvec (‖S A R⁻¹w‖ = ‖w‖ exactly, so only
    ‖A R⁻¹w‖ needs A), and one fused pass gives the residual and
    gradient for the forward-error bound.  Ridge certificates are issued
    for the augmented system [A; √λI], whose solution is the ridge
    solution — the √λ terms are exact column arithmetic, never streamed.

    Returns ``(certificate, rnorm, arnorm)`` where the latter two are the
    ORIGINAL-system diagnostics of the same fused pass (the ridge
    gradient ‖Aᵀ(b − Ax) − λx‖, matching ``_final_diagnostics``), so
    certified callers never stream the residual twice.
    """
    n = source.shape[1]
    dtype = b.dtype
    with obs_trace.span("certify.streamed", n_probes=int(n_probes)):
        W = jax.random.normal(key, (n, int(n_probes)), dtype)
        V = factor.precondition(W)
        AV = _stream_matvec(source, V)  # one pass serves every probe
        yn2 = jnp.sum(AV * AV, axis=0)
        if lam is not None:
            yn2 = yn2 + lam * jnp.sum(V * V, axis=0)
        wn = jnp.linalg.norm(W, axis=0)
        ratios = wn / jnp.maximum(jnp.sqrt(yn2), jnp.finfo(dtype).tiny)
        eps_hat = jnp.max(jnp.abs(ratios - 1.0))

        rn2, g = _stream_residual_grad(source, b, x)
        rn2_aug = rn2
        if lam is not None:
            rn2_aug = rn2 + lam * jnp.sum(x * x)
            # the ridge gradient — also the augmented system's
            g = g - lam * x
        wg = factor.rt_solve(g)
        cert = certify_lib.build_certificate(
            factor,
            distortion=eps_hat,
            rnorm=jnp.sqrt(rn2_aug),
            whitened_arnorm=jnp.linalg.norm(wg),
            xnorm=jnp.linalg.norm(x),
            target=target,
            sketch_rows=sketch_rows,
        )
        obs_trace.maybe_block(cert.passed)
    return cert, jnp.sqrt(rn2), jnp.linalg.norm(g)


def stream_lstsq(
    source,
    b: jax.Array,
    key: jax.Array | None = None,
    *,
    method: str = "auto",
    sketch: str = "clarkson_woodruff",
    sketch_size: int | None = None,
    reg: float | jax.Array | None = None,
    atol: float = 0.0,
    btol: float = 0.0,
    steptol: float | None = None,
    iter_lim: int = 100,
    backend: str = "auto",
    history: bool = False,
    tile_rows: int | None = None,
    certify: bool = False,
    certified_rtol: float | None = None,
    certified_probes: int = 8,
    cluster=None,
    trace: bool | None = None,
) -> SolveResult:
    """min‖Ax − b‖ (+ λ‖x‖² with ``reg=λ``) over a row-streamed A.

    ``source``: anything :func:`repro.streaming.sources.as_source` accepts
    — a ``RowSource``, an in-memory array (tiled at ``tile_rows``), or a
    path to a ``.npy`` file (memory-mapped).  The solver holds one tile,
    the (s, n) sketch and a handful of n/m-vectors; A itself is streamed
    once for the sketch and once per iteration (twice for ``"saa"``).

    With the same ``key``, the streamed S is bit-identical to the
    in-memory solvers' draw, so results match ``lstsq`` on the
    materialized A to machine precision.

    ``certify=True`` (the streaming certified mode — also reached via
    ``lstsq(accuracy="certified")`` on a RowSource) attaches a posterior
    :class:`~repro.core.certify.Certificate` built from the SAME pass-1
    sketch of [A|b]: +1 stream for the blocked distortion probes and +1
    fused residual/gradient stream (which also fills the diagnostics the
    single-pass ``"sketch_and_solve"`` method normally skips).  No
    escalation is attempted out-of-core — a failed certificate reports
    ``passed=False`` and the caller chooses between a larger
    ``sketch_size`` re-run or an in-memory method.

    ``cluster=ClusterSpec(...)`` (or a prebuilt
    :class:`~repro.cluster.coordinator.ClusterEngine`) runs every stream —
    the pass-1 sketch and all pass-2 products — across a fault-tolerant
    worker pool with checkpointable sketch state; see ``repro.cluster``.
    An engine built HERE from a spec is torn down again before returning
    (worker threads joined, temp checkpoint dir removed); a prebuilt
    engine is left open for the caller to reuse and ``close()``.
    """
    source = as_source(source, tile_rows)
    scope = obs_trace.solve_scope(trace)
    with scope, obs_trace.span("stream_lstsq"):
        source, owned = _maybe_cluster(source, cluster, backend)
        try:
            res = _stream_lstsq_impl(
                source, b, key, method=method, sketch=sketch,
                sketch_size=sketch_size, reg=reg, atol=atol, btol=btol,
                steptol=steptol, iter_lim=iter_lim, backend=backend,
                history=history, certify=certify,
                certified_rtol=certified_rtol,
                certified_probes=certified_probes,
            )
        finally:
            if owned is not None:
                owned.close()
    return scope.attach(res)


def _stream_lstsq_impl(
    source, b, key, *, method, sketch, sketch_size, reg, atol, btol,
    steptol, iter_lim, backend, history, certify, certified_rtol,
    certified_probes,
) -> SolveResult:
    m, n = source.shape
    b = jnp.asarray(b)
    if b.shape != (m,):
        raise ValueError(f"b must have shape ({m},), got {b.shape}")
    method = _ALIASES.get(method, method)
    if method == "auto":
        # Certified runs default to the whitened LSQR ("saa"): it iterates
        # to the numerical floor, which the heavy-ball tail often leaves
        # short of within the default iter_lim — the certificate would
        # (correctly) refuse to certify that residual accuracy.
        method = "saa" if certify else "iterative"
    if method not in STREAM_METHODS:
        raise ValueError(
            f"unknown streaming method {method!r}; have "
            f"{('auto',) + STREAM_METHODS} "
            "(direct/lsqr/sap/fossils need the in-memory lstsq)"
        )
    if key is None:
        raise ValueError("stream_lstsq needs a PRNG key (all methods sketch)")
    if steptol is None:
        steptol = 32 * float(jnp.finfo(b.dtype).eps)
    s = sketch_size if sketch_size is not None else default_sketch_size(n, m)

    # ---- pass 1: sketch A and b together ------------------------------
    B, op, c = stream_sketch(
        source, key, sketch=sketch, sketch_size=s, backend=backend, rhs=b
    )
    lam = None if reg is None else jnp.asarray(reg, b.dtype)
    if lam is not None:
        # Structured ridge embedding [B; √λI], [c; 0] — the identity block
        # is exact (never sketched, never streamed): sketch.AugmentedSketch.
        sqrt_lam = jnp.sqrt(lam)
        B = jnp.concatenate([B, sqrt_lam * jnp.eye(n, dtype=B.dtype)], axis=0)
        c = jnp.concatenate([c, jnp.zeros((n,), c.dtype)])
    with obs_trace.span("factor.qr", shape=tuple(B.shape)):
        factor = SketchedFactor.from_sketch(B)
        obs_trace.maybe_block(factor.R)
    x0 = factor.sketch_and_solve(c)

    def _maybe_certificate(x):
        """(certificate, rnorm, arnorm) — Nones when not certifying.  The
        diagnostics come from the certificate's own fused pass, so
        certified runs never stream the residual twice."""
        if not certify:
            return None, None, None
        return _certify_streamed(
            source, b, x, factor, jax.random.fold_in(key, 0xCE27),
            lam=lam, sketch_rows=s, n_probes=certified_probes,
            target=certified_rtol,
        )

    # ---- pass 2(+): iterate with streamed products --------------------
    hist = []
    if method == "sketch_and_solve":
        # Single-pass: no second stream, hence no residual diagnostics —
        # unless a certificate was requested, whose fused pass fills them.
        nan = jnp.asarray(jnp.nan, b.dtype)
        cert, rnorm, arnorm = _maybe_certificate(x0)
        if cert is None:
            rnorm = arnorm = nan
        return SolveResult(
            x=x0,
            istop=jnp.asarray(1, jnp.int32),
            itn=jnp.asarray(0, jnp.int32),
            rnorm=rnorm,
            arnorm=arnorm,
            used_fallback=jnp.asarray(False),
            history=jnp.zeros((0,), b.dtype) if history else None,
            method="stream_sketch_and_solve",
            certificate=cert,
        )
    if method == "iterative":
        alpha, beta = damping_momentum(s, n)
        with obs_trace.span("stream.solve", method="iterative"):
            x, istop, itn, _, _, hist = _iterative_streamed(
                source, b, factor, x0, alpha=alpha, beta=beta, reg=lam,
                atol=atol, btol=btol, steptol=steptol, iter_lim=iter_lim,
                history=history,
            )
        cert, rnorm_c, arnorm_c = _maybe_certificate(x)
        if cert is not None:
            rnorm, arnorm = rnorm_c, arnorm_c
        else:
            rnorm, arnorm = _final_diagnostics(source, b, x, lam)
    else:  # saa: preconditioned LSQR on the whitened system, warm-started
        if lam is None:
            def mv(z):
                return _stream_matvec(source, factor.precondition(z))

            def rmv(u):
                return factor.rt_solve(_stream_rmatvec(source, u))

            b_solve = b
        else:
            sqrt_lam = jnp.sqrt(lam)

            def mv(z):
                v = factor.precondition(z)
                return jnp.concatenate([_stream_matvec(source, v), sqrt_lam * v])

            def rmv(u):
                g = _stream_rmatvec(source, u[:m]) + sqrt_lam * u[m:]
                return factor.rt_solve(g)

            b_solve = jnp.concatenate([b, jnp.zeros((n,), b.dtype)])
        z0 = factor.warm_start(c)
        with obs_trace.span("stream.solve", method="saa"):
            z, istop, itn, rnorm, arnorm, hist = _lsqr_streamed(
                mv, rmv, b_solve, z0, atol=atol, btol=btol, steptol=steptol,
                iter_lim=iter_lim, history=history,
            )
        x = factor.precondition(z)
        cert, rnorm_c, arnorm_c = _maybe_certificate(x)
        if cert is not None:
            rnorm, arnorm = rnorm_c, arnorm_c
        elif lam is not None:
            rnorm, arnorm = _final_diagnostics(source, b, x, lam)
        else:
            rnorm = jnp.asarray(rnorm, b.dtype)
            arnorm = jnp.asarray(arnorm, b.dtype)

    return SolveResult(
        x=x,
        istop=jnp.asarray(istop, jnp.int32),
        itn=jnp.asarray(itn, jnp.int32),
        rnorm=jnp.asarray(rnorm, b.dtype),
        arnorm=jnp.asarray(arnorm, b.dtype),
        used_fallback=jnp.asarray(False),
        history=jnp.asarray(hist, b.dtype) if history else None,
        method=f"stream_{method}",
        certificate=cert,
    )


# --------------------------------------------------------------------------
# Session
# --------------------------------------------------------------------------


class _CountingSource(RowSource):
    """Transparent wrapper that counts passes/tiles into a stats dict.

    Unknown attributes forward to the wrapped source, so the dispatch
    probes in ``_stream_matvec`` et al. still find a ``ClusterEngine``'s
    distributed methods through the wrapper (the engine then counts its
    own passes/tiles via its ``counters`` hook — the serial counting here
    only fires on the serial ``tiles()`` path, never both).
    """

    def __init__(self, inner: RowSource, stats: dict):
        self.inner = inner
        self.stats = stats
        self.shape = inner.shape
        self.dtype = inner.dtype

    def __getattr__(self, name):
        return getattr(self.__dict__["inner"], name)

    @property
    def tile_rows(self):
        return self.inner.tile_rows

    @property
    def supports_random_access(self):
        return self.inner.supports_random_access

    def read_rows(self, offset, length):
        return self.inner.read_rows(offset, length)

    def tiles(self):
        self.stats["passes"] += 1
        for offset, tile in self.inner.tiles():
            self.stats["tiles"] += 1
            yield offset, tile


class StreamingSolver:
    """One streamed sketch + QR, amortized over many right-hand sides.

    The out-of-core twin of :class:`repro.core.session.SketchedSolver`:
    construction streams the tiles ONCE to build the sketched factor;
    each ``solve(b)`` then costs one streamed sketch of b (pass over b
    only, not A) plus the pass-2 iteration streams.  ``solve_many(B)``
    runs the column-batched whitened LSQR — k right-hand sides share
    every stream, so the marginal cost per extra RHS is one matmul
    column.

    ``stats`` counts ``sketches`` / ``qr_factorizations`` / ``solves``
    like the in-memory session, plus ``passes`` / ``tiles`` so the
    streaming cost model is observable.
    """

    def __init__(
        self,
        source,
        key: jax.Array,
        *,
        sketch: str = "clarkson_woodruff",
        sketch_size: int | None = None,
        reg: float | jax.Array | None = None,
        tile_rows: int | None = None,
        atol: float = 0.0,
        btol: float = 0.0,
        steptol: float | None = None,
        iter_lim: int = 100,
        backend: str = "auto",
        cluster=None,
    ):
        self.stats = REGISTRY.stats_dict("streaming", {
            "sketches": 0, "qr_factorizations": 0, "solves": 0,
            "passes": 0, "tiles": 0,
        })
        inner, self._owned_engine = _maybe_cluster(
            as_source(source, tile_rows), cluster, backend,
            counters=self.stats,
        )
        try:
            self.source = _CountingSource(inner, self.stats)
            m, n = self.source.shape
            self.shape = (m, n)
            self.reg = reg
            self.sketch_size = (
                sketch_size if sketch_size is not None
                else default_sketch_size(n, m)
            )
            self.backend = resolve_backend(backend).name
            self._dtype = jnp.dtype(self.source.dtype)
            if steptol is None:
                steptol = 32 * float(jnp.finfo(self._dtype).eps)
            self._kw = dict(atol=atol, btol=btol, steptol=steptol,
                            iter_lim=iter_lim)

            B, self._sketch_op, _ = stream_sketch(
                self.source, key, sketch=sketch,
                sketch_size=self.sketch_size, backend=self.backend,
            )
            self.stats["sketches"] += 1
            if reg is not None:
                sqrt_lam = jnp.sqrt(jnp.asarray(reg, B.dtype))
                B = jnp.concatenate(
                    [B, sqrt_lam * jnp.eye(n, dtype=B.dtype)], axis=0
                )
            self.factor = SketchedFactor.from_sketch(B)
            self.stats["qr_factorizations"] += 1
        except BaseException:
            self.close()  # a failed build must not leak the worker pool
            raise

    def close(self):
        """Release a cluster engine this solver built from a ``cluster=``
        spec (worker threads + temp checkpoint dir); no-op otherwise and
        on repeat calls.  A caller-provided engine is never touched."""
        if self._owned_engine is not None:
            self._owned_engine.close()
            self._owned_engine = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------- helpers
    def _sketch_rhs(self, B_rhs: jax.Array) -> jax.Array:
        """S·b (or S·B for stacked columns) — streams b tile-wise through
        the accumulator, so the Gaussian operator never materializes S and
        the sketch of b costs O(m·k), one pass over b only."""
        m, n = self.shape
        cols = B_rhs[:, None] if B_rhs.ndim == 1 else B_rhs
        acc = make_accumulator(
            self._sketch_op, cols.shape[1], dtype=self._dtype,
            backend=self.backend,
        )
        step = self.source.tile_rows
        for o in range(0, m, step):
            acc.update(cols[o : o + step], o)
        c = acc.finalize()
        if self.reg is not None:
            c = jnp.concatenate([c, jnp.zeros((n, c.shape[1]), c.dtype)])
        return c[:, 0] if B_rhs.ndim == 1 else c

    def _diagnose(self, b, x):
        rn, arn = _final_diagnostics(
            self.source, b, x,
            None if self.reg is None else jnp.asarray(self.reg, self._dtype),
        )
        return rn, arn

    def _whitened_ops(self):
        """(mv, rmv) of the whitened — and, under ridge, augmented —
        system; generic over single vectors and stacked columns."""
        factor, source = self.factor, self.source
        m, n = self.shape
        if self.reg is None:
            def mv(z):
                return _stream_matvec(source, factor.precondition(z))

            def rmv(u):
                return factor.rt_solve(_stream_rmatvec(source, u))
        else:
            sqrt_lam = jnp.sqrt(jnp.asarray(self.reg, self._dtype))

            def mv(z):
                v = factor.precondition(z)
                return jnp.concatenate(
                    [_stream_matvec(source, v), sqrt_lam * v]
                )

            def rmv(u):
                g = _stream_rmatvec(source, u[:m]) + sqrt_lam * u[m:]
                return factor.rt_solve(g)
        return mv, rmv

    def _augment_rhs(self, b):
        if self.reg is None:
            return b
        n = self.shape[1]
        tail = jnp.zeros((n,) + b.shape[1:], b.dtype)
        return jnp.concatenate([b, tail])

    # -------------------------------------------------------------- solves
    def solve(self, b: jax.Array, *, method: str = "saa",
              history: bool = False) -> SolveResult:
        """One right-hand side against the stored factor; ``method`` as in
        :func:`stream_lstsq` (``"saa"``, ``"iterative"``,
        ``"sketch_and_solve"``)."""
        m, n = self.shape
        b = jnp.asarray(b)
        if b.shape != (m,):
            raise ValueError(f"b must have shape ({m},), got {b.shape}")
        method = _ALIASES.get(method, method)
        with obs_trace.span("streaming.solve", method=method):
            c = self._sketch_rhs(b)
            x0 = self.factor.sketch_and_solve(c)
            lam = None if self.reg is None else jnp.asarray(self.reg, b.dtype)
            hist = []
            if method == "sketch_and_solve":
                nan = jnp.asarray(jnp.nan, b.dtype)
                self.stats["solves"] += 1
                return SolveResult(
                    x=x0, istop=jnp.asarray(1, jnp.int32),
                    itn=jnp.asarray(0, jnp.int32), rnorm=nan, arnorm=nan,
                    used_fallback=jnp.asarray(False),
                    method="stream_sketch_and_solve",
                )
            if method == "iterative":
                alpha, beta = damping_momentum(self.sketch_size, n)
                x, istop, itn, _, _, hist = _iterative_streamed(
                    self.source, b, self.factor, x0, alpha=alpha, beta=beta,
                    reg=lam, history=history, **self._kw,
                )
            elif method == "saa":
                mv, rmv = self._whitened_ops()
                z, istop, itn, _, _, hist = _lsqr_streamed(
                    mv, rmv, self._augment_rhs(b), self.factor.warm_start(c),
                    history=history, **self._kw,
                )
                x = self.factor.precondition(z)
            else:
                raise ValueError(
                    f"unknown streaming method {method!r}; "
                    f"have {STREAM_METHODS}"
                )
            rnorm, arnorm = self._diagnose(b, x)
        self.stats["solves"] += 1
        return SolveResult(
            x=x, istop=jnp.asarray(istop, jnp.int32),
            itn=jnp.asarray(itn, jnp.int32), rnorm=rnorm, arnorm=arnorm,
            used_fallback=jnp.asarray(False),
            history=jnp.asarray(hist, b.dtype) if history else None,
            method=f"stream_{method}",
        )

    def solve_many(self, B: jax.Array, *, method: str = "saa") -> SolveResult:
        """k stacked right-hand sides (m, k) → x of shape (n, k).

        Every stream serves ALL k columns (the per-tile products become
        matmuls), so k solves cost the iteration streams of one.
        ``method="saa"`` (default) runs the column-batched preconditioned
        LSQR — per-column recurrences, shared streams — and iterates
        until the slowest column stops; ``method="iterative"`` runs the
        block heavy-ball iteration on the overall (Frobenius) step floor.
        """
        m, n = self.shape
        B = jnp.asarray(B)
        if B.ndim != 2 or B.shape[0] != m:
            raise ValueError(
                f"solve_many needs B of shape ({m}, k), got {B.shape}"
            )
        method = _ALIASES.get(method, method)
        with obs_trace.span(
            "streaming.solve_many", method=method, k=int(B.shape[1])
        ):
            C = self._sketch_rhs(B)
            lam = None if self.reg is None else jnp.asarray(self.reg, B.dtype)
            if method == "saa":
                mv, rmv = self._whitened_ops()
                Z, istop, itn, _, _, _ = _lsqr_streamed(
                    mv, rmv, self._augment_rhs(B), self.factor.warm_start(C),
                    **self._kw,
                )
                X = self.factor.precondition(Z)
            elif method == "iterative":
                X0 = self.factor.sketch_and_solve(C)
                alpha, beta = damping_momentum(self.sketch_size, n)
                X, istop, itn, _, _, _ = _iterative_streamed(
                    self.source, B, self.factor, X0, alpha=alpha, beta=beta,
                    reg=lam, **self._kw,
                )
                istop = jnp.full((B.shape[1],), istop, jnp.int32)
            else:
                raise ValueError(
                    f"solve_many supports methods ('saa', 'iterative'); "
                    f"got {method!r}"
                )
            rn2, G = _stream_residual_grad(self.source, B, X)
            if lam is not None:
                G = G - lam * X
        self.stats["solves"] += int(B.shape[1])
        return SolveResult(
            x=X, istop=jnp.asarray(istop, jnp.int32),
            itn=jnp.asarray(itn, jnp.int32),
            rnorm=jnp.sqrt(rn2), arnorm=jnp.linalg.norm(G, axis=0),
            used_fallback=jnp.zeros(B.shape[1], bool),
            method=f"stream_{method}",
        )
