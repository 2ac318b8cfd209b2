"""``lstsq`` — the one-call driver over every least-squares solver.

``lstsq(A, b, key)`` auto-selects among the package's solvers by shape,
sketch-size regime and requested accuracy, and always returns the unified
:class:`repro.core.result.SolveResult` (with ``.method`` naming the solver
that ran).  ``method=`` forces a specific solver:

=============  ============================================================
method         solver
=============  ============================================================
``direct``     Householder-QR ``qr_solve`` (ground truth; small problems)
``lsqr``       plain LSQR on A (no sketching; works without a key)
``saa``        SAA-SAS, paper Algorithm 1 (fastest sketched path)
``sap``        sketch-and-precondition baseline (paper §4)
``iterative``  iterative sketching with damping + momentum (forward stable)
``fossils``    sketch-and-precondition + iterative refinement (forward
               stable, direct-method accuracy)
=============  ============================================================

``A`` may be a dense ``jax.Array``, a ``jax.experimental.sparse`` BCOO
matrix, or any ``repro.core.linop`` operator (the matrix-free protocol) —
every solver above accepts all three.  ``reg=λ`` solves the Tikhonov/ridge
problem min‖Ax − b‖² + λ‖x‖² through the augmented operator [A; √λ·I]
(``linop.TikhonovAugmented``) with zero solver-specific code; the returned
``rnorm``/``arnorm`` are recomputed for the ORIGINAL system (``arnorm`` is
the ridge gradient norm ‖Aᵀ(b − Ax) − λx‖).

``A`` may ALSO be a ``repro.streaming`` row source (a ``RowSource``
instance) — an out-of-core matrix streamed one row tile at a time.  Those
inputs delegate to :func:`repro.streaming.solve.stream_lstsq` (also
re-exported here as ``stream_lstsq``), whose two-pass solvers never hold
A; ``method`` must then be one of its streaming methods (``"auto"``,
``"saa"``, ``"iterative"``, ``"sketch_and_solve"``).

Auto-selection (``method="auto"``):

- problems too small or too square for sketching to pay off → ``direct``
  (nearly-square and underdetermined shapes, where no sketch can shrink
  the row space, always land here / on ``lsqr``);
- large and strongly overdetermined with a PRNG key → a sketched solver by
  ``accuracy``: ``"fast"`` → ``saa``, ``"balanced"`` (default) →
  ``iterative``, ``"high"`` → ``fossils``;
- large but no key supplied → ``lsqr`` (the only deterministic iterative
  path);
- sparse / matrix-free inputs never select ``direct`` (it would densify
  A): with a key they go to the sketched iterative solvers, without one to
  ``lsqr``;
- with ``reg=λ`` the regime tests run on the ORIGINAL data shape, not the
  augmented ``(m + n, n)`` operator the solver ultimately sees (the
  appended √λ·I rows used to inflate m and mis-classify near-boundary
  ridge problems as sketchable).

A dense array whose ``NamedSharding`` splits its rows over more than one
device (and keeps its columns whole) takes the mesh path:
:func:`repro.core.distributed.sketched_lstsq`, the row-sharded SAA-SAS, on
the mesh and axes of ``A.sharding`` (``b`` is placed beside A's rows).
Each chip sketches its own rows and one psum assembles the sketch; no
collective moves A.  Of the methods only ``saa`` has that form, so
``method="auto"`` with ``accuracy`` "fast" or "balanced" selects it, and
the options it has no form for (``accuracy`` "high"/"certified",
``reg=``, ``precision="mixed"``, ``cluster=``, ``fused=``,
``history=True``, another forced method, a sketch kind without per-row
parameters) raise instead of gathering A onto one device.

``accuracy="certified"`` is the fourth, adaptive tier: solve, then
*certify* the answer with the posterior estimators of
``repro.core.certify`` (embedding-distortion probe, cond(R), a forward
error bound), and on a failed certificate escalate — append rows to the
sketch (the stored B = SA is extended, never recomputed) and climb the
method ladder saa → iterative → fossils → dense-QR fallback.  The result
carries a ``certificate`` with the bound that was finally certified.

The driver is a thin Python-level dispatch — every method underneath is its
own jitted, backend-dispatched solver, so there is no extra trace or
runtime cost over calling the solver directly.

Tolerance forwarding is explicit: each method supports a documented
subset of ``atol``/``btol``/``steptol``/``iter_lim`` (see
``TOL_SUPPORT``).  Forcing a method while passing a knob it does not
consume raises; under ``method="auto"`` unsupported knobs are dropped
(the selected method may legitimately vary with shape).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import backend as backend_lib
from . import certify as certify_lib
from . import distributed
from . import linop
from ..kernels.common import matmul
from ..obs import trace as obs_trace
from .direct import qr_solve
from .iterative import (
    damping_momentum,
    default_inner_iter_lim,
    fossils,
    fossils_refine,
    heavy_ball_refine,
    iterative_sketching,
)
from .lsqr import lsqr_operator
from .precond import SketchedFactor, default_sketch_size
from .result import SolveResult
from .saa import _solve_with_factor, saa_sas
from .sap import sap_sas
from .sketch import SKETCH_KINDS

__all__ = [
    "lstsq",
    "select_method",
    "stream_lstsq",
    "METHODS",
    "ACCURACIES",
    "TOL_SUPPORT",
]


def __getattr__(name):
    # Lazy re-export: repro.streaming imports repro.core at module scope,
    # so the streaming driver can only be pulled in on first access.
    if name == "stream_lstsq":
        from ..streaming.solve import stream_lstsq

        return stream_lstsq
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

METHODS = ("direct", "lsqr", "saa", "sap", "iterative", "fossils")
ACCURACIES = ("fast", "balanced", "high", "certified")
_ALIASES = {"iterative_sketching": "iterative", "qr": "direct"}

# m·n² flops below which Householder QR is effectively free and sketching
# overhead (operator draw + sketch + small QR) cannot pay for itself.
DIRECT_FLOP_CUTOFF = 1 << 26

_SKETCHED_BY_ACCURACY = {"fast": "saa", "balanced": "iterative", "high": "fossils"}

# Which tolerance knobs each method actually consumes (the explicit
# forwarding audit): ``direct`` takes none (one exact factorization),
# ``fossils`` controls its budget through refinement/inner-loop parameters
# and only honours the step floor.  Forcing a method with a knob outside
# its set raises; under auto-selection unsupported knobs are dropped.
_TOL_KEYS = ("atol", "btol", "steptol", "iter_lim")
TOL_SUPPORT = {
    "direct": frozenset(),
    "lsqr": frozenset(_TOL_KEYS),
    "saa": frozenset(_TOL_KEYS),
    "sap": frozenset(_TOL_KEYS),
    "iterative": frozenset(_TOL_KEYS),
    "fossils": frozenset({"steptol"}),
}

# The certified tier's escalation ladder: each failed certificate both
# grows the sketch (appended rows, stored B reused) and climbs one rung.
CERTIFIED_LADDER = ("saa", "iterative", "fossils", "direct")

# Methods whose factor build honours ``precision=``/``fused=`` (the sketched
# solvers that go through ``SketchedFactor.build``).  ``sap``/``lsqr``/
# ``direct`` never sketch-and-factor this way: forcing one of them together
# with ``precision="mixed"`` raises, auto-selection falls back to full.
PRECISION_SUPPORT = frozenset({"saa", "iterative", "fossils"})


def select_method(
    m: int,
    n: int,
    *,
    has_key: bool = True,
    accuracy: str = "balanced",
    sketch_size: int | None = None,
    matrix_free: bool = False,
) -> str:
    """Pick a solver from shape, sketch-size regime and requested accuracy.

    ``matrix_free=True`` (sparse / operator inputs) rules out ``direct``:
    the iterative sketched solvers only take products with A, which is the
    whole point of those inputs.

    For ridge problems callers must pass the ORIGINAL data shape, not the
    augmented ``(m + n, n)`` one — ``lstsq(reg=λ)`` does so since the
    regime tests would otherwise see an inflated m.  Nearly-square and
    underdetermined shapes (where ``default_sketch_size`` clamps to
    s = m and no embedding can shrink the row space) always fail the
    regime test and route to ``direct``/``lsqr``.
    """
    if accuracy not in _SKETCHED_BY_ACCURACY:
        raise ValueError(
            f"select_method picks a single solver; accuracy must be one of "
            f"{tuple(_SKETCHED_BY_ACCURACY)} (the 'certified' tier runs its "
            f"own escalation ladder), got {accuracy!r}"
        )
    s = sketch_size if sketch_size is not None else default_sketch_size(n, m)
    # The sketched solvers need the embedding to actually shrink the row
    # space: s rows must both dominate n and be a small fraction of m.
    regime_ok = (s >= n + 1) and (m >= 2 * s) and (m >= 4 * n)
    if matrix_free:
        if has_key and regime_ok:
            return _SKETCHED_BY_ACCURACY[accuracy]
        return "lsqr"
    big = m * n * n > DIRECT_FLOP_CUTOFF
    if big and regime_ok and has_key:
        return _SKETCHED_BY_ACCURACY[accuracy]
    if big and not has_key:
        return "lsqr"
    return "direct"


@jax.jit
def _direct_result(A, b):
    x = qr_solve(A, b)
    r = b - matmul(A, x)
    return SolveResult(
        x=x,
        istop=jnp.asarray(1, jnp.int32),
        itn=jnp.asarray(0, jnp.int32),
        rnorm=jnp.linalg.norm(r),
        arnorm=jnp.linalg.norm(matmul(A.T, r)),
        used_fallback=jnp.asarray(False),
    )


@jax.jit
def _ridge_diagnostics(A, b, x, reg):
    """(rnorm, arnorm) of the ORIGINAL ridge problem at x."""
    r = b - A.matvec(x)
    g = A.rmatvec(r) - reg * x
    return jnp.linalg.norm(r), jnp.linalg.norm(g)


def _certified_lstsq(
    A_in,
    A_op,
    b_solve,
    key,
    *,
    sketch,
    sketch_size,
    backend,
    tol,
    history,
    rtol,
    n_probes,
    precision="full",
    fused=None,
):
    """The adaptive certified driver: solve → certify → escalate.

    One factor is built at the initial sketch size; every escalation
    APPENDS rows to it (``SketchedFactor.extend`` — only the new rows are
    sketched, the stored B is reused bit-for-bit) and climbs one rung of
    :data:`CERTIFIED_LADDER`.  Returns ``(result, method_name)`` for the
    first certificate that passes, else the attempt with the smallest
    posterior error bound (its certificate carries ``passed=False``).

    With ``precision="mixed"`` the FIRST escalation move is a precision
    escalation, not a size/method one: the SAME sketch operator is
    re-applied at full precision (cheap — one sketch apply, no new QR
    rows) and the SAME rung retried.  A bf16-rounded sketch loses the
    embedding only through rounding, so when its certificate fails,
    restoring precision is the targeted repair; only if the full-precision
    retry also fails does the driver resume the size/method ladder.  Each
    certificate records the precision its factor was built at.
    """
    m_data, n = A_in.shape
    dtype = A_op.dtype
    steptol = tol.get("steptol")
    if steptol is None:
        steptol = 32 * float(jnp.finfo(dtype).eps)
    atol = tol.get("atol", 0.0)
    btol = tol.get("btol", 0.0)
    iter_lim = tol.get("iter_lim", 100)
    dense_input = isinstance(A_in, linop.DenseOperator)

    k_build, k_loop = jax.random.split(key)
    s = (
        sketch_size
        if sketch_size is not None
        else default_sketch_size(n, m_data)
    )
    factor, op, B = SketchedFactor.build_full(
        A_op, k_build, sketch=sketch, sketch_size=s, backend=backend,
        precision=precision, fused=fused,
    )
    prec_now = precision
    escalations = 0
    best = None  # (bound, result, method) of the best failed attempt

    rung = 0
    attempt = 0
    while rung < len(CERTIFIED_LADDER):
        meth = CERTIFIED_LADDER[rung]
        k_probe, k_ext = jax.random.split(jax.random.fold_in(k_loop, attempt))
        attempt += 1
        rung_span = obs_trace.span(
            "certified.rung", method=meth, attempt=attempt - 1,
            sketch_rows=s, precision=prec_now,
        )
        with rung_span:
            if meth == "direct":
                if not dense_input:
                    # Sparse and matrix-free inputs stop at the fossils rung —
                    # the whole point of those input forms is that A is never
                    # densified (BCOO is technically materializable, but an
                    # 8 GB todense() is not a fallback).
                    break
                res = _direct_result(
                    linop.ensure_dense(A_op, who="the certified QR fallback"),
                    b_solve,
                )
            elif meth == "saa":
                c = op.apply(b_solve, backend=backend)
                x, inner = _solve_with_factor(
                    A_op, b_solve, factor, c, materialize_y=dense_input,
                    atol=atol, btol=btol, iter_lim=iter_lim, steptol=steptol,
                    history=history,
                )
                res = inner._replace(x=x)
            else:
                alpha, beta = damping_momentum(s, n)
                x0 = factor.sketch_and_solve(op.apply(b_solve, backend=backend))
                if meth == "iterative":
                    res = heavy_ball_refine(
                        A_op, b_solve, factor, x0, alpha, beta,
                        atol=atol, btol=btol, steptol=steptol,
                        iter_lim=iter_lim, backend=backend, history=history,
                    )
                    if rung_span:
                        rung_span.set(one_pass=linop.one_pass(
                            A_op, b_solve, backend=backend))
                else:  # fossils
                    res = fossils_refine(
                        A_op, b_solve, factor, op, x0, alpha, beta,
                        inner_iter_lim=default_inner_iter_lim(beta, dtype),
                        steptol=steptol, backend=backend, history=history,
                    )
            obs_trace.maybe_block(res.x)
            cert = certify_lib.certify(
                A_op, b_solve, res.x, factor, k_probe, n_probes=n_probes,
                target=rtol, sketch_rows=s, escalations=escalations,
                precision=prec_now,
            )
            res = res._replace(certificate=cert)
            passed = bool(cert.passed)
            if rung_span:
                rung_span.set(
                    passed=passed, bound=float(cert.rel_error_bound)
                )
        if passed:
            return res, meth
        bound = float(cert.rel_error_bound)
        if not math.isfinite(bound):
            bound = math.inf
        if best is None or bound < best[0]:
            best = (bound, res, meth)
        if prec_now == "mixed" and meth != "direct":
            # Precision escalation: re-apply the SAME operator at full
            # precision (one sketch apply, no extra rows) and retry this
            # rung — the cheapest repair when bf16 rounding alone broke
            # the embedding.
            with obs_trace.span("certified.precision_escalate", rows=s):
                B = op.apply_op(A_op, backend=backend)
                factor = SketchedFactor.from_sketch(B)
                obs_trace.maybe_block(factor.R)
            prec_now = "full"
            escalations += 1
            continue
        # Escalate before the next sketched rung: double the sketch by
        # appending rows, capped at the data row count (beyond which a
        # sketch embeds nothing an exact method wouldn't).
        if rung + 1 < len(CERTIFIED_LADDER):
            extra = min(s, max(m_data - s, 0))
            if extra > 0 and CERTIFIED_LADDER[rung + 1] != "direct":
                with obs_trace.span("certified.escalate", extra=extra):
                    factor, op, B = factor.extend(
                        A_op, op, k_ext, extra, B=B, backend=backend
                    )
                    obs_trace.maybe_block(factor.R)
                s += extra
                escalations += 1
        rung += 1

    _, res, meth = best
    return res, meth


def lstsq(
    A,
    b: jax.Array,
    key: jax.Array | None = None,
    *,
    method: str = "auto",
    accuracy: str = "balanced",
    sketch: str = "clarkson_woodruff",
    sketch_size: int | None = None,
    reg: float | jax.Array | None = None,
    atol: float | None = None,
    btol: float | None = None,
    steptol: float | None = None,
    iter_lim: int | None = None,
    backend: str = "auto",
    precision: str = "full",
    fused: bool | None = None,
    history: bool = False,
    certified_rtol: float | None = None,
    certified_probes: int = 8,
    cluster=None,
    trace: bool | None = None,
) -> SolveResult:
    """Solve min‖Ax − b‖₂ (+ λ‖x‖₂² with ``reg=λ``) with an auto-selected
    (or forced) solver.

    ``trace=True`` records a nested wall-clock span timeline for this call
    (method selection, sketch vs QR, refinement, certificate rungs — and,
    through the streaming/cluster delegations, tiles and worker tasks) and
    attaches it as ``SolveResult.timeline`` (a
    :class:`repro.obs.trace.Timeline`; ``str(...)`` renders the tree,
    ``.save(path)`` writes Chrome-trace JSON).  With ``REPRO_TRACE=1`` (or
    inside ``repro.obs.tracing()``) the timeline is attached without the
    flag; ``trace=None`` (default) otherwise records nothing and costs
    nothing.

    ``precision="mixed"`` sketches a bf16-rounded copy of (dense) A with
    ≥ f32 accumulation; refinement stays full-precision and recovers full
    working accuracy for moderately conditioned problems, while the
    ``accuracy="certified"`` tier *verifies* recovery and escalates back
    to full precision when rounding broke the embedding.  ``fused`` routes
    factor builds through the fused sketch→QR pipeline
    (``repro.kernels.tsqr.sketch_qr``; ``None`` → ``REPRO_FUSED_QR`` env,
    default off).  Both knobs apply to the sketched methods
    (:data:`PRECISION_SUPPORT`); forcing any other method with
    ``precision="mixed"`` raises, auto-selection just runs it at full
    precision.

    ``A``: dense array, BCOO sparse matrix, or ``linop.LinearOperator``.
    ``atol``/``btol``/``steptol``/``iter_lim`` left as ``None`` use each
    solver's own defaults.  Forwarding is audited against ``TOL_SUPPORT``:
    forcing a method alongside a knob it does not consume (``direct`` takes
    none; ``fossils`` only ``steptol``) raises ``ValueError``; under
    ``method="auto"`` unsupported knobs are dropped for the selected
    solver.

    ``accuracy="certified"`` (``method="auto"`` only) runs the adaptive
    certified driver: solve, certify with the posterior estimators of
    ``repro.core.certify``, and on failure escalate sketch size and method
    (see :data:`CERTIFIED_LADDER`).  ``certified_rtol`` is the relative
    forward-error target (``None`` → the adaptive QR-attainable default);
    ``certified_probes`` sets the distortion probe count.  The returned
    ``SolveResult.certificate`` carries the final posterior bound.

    ``cluster=ClusterSpec(...)`` runs the streaming path across a
    fault-tolerant multi-worker pool with checkpointable sketch state
    (``repro.cluster``); it implies the streaming path, so a plain array
    ``A`` is coerced to a row source first.
    """
    scope = obs_trace.solve_scope(trace)
    with scope:
        root = obs_trace.span("lstsq", accuracy=accuracy)
        with root:
            res = _lstsq_impl(
                A, b, key, method=method, accuracy=accuracy, sketch=sketch,
                sketch_size=sketch_size, reg=reg, atol=atol, btol=btol,
                steptol=steptol, iter_lim=iter_lim, backend=backend,
                precision=precision, fused=fused, history=history,
                certified_rtol=certified_rtol,
                certified_probes=certified_probes, cluster=cluster,
            )
            if root and res.method:
                root.set(method=res.method)
    return scope.attach(res)


def _given(**tol):
    """The tolerance knobs the caller set (``None`` leaves a solver's own
    default)."""
    return {k: v for k, v in tol.items() if v is not None}


# The accuracies the mesh path's one method, SAA-SAS, serves.
_MESH_ACCURACIES = ("fast", "balanced")


def _mesh_lstsq(A, b, key, mesh_rows, *, method, accuracy, sketch,
                sketch_size, reg, atol, btol, steptol, iter_lim, backend,
                precision, fused, history, cluster) -> SolveResult:
    """``A`` row-sharded over ``mesh_rows = (mesh, axes)``: the distributed
    SAA-SAS, or a ``ValueError`` for what it has no form for."""
    mesh, axes = mesh_rows
    method = _ALIASES.get(method, method)
    refused = []
    if method not in ("auto", "saa"):
        refused.append(f"method={method!r}")
    if accuracy not in _MESH_ACCURACIES:
        refused.append(f"accuracy={accuracy!r}")
    if reg is not None:
        refused.append("reg=")
    if precision != "full":
        refused.append(f"precision={precision!r}")
    if cluster is not None:
        refused.append("cluster=")
    if fused:
        refused.append("fused=True")
    if history:
        refused.append("history=True")
    if SKETCH_KINDS.get(sketch) not in distributed.ROW_SKETCHES:
        refused.append(f"sketch={sketch!r}")
    if key is None:
        refused.append("key=None")
    if refused:
        kinds = sorted(k for k, c in SKETCH_KINDS.items()
                       if c in distributed.ROW_SKETCHES)
        raise ValueError(
            f"A is row-sharded over {axes} of its mesh; the mesh path "
            f"(row-sharded SAA-SAS) cannot take {', '.join(refused)}. It "
            f"supports method 'auto' or 'saa', accuracy in "
            f"{_MESH_ACCURACIES}, full precision, a PRNG key, the sketch "
            f"kinds {kinds}, sketch_size, atol/btol/steptol/iter_lim and "
            "backend. Gather A onto one device yourself to use the rest."
        )
    m, n = A.shape
    with obs_trace.span("lstsq.select", m=m, n=n, accuracy=accuracy,
                        chips=distributed.mesh_size(mesh, axes),
                        method="saa"):
        pass
    b_rows = NamedSharding(mesh, P(axes))
    if getattr(b, "sharding", None) != b_rows:
        b = jax.device_put(b, b_rows)
    tol = _given(atol=atol, btol=btol, steptol=steptol, iter_lim=iter_lim)
    with obs_trace.span("lstsq.solve", method="saa") as sp:
        res = distributed.sketched_lstsq(
            A, b, key, mesh=mesh, axes=axes, sketch=sketch,
            sketch_size=sketch_size, backend=backend, **tol,
        )
        if sp:
            sp.set(itn=int(res.itn))
    return res


def _lstsq_impl(
    A,
    b,
    key,
    *,
    method,
    accuracy,
    sketch,
    sketch_size,
    reg,
    atol,
    btol,
    steptol,
    iter_lim,
    backend,
    precision,
    fused,
    history,
    certified_rtol,
    certified_probes,
    cluster,
) -> SolveResult:
    if accuracy not in ACCURACIES:
        raise ValueError(f"unknown accuracy {accuracy!r}; have {ACCURACIES}")
    if precision not in backend_lib.PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; have {backend_lib.PRECISIONS}"
        )
    mesh_rows = distributed.row_sharding(A)
    if mesh_rows is not None:
        return _mesh_lstsq(
            A, b, key, mesh_rows, method=method, accuracy=accuracy,
            sketch=sketch, sketch_size=sketch_size, reg=reg, atol=atol,
            btol=btol, steptol=steptol, iter_lim=iter_lim, backend=backend,
            precision=precision, fused=fused, history=history,
            cluster=cluster,
        )
    if cluster is not None and not callable(getattr(A, "tiles", None)):
        # cluster solving is a streaming mode: coerce in-memory inputs
        from ..streaming.sources import as_source as _as_source

        A = _as_source(A)
    if callable(getattr(A, "tiles", None)):
        # Row-streamed (out-of-core) input: delegate to the two-pass
        # streaming drivers.  Lazy import — repro.streaming imports this
        # package, so a top-level import would be circular.  A forced
        # method composes with accuracy="certified" here: streams have no
        # escalation ladder, certification just rides along.
        from ..streaming.solve import stream_lstsq as _stream_lstsq

        tol = _given(atol=atol, btol=btol, steptol=steptol,
                     iter_lim=iter_lim)
        return _stream_lstsq(
            A, b, key, method=method, sketch=sketch,
            sketch_size=sketch_size, reg=reg, backend=backend,
            history=history, certify=accuracy == "certified",
            certified_rtol=certified_rtol, certified_probes=certified_probes,
            cluster=cluster, **tol,
        )
    A_in = linop.as_operator(A)
    if reg is not None:
        A_op = linop.TikhonovAugmented.wrap(A_in, reg)
        b_solve = A_op.augment_rhs(b)
    else:
        A_op, b_solve = A_in, b
    matrix_free = not isinstance(A_in, linop.DenseOperator)

    # Select on the ORIGINAL data shape: with reg=λ the solver sees the
    # augmented (m + n, n) operator, but its extra √λ·I rows are exact
    # (never sketched) and must not inflate m in the regime tests.
    m, n = A_in.shape
    method = _ALIASES.get(method, method)
    forced = method != "auto"

    tol = _given(atol=atol, btol=btol, steptol=steptol, iter_lim=iter_lim)

    if accuracy == "certified":
        if forced:
            raise ValueError(
                "accuracy='certified' drives its own method ladder "
                f"{CERTIFIED_LADDER}; don't force method={method!r}"
            )
        if key is None:
            raise ValueError("accuracy='certified' needs a PRNG key")
        res, used = _certified_lstsq(
            A_in, A_op, b_solve, key, sketch=sketch,
            sketch_size=sketch_size, backend=backend, tol=tol,
            history=history, rtol=certified_rtol, n_probes=certified_probes,
            precision=precision, fused=fused,
        )
        if reg is not None:
            rnorm, arnorm = _ridge_diagnostics(
                A_in, b, res.x, jnp.asarray(reg, A_in.dtype)
            )
            res = res._replace(rnorm=rnorm, arnorm=arnorm)
        return res._replace(method=used)

    if method == "auto":
        with obs_trace.span(
            "lstsq.select", m=m, n=n, accuracy=accuracy
        ) as sel:
            method = select_method(
                m, n, has_key=key is not None, accuracy=accuracy,
                sketch_size=sketch_size, matrix_free=matrix_free,
            )
            if sel:
                sel.set(method=method)
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; have {('auto',) + METHODS}")
    if method in ("saa", "sap", "iterative", "fossils") and key is None:
        raise ValueError(f"method {method!r} needs a PRNG key")

    unsupported = sorted(set(tol) - TOL_SUPPORT[method])
    if unsupported:
        if forced:
            supported = sorted(TOL_SUPPORT[method]) or ["(none)"]
            raise ValueError(
                f"method {method!r} does not consume {unsupported}; it "
                f"supports {supported} — drop the unsupported knobs or let "
                "method='auto' do so"
            )
        # auto-selected: drop explicitly rather than silently absorb
        for k in unsupported:
            tol.pop(k)
    sk = dict(sketch=sketch, sketch_size=sketch_size, backend=backend)
    if method in PRECISION_SUPPORT:
        sk.update(precision=precision, fused=fused)
    elif precision != "full":
        if forced:
            raise ValueError(
                f"method {method!r} does not sketch through "
                "SketchedFactor.build and cannot honour precision="
                f"{precision!r}; supported: {sorted(PRECISION_SUPPORT)}"
            )
        precision = "full"  # auto-selected a non-sketched method: run full

    with obs_trace.span("lstsq.solve", method=method) as sp:
        if method == "direct":
            res = _direct_result(
                linop.ensure_dense(A_op, who="method='direct'"), b_solve
            )
        elif method == "lsqr":
            res = lsqr_operator(A_op, b_solve, history=history, **tol)
        elif method == "saa":
            res = saa_sas(A_op, b_solve, key, history=history, **sk, **tol)
        elif method == "sap":
            res = sap_sas(A_op, b_solve, key, history=history, **sk, **tol)
        elif method == "iterative":
            res = iterative_sketching(
                A_op, b_solve, key, history=history, **sk, **tol
            )
        else:  # fossils (tol holds at most steptol after the audit above)
            res = fossils(A_op, b_solve, key, history=history, **sk, **tol)
        obs_trace.maybe_block(res.x)
        if sp:
            sp.set(itn=int(res.itn))
            if method == "iterative":
                sp.set(one_pass=linop.one_pass(A_op, b_solve, backend=backend))

    if reg is not None:
        # Report diagnostics of the ORIGINAL problem, not the augmented one.
        rnorm, arnorm = _ridge_diagnostics(
            A_in, b, res.x, jnp.asarray(reg, A_in.dtype)
        )
        res = res._replace(rnorm=rnorm, arnorm=arnorm)
    return res._replace(method=method)
