"""``SketchedSolver`` — a reusable sketch-and-solve session.

Every sketched solver pays the same precompute: draw S, sketch B = SA,
QR-factor B.  For serving-style workloads (many right-hand sides against
one design matrix, the ROADMAP's heavy-repeated-traffic scenario) that
precompute dominates, and redoing it per call — which the functional
``lstsq``/``saa_sas`` API forces — throws the amortization away.

``SketchedSolver(A, key)`` builds the :class:`repro.core.precond
.SketchedFactor` ONCE and then serves:

- ``solve(b)``        — one right-hand side against the stored factor;
- ``solve_many(B)``   — k stacked right-hand sides, LSQR vmapped over
  columns, still one factor;
- ``update_rows(idx, rows)`` — row update of A with an O(|idx|·n)
  *delta-sketch*: S is linear in the rows of A, so
  SA′ = SA + S[:, idx]·(A′[idx] − A[idx]); only the small s×n QR is redone,
  never the full sketch (SRHT has no cheap column restriction and falls
  back to re-sketching with the SAME S — still no new operator draw).

``A`` may be a dense array, a BCOO matrix or a ``repro.core.linop``
operator (``update_rows`` needs dense, since it rewrites rows in place).
``reg=λ`` serves ridge solves through the augmented operator.  ``stats``
counts the expensive events (``sketches``, ``qr_factorizations``,
``solves``) so amortization is observable — the whole point of the
session API is that ``sketches`` stays at 1 while ``solves`` grows.

Trust layer (``repro.core.certify``): ``certify()`` issues a posterior
:class:`~repro.core.certify.Certificate` for the stored factor — and,
given a solve's ``(b, result)``, a forward-error bound for that answer.
Row updates DRIFT the embedding: S was drawn obliviously to the original
A, and enough rewritten rows can degrade its quality for the new
range(A) without any bookkeeping going stale (the delta-sketch itself is
exact).  ``auto_recertify=True`` re-probes after every ``update_rows``
and, when the probe fails, escalates the sketch in place
(``SketchedFactor.extend`` — appended rows, stored B reused) until it
certifies again or the sketch reaches the data row count.

The per-call work is one sketch of b (O(m) for CountSketch), the whitened
LSQR iterations (κ-independent count) and one n×n back substitution —
exactly the marginal cost of a query in ``saa_sas_batch``, but without
needing all right-hand sides up front.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import certify as certify_lib
from . import linop
from . import sketch as sketch_lib
from ..obs import trace as obs_trace
from ..obs.metrics import REGISTRY
from .backend import resolve as resolve_backend
from .lsqr import lsqr
from .precond import SketchedFactor, default_sketch_size
from .result import SolveResult

__all__ = ["SketchedSolver"]

# key-derivation constant for the session's certification probe stream
# (disjoint from the sketch draw made with the constructor key itself)
_CERTIFY_SALT = 0x6CE7


_SOLVE_STATICS = ("atol", "btol", "steptol", "iter_lim", "backend", "history")


@partial(jax.jit, static_argnames=_SOLVE_STATICS)
def _solve_one(
    A, Y, factor, sk_op, b, *, atol, btol, steptol, iter_lim, backend, history
):
    """One RHS against a prebuilt factor (Y = None → operator-form mv/rmv)."""
    c = sk_op.apply(b, backend=backend)
    z0 = factor.warm_start(c)
    if Y is not None:
        mv, rmv = Y.matvec, Y.rmatvec
    else:
        mv = partial(factor.whiten_mv, A)
        rmv = partial(factor.whiten_rmv, A)
    res = lsqr(
        mv, rmv, b, x0=z0, n=factor.n, atol=atol, btol=btol,
        iter_lim=iter_lim, steptol=steptol, history=history,
    )
    return res._replace(
        x=factor.precondition(res.x), used_fallback=jnp.asarray(False)
    )


@partial(jax.jit, static_argnames=_SOLVE_STATICS)
def _solve_many(
    A, Y, factor, sk_op, B, *, atol, btol, steptol, iter_lim, backend, history
):
    """k stacked RHS columns, LSQR vmapped, one shared factor."""
    del history  # per-column histories are not exposed
    C = sk_op.apply(B, backend=backend)  # (s, k)
    Z0 = factor.warm_start(C)  # (n, k)
    if Y is not None:
        mv, rmv = Y.matvec, Y.rmatvec
    else:
        mv = partial(factor.whiten_mv, A)
        rmv = partial(factor.whiten_rmv, A)

    def solve_col(b_i, z0_i):
        return lsqr(
            mv, rmv, b_i, x0=z0_i, n=factor.n, atol=atol, btol=btol,
            iter_lim=iter_lim, steptol=steptol,
        )

    # Named for the profile: the batched LSQR's device ops carry "lsqr" in
    # their op_name; the RHS block's sketch above has its kernel's name.
    with jax.named_scope("lsqr"):
        res = jax.vmap(solve_col, in_axes=(1, 1))(B, Z0)
    X = factor.precondition(res.x.T)  # (n, k)
    return res._replace(x=X, used_fallback=jnp.zeros(B.shape[1], bool))


class SketchedSolver:
    """One sketch + QR, amortized over arbitrarily many solves.

    Parameters mirror ``saa_sas`` (sketch kind/size, tolerances, backend);
    ``materialize_y=None`` resolves to True for dense A (fast matmul LSQR)
    and False otherwise (operator form, A never densified).  ``reg=λ``
    builds the factor for the Tikhonov-augmented operator and zero-pads
    each right-hand side transparently.
    """

    def __init__(
        self,
        A,
        key: jax.Array,
        *,
        sketch: str = "clarkson_woodruff",
        sketch_size: int | None = None,
        reg: float | jax.Array | None = None,
        atol: float = 0.0,
        btol: float = 0.0,
        steptol: float | None = None,
        iter_lim: int = 100,
        materialize_y: bool | None = None,
        backend: str = "auto",
        auto_recertify: bool = False,
        max_distortion: float = certify_lib.DEFAULT_MAX_DISTORTION,
        certify_probes: int = 8,
    ):
        self.A = linop.as_operator(A)
        self.reg = reg
        self._solve_op = (
            linop.TikhonovAugmented.wrap(self.A, reg) if reg is not None else self.A
        )
        m, n = self.A.shape  # sketch size is set by the DATA rows
        self.sketch_size = (
            sketch_size if sketch_size is not None else default_sketch_size(n, m)
        )
        self.backend = resolve_backend(backend).name
        if steptol is None:
            steptol = 32 * float(jnp.finfo(self.A.dtype).eps)
        self._kw = dict(
            atol=atol, btol=btol, steptol=steptol, iter_lim=iter_lim,
            backend=self.backend,
        )
        if materialize_y is None:
            materialize_y = isinstance(self.A, linop.DenseOperator)
        self._materialize_y = materialize_y

        inner = sketch_lib.sample(
            sketch, key, self.sketch_size, m, dtype=self.A.dtype
        )
        # Ridge: structured blockdiag(S, I) embedding — the identity block
        # of [A; √λI] must be kept exact (see sketch.AugmentedSketch).
        self._sketch_op = (
            sketch_lib.AugmentedSketch(inner=inner, tail=n)
            if reg is not None
            else inner
        )
        self.auto_recertify = auto_recertify
        self.max_distortion = float(max_distortion)
        self.certify_probes = int(certify_probes)
        self._certify_key = jax.random.fold_in(key, _CERTIFY_SALT)
        self._certify_calls = 0
        self.certificate = None  # embedding-level cert of the CURRENT factor
        self.recertifications = 0  # auto-recertify probes taken so far
        self.escalations = 0  # sketch extensions taken by recertification

        self.stats = REGISTRY.stats_dict(
            "session", {"sketches": 0, "qr_factorizations": 0, "solves": 0}
        )
        with obs_trace.span("session.build", rows=self.sketch_size):
            with obs_trace.span("sketch.apply", kind=sketch):
                self._B = self._sketch_op.apply_op(
                    self._solve_op, backend=self.backend
                )
                obs_trace.maybe_block(self._B)
            self.stats["sketches"] += 1
            self._refactor()

    # ------------------------------------------------------------------ build
    def _refactor(self):
        """(Re)build the QR factor — and Y, if materialized — from self._B."""
        with obs_trace.span("factor.qr", shape=tuple(self._B.shape)):
            self.factor = SketchedFactor.from_sketch(self._B)
            obs_trace.maybe_block(self.factor.R)
        self._after_refactor()

    def _after_refactor(self):
        """Bookkeeping shared by every path that replaced the factor."""
        self.stats["qr_factorizations"] += 1
        self._Y = (
            linop.DenseOperator(self.factor.materialize_whitened(self._solve_op))
            if self._materialize_y
            else None
        )

    @property
    def shape(self) -> tuple[int, int]:
        return self.A.shape

    def _rhs(self, b):
        if self.reg is None:
            return b
        return self._solve_op.augment_rhs(b)

    def _set_matrix(self, A_new: jax.Array):
        """Point the session at updated dense data (rewraps the ridge op)."""
        self.A = linop.DenseOperator(A_new)
        self._solve_op = (
            linop.TikhonovAugmented.wrap(self.A, self.reg)
            if self.reg is not None
            else self.A
        )

    def _ridge_diagnostics(self, b, res: SolveResult) -> SolveResult:
        """Report rnorm/arnorm of the ORIGINAL ridge problem, matching
        lstsq(reg=...): the solvers see the augmented system, whose
        residual is inflated by the λ‖x‖² penalty term."""
        if self.reg is None:
            return res
        lam = jnp.asarray(self.reg, self.A.dtype)
        if res.x.ndim == 1:
            r = b - self.A.matvec(res.x)
            g = self.A.rmatvec(r) - lam * res.x
            axis = None
        else:  # (n, k) solve_many result, b is the original (m, k) block
            r = b - self.A.matmat(res.x)
            g = self.A.rmatmat(r) - lam * res.x
            axis = 0
        return res._replace(
            rnorm=jnp.linalg.norm(r, axis=axis),
            arnorm=jnp.linalg.norm(g, axis=axis),
        )

    # ------------------------------------------------------- certification
    def _next_probe_key(self):
        self._certify_calls += 1
        return jax.random.fold_in(self._certify_key, self._certify_calls)

    def _random_rows(self) -> int:
        """Rows of the random part of S (ridge sessions exclude the exact
        √λ·I tail — it is not part of the embedding)."""
        op = self._sketch_op
        if isinstance(op, sketch_lib.AugmentedSketch):
            return op.inner.d
        return op.d

    def certify(self, b=None, result=None, *, n_probes=None, target=None):
        """Posterior :class:`~repro.core.certify.Certificate` for the
        stored factor — or, given one solve's ``(b, result)``, for that
        specific answer (forward-error bound included).

        The embedding-level form (no arguments) is cached on
        ``self.certificate`` and is what ``auto_recertify`` refreshes
        after row updates.  Cost: ``certify_probes`` matvecs with A plus
        one n×n SVD; nothing is re-sketched.
        """
        if (b is None) != (result is None):
            raise ValueError("pass b and result together (or neither)")
        x = None
        b_solve = None
        if b is not None:
            x = result.x
            if x.ndim != 1:
                raise ValueError(
                    "certify takes one right-hand side at a time; "
                    "certify solve_many columns individually"
                )
            b_solve = self._rhs(jnp.asarray(b, self.A.dtype))
        with obs_trace.span("session.certify", with_solution=x is not None):
            cert = certify_lib.certify(
                self._solve_op, b_solve, x, self.factor,
                self._next_probe_key(),
                n_probes=(
                    self.certify_probes if n_probes is None else int(n_probes)
                ),
                target=target, max_distortion=self.max_distortion,
                sketch_rows=self._random_rows(),
                escalations=self.escalations,
            )
        if x is None:
            self.certificate = cert
        return cert

    def _escalate(self, extra: int):
        """Append ``extra`` fresh rows to S and re-QR — the stored sketch
        is extended (never recomputed), exactly the certified driver's
        escalation move."""
        with obs_trace.span("session.escalate", extra=extra):
            self.factor, self._sketch_op, self._B = self.factor.extend(
                self._solve_op, self._sketch_op, self._next_probe_key(),
                extra, B=self._B, backend=self.backend,
            )
        # extend() sketched the new rows and re-QRed internally
        self.stats["sketches"] += 1
        self._after_refactor()
        self.sketch_size = self._random_rows()
        self.escalations += 1

    def _recertify_after_update(self):
        """Probe the drifted embedding; escalate until it certifies again
        (or the sketch reaches the data row count)."""
        m = self.A.shape[0]
        cert = self.certify()
        self.recertifications += 1
        while not bool(cert.passed):
            s = self._random_rows()
            extra = min(s, m - s)
            if extra <= 0:
                break
            self._escalate(extra)
            cert = self.certify()
            self.recertifications += 1

    # ----------------------------------------------------------------- solves
    def _check_rhs(self, b: jax.Array, *, many: bool) -> jax.Array:
        """Validate a right-hand side up front — shape and dtype.

        Shape mismatches raise here with the session's expectation spelled
        out instead of surfacing as an XLA dot-dimension failure deep in
        the jitted solve.  Dtype policy: a RHS that would *promote* the
        solve away from A's dtype (f64 b against an f32 session, complex
        against real) is an error — silent promotion would recompile the
        cached executables and lie about the precision the factor was
        built at; a safely-representable RHS (f32 b, f64 A) is cast to
        A's dtype explicitly.
        """
        b = jnp.asarray(b)
        m = self.A.shape[0]
        if many:
            if b.ndim != 2 or b.shape[0] != m:
                raise ValueError(
                    f"solve_many needs B of shape ({m}, k), got {b.shape}"
                )
        else:
            if b.ndim != 1 or b.shape[0] != m:
                raise ValueError(
                    f"solve needs b of shape ({m},) matching A's row count, "
                    f"got {b.shape}"
                )
        dtype = self.A.dtype
        if b.dtype != dtype:
            if jnp.result_type(b.dtype, dtype) != dtype:
                raise TypeError(
                    f"right-hand side dtype {b.dtype} does not fit the "
                    f"session's {dtype} factor: solving would silently "
                    f"promote past the precision A was sketched at — cast "
                    f"b (or rebuild the session at {b.dtype}) explicitly"
                )
            b = b.astype(dtype)
        return b

    def solve(self, b: jax.Array, *, history: bool = False) -> SolveResult:
        """min‖Ax − b‖ against the stored factor (one whitened LSQR run)."""
        b = self._check_rhs(b, many=False)
        with obs_trace.span("session.solve") as sp:
            res = _solve_one(
                self._solve_op, self._Y, self.factor, self._sketch_op,
                self._rhs(b), history=history, **self._kw,
            )
            obs_trace.maybe_block(res.x)
            if sp:
                sp.set(itn=int(res.itn))
        self.stats["solves"] += 1
        return self._ridge_diagnostics(b, res)._replace(method="session")

    def solve_many(self, B: jax.Array) -> SolveResult:
        """k stacked right-hand sides (m, k) → x of shape (n, k).

        One sketch of B, k vmapped LSQR runs, one blocked back
        substitution — the factor is shared by construction.  (vmap-of-
        while semantics: all columns iterate until the slowest converges.)
        """
        B = self._check_rhs(B, many=True)
        B_orig = B
        if self.reg is not None:
            n = self.A.shape[1]
            B = jnp.concatenate([B, jnp.zeros((n, B.shape[1]), B.dtype)], axis=0)
        with obs_trace.span("session.solve_many", k=int(B.shape[1])):
            res = _solve_many(
                self._solve_op, self._Y, self.factor, self._sketch_op, B,
                history=False, **self._kw,
            )
            obs_trace.maybe_block(res.x)
        self.stats["solves"] += int(B.shape[1])
        return self._ridge_diagnostics(B_orig, res)._replace(method="session")

    # ---------------------------------------------------------------- updates
    def update_rows(self, idx, rows: jax.Array) -> None:
        """Replace rows ``A[idx] ← rows`` and refresh the factor in
        O(|idx|·n) sketch work + one s×n QR (no full re-sketch).

        ``idx`` must contain unique row indices.  Dense A only: the row
        rewrite itself needs entry access.
        """
        if not isinstance(self.A, linop.DenseOperator):
            raise TypeError(
                "update_rows needs a dense A (rows are rewritten in place); "
                f"got {type(self.A).__name__} — rebuild the session instead"
            )
        idx = jnp.asarray(idx)
        rows = jnp.asarray(rows, self.A.dtype)
        if rows.shape != (idx.shape[0], self.A.shape[1]):
            raise ValueError(
                f"rows must have shape ({idx.shape[0]}, {self.A.shape[1]}), "
                f"got {rows.shape}"
            )
        if int(jnp.unique(idx).shape[0]) != int(idx.shape[0]):
            # duplicates would double-count in the delta-sketch while the
            # row rewrite is last-write-wins — the stored B would silently
            # stop matching S·A and poison every later solve
            raise ValueError("idx must contain unique row indices")
        A_new = self.A.A.at[idx].set(rows)
        with obs_trace.span("session.update_rows", rows=int(idx.shape[0])):
            # Ridge sessions sketch through blockdiag(S, I); the updated
            # rows all live in the data block, so restrict the INNER sketch
            # and pad the delta-sketch with zero rows for the untouched
            # identity block.
            sk_op = self._sketch_op
            tail = 0
            if isinstance(sk_op, sketch_lib.AugmentedSketch):
                sk_op, tail = sk_op.inner, sk_op.tail
            # The sub-sketch S[:, idx] (shared with the streaming
            # accumulators and the distributed per-shard assembly); None
            # for SRHT.
            sub = sk_op.restrict_cols(idx)
            if sub is None:
                # SRHT: no column restriction — re-sketch with the SAME S.
                self._set_matrix(A_new)
                self._B = self._sketch_op.apply_op(
                    self._solve_op, backend=self.backend
                )
                self.stats["sketches"] += 1
            else:
                delta = rows - self.A.A[idx]
                d_sk = sub.apply(delta, backend=self.backend)
                if tail:
                    d_sk = jnp.concatenate(
                        [d_sk, jnp.zeros((tail, d_sk.shape[1]), d_sk.dtype)],
                        axis=0,
                    )
                self._B = self._B + d_sk
                self._set_matrix(A_new)
            self._refactor()
        # The delta-sketch is exact, but S itself was drawn obliviously to
        # the ORIGINAL rows — its embedding quality for the new range(A)
        # must be re-established, not assumed.
        self.certificate = None
        if self.auto_recertify:
            self._recertify_after_update()
