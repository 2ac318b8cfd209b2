"""The sketched QR factor — the one reusable object behind every solver.

The paper's speed/accuracy claims all rest on the same construction: draw a
subspace embedding S (s×m, s ≪ m), sketch B = SA, and take the (reduced,
Householder) QR factor B = QR.  The triangular R is then simultaneously

- a **right preconditioner**: A R⁻¹ has all singular values in
  [1/(1+ε), 1/(1−ε)] w.h.p., where ε is the embedding distortion — so any
  Krylov or gradient iteration on the *whitened* operator Y = A R⁻¹
  converges at a κ-independent rate; and
- a **coordinate change** back to x-space: x = R⁻¹ z.

Before this module the sketch → QR → triangular-solve plumbing was copied
near-identically into ``saa.py`` (twice), ``sap.py`` and ``distributed.py``.
:class:`SketchedFactor` names it once; SAA-SAS, SAP-SAS, the batched and
distributed drivers, and the forward-stable solvers in
``repro.core.iterative`` are all built on it.

``SketchedFactor`` is a NamedTuple of arrays, hence a JAX pytree: it can be
carried through ``jit``, ``vmap`` (the batched solver), ``lax.cond`` (the
SAA fallback) and ``shard_map`` (the distributed driver, which assembles the
sketch with a psum and then builds the factor replicated).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from . import backend as backend_lib
from . import linop
from . import sketch as sketch_lib
from ..kernels.common import matmul
from ..obs import trace as obs_trace

__all__ = ["SketchedFactor", "default_sketch_size", "distortion"]


def _lowp_operator(A, use_pallas: bool):
    """bf16-rounded copy of a dense(-backed) operator for the mixed sketch.

    Under pallas the bf16 array feeds the kernels directly (they accumulate
    in f32 and now *return* f32 for half inputs); under the reference
    backend the data is rounded to bf16 then upcast so XLA matmuls also
    accumulate in ≥ f32.  Only dense data admits the cast — sparse/custom
    operators raise, and the caller (or the certified driver) falls back to
    ``precision="full"``.
    """

    def cast(arr):
        low = arr.astype(jnp.bfloat16)
        return low if use_pallas else low.astype(jnp.float32)

    if isinstance(A, linop.DenseOperator):
        return linop.DenseOperator(A=cast(A.A))
    if isinstance(A, linop.TikhonovAugmented) and isinstance(
        A.op, linop.DenseOperator
    ):
        return linop.TikhonovAugmented.wrap(cast(A.op.A), A.reg)
    raise ValueError(
        "precision='mixed' needs a dense data matrix (or Tikhonov-augmented "
        f"dense); got {type(A).__name__}"
    )


def _sketch_apply(op, A, *, backend: str, precision: str):
    """B = S·A honouring ``precision`` — the unfused sketch-apply stage.

    Mixed precision rounds the data to bf16 before the apply and returns B
    in A's working dtype: the *sketch* is cheap/low-precision, everything
    downstream (QR, refinement, certificates) stays full precision.
    """
    A = linop.as_operator(A)
    if precision == "mixed":
        rb = backend_lib.resolve(backend)
        B = op.apply_op(_lowp_operator(A, rb.use_pallas), backend=backend)
        return B.astype(A.dtype)
    return op.apply_op(A, backend=backend)


def default_sketch_size(n: int, m: int) -> int:
    """Paper regime: m ≫ s > n.  s = 4n is the usual CW sweet spot.

    Clamped to s ≤ m: for nearly-square or underdetermined shapes the
    ``max(m // 2, n + 1)`` branch used to exceed m, building an over-tall
    sketch that embeds nothing (``select_method`` routes such shapes to
    ``direct``/``lsqr`` — the regime test ``s ≥ n + 1`` can then only pass
    when the sketch genuinely shrinks the row space).
    """
    s = int(min(max(4 * n, n + 16), max(m // 2, n + 1)))
    return max(min(s, m), 1)


def distortion(sketch_size: int, n: int) -> float:
    """A-priori embedding distortion estimate ε ≈ √(n/s).

    For the dense and CountSketch-style embeddings at s = Θ(n) this is the
    right order for the subspace distortion w.h.p.; it is what the damping
    and momentum coefficients of ``repro.core.iterative`` are derived from
    (Epperly 2024).  Clipped away from 1 so downstream rate formulas stay
    finite even for aggressive (s ≈ n) sketches.
    """
    return min((n / float(sketch_size)) ** 0.5, 0.99)


class SketchedFactor(NamedTuple):
    """QR factor of a sketch SA: preconditioner, whitener and warm-starter.

    ``Q`` is (s, n) with orthonormal columns, ``R`` is (n, n) upper
    triangular with B = SA = QR.  All methods are linear-algebra one-liners;
    they exist so every solver spells the same operation the same way.
    """

    Q: jax.Array  # (s, n) orthonormal columns of the sketched matrix
    R: jax.Array  # (n, n) upper triangular

    # ---------------------------------------------------------------- build
    @classmethod
    def from_sketch(cls, B: jax.Array) -> "SketchedFactor":
        """Factor an already-assembled sketch B = SA (HHQR)."""
        Q, R = jnp.linalg.qr(B, mode="reduced")
        return cls(Q=Q, R=R)

    @classmethod
    def build(
        cls,
        A,
        key: jax.Array,
        *,
        sketch: str = "clarkson_woodruff",
        sketch_size: int | None = None,
        backend: str = "auto",
        precision: str = "full",
        fused: bool | None = None,
    ):
        """Draw S, sketch A and factor: returns ``(factor, op)``.

        ``A`` may be a dense array, a BCOO matrix or any
        ``repro.core.linop`` operator — the sketch applies through
        ``op.apply_op`` (sparse inputs are sketched without densifying).
        The sketch operator ``op`` is returned so callers can sketch the
        right-hand side (``op.apply(b)`` → warm start) or re-sketch a
        perturbed matrix (the SAA fallback) with the SAME S.

        ``precision="mixed"`` sketches a bf16-rounded copy of a *dense* A
        (accumulating in ≥ f32); the factor comes back in A's dtype for the
        refinement loops, which recover — and the certificates verify —
        full working accuracy.  ``fused`` routes the build through the
        fused ``sketch_qr`` pipeline (``None`` → ``REPRO_FUSED_QR`` env,
        default off).
        """
        factor, op, _ = cls.build_full(
            A, key, sketch=sketch, sketch_size=sketch_size, backend=backend,
            precision=precision, fused=fused,
        )
        return factor, op

    @classmethod
    def build_full(
        cls,
        A,
        key: jax.Array,
        *,
        sketch: str = "clarkson_woodruff",
        sketch_size: int | None = None,
        backend: str = "auto",
        precision: str = "full",
        fused: bool | None = None,
    ):
        """:meth:`build` that also returns the assembled sketch:
        ``(factor, op, B)``.  The adaptive certified driver keeps B so a
        later :meth:`extend` reuses the stored rows bit-for-bit instead of
        re-sketching A."""
        if precision not in backend_lib.PRECISIONS:
            raise ValueError(
                f"unknown precision {precision!r}; have {backend_lib.PRECISIONS}"
            )
        A = linop.as_operator(A)
        if isinstance(A, linop.TikhonovAugmented):
            # Structured embedding blockdiag(S, I): sketch the data rows,
            # keep the (maximally coherent) regularization rows exact —
            # see sketch.AugmentedSketch for why random bucketing of the
            # identity block destroys the embedding.
            m_in, n = A.op.shape
            s = (
                sketch_size
                if sketch_size is not None
                else default_sketch_size(n, m_in)
            )
            inner = sketch_lib.sample(sketch, key, s, m_in, dtype=A.dtype)
            op = sketch_lib.AugmentedSketch(inner=inner, tail=n)
        else:
            m, n = A.shape
            s = (
                sketch_size
                if sketch_size is not None
                else default_sketch_size(n, m)
            )
            op = sketch_lib.sample(sketch, key, s, m, dtype=A.dtype)
        if backend_lib.resolve_fused(fused):
            from ..kernels.tsqr import sketch_qr  # kernels import core

            with obs_trace.span("factor.build", sketch=sketch, rows=s,
                                fused=True):
                Q, R, B = sketch_qr(op, A, backend=backend,
                                    precision=precision)
                obs_trace.maybe_block(R)
            return cls(Q=Q, R=R), op, B
        with obs_trace.span("factor.build", sketch=sketch, rows=s,
                            fused=False):
            with obs_trace.span("sketch.apply", kind=sketch,
                                precision=precision):
                B = _sketch_apply(op, A, backend=backend,
                                  precision=precision)
                obs_trace.maybe_block(B)
            with obs_trace.span("factor.qr", shape=tuple(B.shape)):
                factor = cls.from_sketch(B)
                obs_trace.maybe_block(factor.R)
        return factor, op, B

    @classmethod
    def build_streaming(
        cls,
        source,
        key: jax.Array,
        *,
        sketch: str = "clarkson_woodruff",
        sketch_size: int | None = None,
        backend: str = "auto",
    ):
        """Build the factor from a row-streamed A: returns ``(factor, op)``.

        ``source`` is anything ``repro.streaming.sources.as_source``
        accepts (RowSource, array, ``.npy`` path).  One pass over the
        tiles assembles B = SA through the mergeable accumulators of
        ``repro.streaming.accumulate`` — A is never resident; with the
        same ``key`` the operator draw is bit-identical to :meth:`build`
        on the materialized matrix.
        """
        from ..streaming.solve import stream_sketch  # streaming imports us

        B, op, _ = stream_sketch(
            source, key, sketch=sketch, sketch_size=sketch_size,
            backend=backend,
        )
        return cls.from_sketch(B), op

    # ----------------------------------------------------------- escalation
    def extend(
        self,
        A,
        op,
        key: jax.Array,
        extra: int,
        *,
        B: jax.Array | None = None,
        backend: str = "auto",
    ):
        """Grow the sketch by ``extra`` appended rows and re-QR.

        The adaptive repair move of ``lstsq(accuracy="certified")``: when a
        certificate fails, the embedding is escalated by appending fresh
        rows to S (``op.extend_rows`` — a weighted stack that embeds like a
        from-scratch draw at the larger size) and only those new rows are
        ever applied to A.  ``B`` is the stored sketch this factor was
        built from (``build_full``); when omitted it is reconstructed as
        Q·R (exact to rounding — pass B for the bit-exact path).  Returns
        ``(factor, op_new, B_new)``; the cost is one ``extra``-row sketch
        apply plus one (d + extra) × n QR, never a full re-sketch.
        """
        A = linop.as_operator(A)
        with obs_trace.span("factor.extend", extra=extra):
            op_new = op.extend_rows(key, extra)
            if B is None:
                B = matmul(self.Q, self.R)
            B_new = op_new.extend_sketch(B, A, backend=backend)
            with obs_trace.span("factor.qr", shape=tuple(B_new.shape)):
                factor = SketchedFactor.from_sketch(B_new)
                obs_trace.maybe_block(factor.R)
        return factor, op_new, B_new

    # ------------------------------------------------------------ shape info
    @property
    def n(self) -> int:
        return self.R.shape[-1]

    @property
    def sketch_size(self) -> int:
        return self.Q.shape[-2]

    # ------------------------------------------------- triangular primitives
    def precondition(self, z: jax.Array) -> jax.Array:
        """x = R⁻¹ z — z-space (whitened) back to x-space (back substitution)."""
        return solve_triangular(self.R, z, lower=False)

    def rt_solve(self, v: jax.Array) -> jax.Array:
        """R⁻ᵀ v (forward substitution on the lower-triangular Rᵀ)."""
        return solve_triangular(self.R, v, trans=1, lower=False)

    # --------------------------------------------------- whitened operator Y
    def whiten_mv(self, A, z: jax.Array) -> jax.Array:
        """Y z = A (R⁻¹ z) — operator-form matvec of the whitened system.

        ``A`` may be an array, a BCOO matrix or a linop operator (so the
        whitened system is matrix-free whenever A is)."""
        return linop.as_operator(A).matvec(self.precondition(z))

    def whiten_rmv(self, A, u: jax.Array) -> jax.Array:
        """Yᵀ u = R⁻ᵀ (Aᵀ u) — operator-form rmatvec of the whitened system."""
        return self.rt_solve(linop.as_operator(A).rmatvec(u))

    def materialize_whitened(self, A) -> jax.Array:
        """Y = A R⁻¹ explicitly (one n×n triangular solve against Aᵀ).

        O(mn) extra memory; trades the two triangular solves per iteration
        of the operator form for plain matmuls (the fast path when Y fits).
        For non-dense operators Y is assembled as A·R⁻¹ (n matvecs' worth
        of work, e.g. one O(nnz·n) product for BCOO inputs).
        """
        A = linop.as_operator(A)
        if isinstance(A, linop.DenseOperator):
            return self.rt_solve(A.A.T).T
        r_inv = solve_triangular(
            self.R, jnp.eye(self.n, dtype=self.R.dtype), lower=False
        )
        return A.matmat(r_inv)

    # ------------------------------------------------------------ warm start
    def warm_start(self, c: jax.Array) -> jax.Array:
        """z₀ = Qᵀ c with c = Sb — the sketch-and-solve solution in z-space.

        This is the minimizer of the *sketched* problem min‖B z − c‖, an
        O(ε)-accurate starting point for any iteration on the whitened
        system; using it is what makes the preconditioned solve start a
        constant factor from optimal rather than from zero.
        """
        return matmul(self.Q.T, c)

    def sketch_and_solve(self, c: jax.Array) -> jax.Array:
        """x̂ = R⁻¹ Qᵀ c — the plain sketch-and-solve estimate in x-space."""
        return self.precondition(self.warm_start(c))

    # ------------------------------------------------------- normal equations
    def normal_solve(self, g: jax.Array) -> jax.Array:
        """(RᵀR)⁻¹ g = (SA)ᵀ(SA) \\ g — the sketched-normal-equations solve.

        One forward + one back substitution; this is the per-iteration step
        of iterative sketching (``repro.core.iterative``), where
        g = Aᵀ(b − Ax) is the true gradient and RᵀR ≈ AᵀA its sketched
        Hessian.
        """
        return self.precondition(self.rt_solve(g))
