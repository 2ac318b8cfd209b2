"""Matrix-free linear operators — the input protocol of every solver.

The solvers in ``repro.core`` only ever touch the data matrix A through
products: ``A @ x`` (matvec), ``Aᵀ @ u`` (rmatvec) and their blocked
variants.  Nothing in the sketch-and-solve analysis requires A to be a
materialized dense array — sparse and implicitly-defined problems are
exactly where sketching wins biggest.  This module names that contract:

- :class:`LinearOperator` — the protocol: ``shape``, ``dtype``,
  ``matvec``/``rmatvec`` (vectors), ``matmat``/``rmatmat`` (blocks),
  ``materialize`` (dense A, when possible).
- :class:`DenseOperator` — wraps a ``jax.Array`` (the classical path; all
  solvers route dense inputs through it unchanged).
- :class:`SparseOperator` — wraps a ``jax.experimental.sparse`` BCOO
  matrix; products cost O(nnz) and A is never densified by the iterative
  solvers.
- :class:`TikhonovAugmented` — the ridge operator [A; √λ·Iₙ] behind
  ``lstsq(..., reg=λ)``: min‖Ax − b‖² + λ‖x‖² as a pure least-squares
  problem on the augmented system, no new solver code.
- :class:`CustomOperator` — adapts any (matvec, rmatvec) pair, including
  SciPy-style duck-typed operators.

``as_operator`` coerces ``jax.Array | BCOO | LinearOperator | duck-typed``
into the protocol; it is idempotent and is called at the top of every
solver, so user code can pass any of the three forms anywhere.

All concrete operators are registered JAX pytrees (array payloads are
leaves, shapes/dtypes/callables are static), so they pass through ``jit``,
``vmap``, ``lax.cond`` and ``shard_map`` exactly like plain arrays do.

``estimate_2norm`` is the shared Golub–Kahan σ_max estimator (formerly
private copies in the solver modules); it works on anything
``as_operator`` accepts.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.sparse import BCOO

from ..kernels.common import matmul
from ..kernels.residual_pass.ops import fits, residual_pass
from . import backend as backend_lib

__all__ = [
    "LinearOperator",
    "DenseOperator",
    "SparseOperator",
    "TikhonovAugmented",
    "CustomOperator",
    "as_operator",
    "ensure_dense",
    "one_pass",
    "estimate_2norm",
]


def _static(default=dataclasses.MISSING):
    return dataclasses.field(metadata=dict(static=True), default=default)


class LinearOperator:
    """Protocol base: a linear map R^n → R^m known only through products.

    Subclasses define ``shape``/``dtype``/``matvec``/``rmatvec``; the
    blocked ``matmat``/``rmatmat`` default to vmapping the vector products
    (override when a faster blocked form exists).  ``materialize`` returns
    the dense A for operators that can afford it (``materializable`` says
    which) — the direct solver and the distributed driver need it, the
    iterative solvers never call it.
    """

    # -- shape info ---------------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        raise NotImplementedError

    @property
    def dtype(self):
        raise NotImplementedError

    @property
    def ndim(self) -> int:
        return 2

    # -- products -----------------------------------------------------------
    def matvec(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def rmatvec(self, u: jax.Array) -> jax.Array:
        raise NotImplementedError

    def matmat(self, X: jax.Array) -> jax.Array:
        return jax.vmap(self.matvec, in_axes=1, out_axes=1)(X)

    def rmatmat(self, U: jax.Array) -> jax.Array:
        return jax.vmap(self.rmatvec, in_axes=1, out_axes=1)(U)

    def residual_gradient(self, x: jax.Array, b: jax.Array, *,
                          backend: str = "reference"):
        """(g, rsq) = (Aᵀ(b − A x), ‖b − A x‖²): the two products a
        least-squares iteration takes each step.  ``backend`` is a
        resolved name of ``repro.core.backend``; operators with a one-pass
        form (:class:`DenseOperator`) use it under ``"pallas"``."""
        r = b - self.matvec(x)
        return self.rmatvec(r), jnp.sum(r * r)

    def __matmul__(self, other):
        other = jnp.asarray(other)
        if other.ndim == 1:
            return self.matvec(other)
        if other.ndim == 2:
            return self.matmat(other)
        raise ValueError(f"operand must be 1- or 2-D, got ndim={other.ndim}")

    # -- materialization ----------------------------------------------------
    @property
    def materializable(self) -> bool:
        return False

    def materialize(self) -> jax.Array:
        raise TypeError(
            f"{type(self).__name__} cannot be materialized to a dense array; "
            "use a matrix-free solver (lstsq picks one automatically)"
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseOperator(LinearOperator):
    """A dense ``jax.Array`` seen through the operator protocol."""

    A: jax.Array

    @property
    def shape(self):
        return self.A.shape

    @property
    def dtype(self):
        return self.A.dtype

    def matvec(self, x):
        return matmul(self.A, x)

    def rmatvec(self, u):
        return matmul(self.A.T, u)

    def matmat(self, X):
        return matmul(self.A, X)

    def rmatmat(self, U):
        return matmul(self.A.T, U)

    def residual_gradient(self, x, b, *, backend="reference"):
        """Under ``backend="pallas"``, a float32 A with vectors x and b
        (``one_pass``) is read once (``kernels.residual_pass``); anything
        else takes the two products."""
        if (one_pass(self, b, backend=backend) and jnp.ndim(x) == 1
                and jnp.result_type(x) == jnp.float32):
            return residual_pass(
                self.A, x, b,
                interpret=backend_lib.resolve(backend).interpret)
        return super().residual_gradient(x, b)

    @property
    def materializable(self):
        return True

    def materialize(self):
        return self.A


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseOperator(LinearOperator):
    """A ``jax.experimental.sparse`` BCOO matrix: O(nnz) products.

    The sparse sketches (CountSketch, sparse-sign, uniform-sparse) sketch a
    ``SparseOperator`` sparse-to-sparse, so A is never densified anywhere
    in the sketched-solver pipeline.
    """

    M: BCOO

    @property
    def shape(self):
        return self.M.shape

    @property
    def dtype(self):
        return self.M.dtype

    @property
    def nse(self) -> int:
        return self.M.nse

    def matvec(self, x):
        return self.M @ x

    def rmatvec(self, u):
        return self.M.T @ u

    def matmat(self, X):
        return self.M @ X

    def rmatmat(self, U):
        return self.M.T @ U

    @property
    def materializable(self):
        return True

    def materialize(self):
        return self.M.todense()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TikhonovAugmented(LinearOperator):
    """The ridge operator [A; √λ·Iₙ] of shape (m + n, n).

    min‖Ax − b‖² + λ‖x‖²  ==  min‖[A; √λI] x − [b; 0]‖², so any
    least-squares solver handles Tikhonov regularization through this
    operator with zero new solver code.  ``reg`` (= λ ≥ 0) is a pytree
    leaf, so re-solving with a different λ does not retrace.
    """

    op: LinearOperator
    reg: jax.Array

    @classmethod
    def wrap(cls, A, reg) -> "TikhonovAugmented":
        op = as_operator(A)
        return cls(op=op, reg=jnp.asarray(reg, op.dtype))

    @property
    def shape(self):
        m, n = self.op.shape
        return (m + n, n)

    @property
    def dtype(self):
        return self.op.dtype

    @property
    def _sqrt_reg(self):
        return jnp.sqrt(self.reg.astype(self.dtype))

    def matvec(self, x):
        return jnp.concatenate([self.op.matvec(x), self._sqrt_reg * x])

    def rmatvec(self, u):
        m, n = self.op.shape
        return self.op.rmatvec(u[:m]) + self._sqrt_reg * u[m:]

    def matmat(self, X):
        return jnp.concatenate([self.op.matmat(X), self._sqrt_reg * X], axis=0)

    def rmatmat(self, U):
        m, n = self.op.shape
        return self.op.rmatmat(U[:m]) + self._sqrt_reg * U[m:]

    def augment_rhs(self, b: jax.Array) -> jax.Array:
        """[b; 0ₙ] — the right-hand side of the augmented system."""
        n = self.op.shape[1]
        return jnp.concatenate([b, jnp.zeros((n,), b.dtype)])

    @property
    def materializable(self):
        return self.op.materializable

    def materialize(self):
        n = self.op.shape[1]
        eye = jnp.eye(n, dtype=self.dtype)
        return jnp.concatenate([self.op.materialize(), self._sqrt_reg * eye], axis=0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CustomOperator(LinearOperator):
    """Adapter for an arbitrary (matvec, rmatvec) pair.

    The callables are static pytree metadata: arrays they close over are
    baked into the jit trace as constants, so prefer
    :class:`DenseOperator`/:class:`SparseOperator` when the operator is
    just a stored matrix.  ``materialize_fn`` is optional; without it the
    operator is non-materializable and ``lstsq`` routes it to the
    matrix-free solvers.
    """

    matvec_fn: Callable = _static()
    rmatvec_fn: Callable = _static()
    op_shape: tuple[int, int] = _static()
    op_dtype: Any = _static()
    materialize_fn: Callable | None = _static(default=None)

    @property
    def shape(self):
        return self.op_shape

    @property
    def dtype(self):
        return self.op_dtype

    def matvec(self, x):
        return self.matvec_fn(x)

    def rmatvec(self, u):
        return self.rmatvec_fn(u)

    @property
    def materializable(self):
        return self.materialize_fn is not None

    def materialize(self):
        if self.materialize_fn is None:
            return super().materialize()
        return self.materialize_fn()


def as_operator(A) -> LinearOperator:
    """Coerce ``jax.Array | BCOO | LinearOperator | duck-typed`` to the
    protocol.  Idempotent; every solver calls it on its data-matrix input,
    so the whole stack accepts all three public forms interchangeably."""
    if isinstance(A, LinearOperator):
        return A
    if isinstance(A, BCOO):
        if A.ndim != 2:
            raise ValueError(f"need a 2-D matrix, got shape {A.shape}")
        return SparseOperator(A)
    if hasattr(A, "matvec") and hasattr(A, "rmatvec") and hasattr(A, "shape"):
        # SciPy-style duck-typed operator.
        dtype = getattr(A, "dtype", None)
        if dtype is None:
            raise TypeError(f"duck-typed operator {A!r} must expose .dtype")
        mat = getattr(A, "materialize", None)
        return CustomOperator(
            matvec_fn=A.matvec,
            rmatvec_fn=A.rmatvec,
            op_shape=tuple(A.shape),
            op_dtype=dtype,
            materialize_fn=mat,
        )
    A = jnp.asarray(A)
    if A.ndim != 2:
        raise ValueError(f"need a 2-D matrix, got shape {A.shape}")
    return DenseOperator(A)


def one_pass(A, b, *, backend: str) -> bool:
    """Whether a least-squares iteration on (A, b) reads A once a step
    (``DenseOperator.residual_gradient``): ``backend`` resolves to
    ``"pallas"``, A is a dense float32 matrix whose tile fits the kernel,
    and b is a float32 vector.  A static test on what the inputs show."""
    A = as_operator(A)
    f32 = jnp.dtype(jnp.float32)
    return (
        isinstance(A, DenseOperator)
        and backend_lib.resolve(backend).use_pallas
        and jnp.dtype(A.dtype) == f32 and fits(*A.shape)
        and jnp.ndim(b) == 1 and jnp.result_type(b) == f32
    )


def ensure_dense(A, *, who: str = "this solver") -> jax.Array:
    """Materialize ``A`` to a dense array or raise with a pointer to the
    matrix-free paths.  Used by the direct solver and the row-sharded
    distributed driver, whose algorithms genuinely need the entries."""
    op = as_operator(A)
    if isinstance(op, DenseOperator):
        return op.A  # no copy — preserves sharding/placement
    if not op.materializable:
        raise TypeError(
            f"{who} needs a materializable matrix, got {type(op).__name__}; "
            "use lstsq(method='iterative'/'fossils'/'saa'/'lsqr') for "
            "matrix-free inputs"
        )
    return op.materialize()


def estimate_2norm(A, key: jax.Array, iters: int = 25) -> jax.Array:
    """σ_max(A) by Golub–Kahan–Lanczos bidiagonalization — the one shared
    2-norm estimator.

    ``iters`` steps of the bidiagonalization (one product with A and one
    with Aᵀ each) give a (iters × iters) upper-bidiagonal B whose largest
    singular value is a lower bound on σ_max(A) that converges far faster
    than power iteration when σ₁ and σ₂ are close.  Accepts anything
    :func:`as_operator` does; only products with A are used.  (Supersedes
    the private per-solver copies: SAA-SAS's fallback σ and any future
    spectral-norm need route through here.)
    """
    A = as_operator(A)
    tiny = jnp.finfo(A.dtype).tiny

    def unit(w):
        nw = jnp.linalg.norm(w)
        return w / jnp.maximum(nw, tiny), nw

    v, _ = unit(jax.random.normal(key, (A.shape[1],), A.dtype))
    u, alpha = unit(A.matvec(v))

    def body(i, carry):
        u, v, alpha, alphas, betas = carry
        v, beta = unit(A.rmatvec(u) - alpha * v)
        u, alpha = unit(A.matvec(v) - beta * u)
        return u, v, alpha, alphas.at[i + 1].set(alpha), betas.at[i].set(beta)

    alphas = jnp.zeros((iters,), A.dtype).at[0].set(alpha)
    betas = jnp.zeros((max(iters - 1, 0),), A.dtype)
    _, _, _, alphas, betas = lax.fori_loop(
        0, iters - 1, body, (u, v, alpha, alphas, betas)
    )
    B = jnp.diag(alphas) + jnp.diag(betas, 1)
    return jnp.linalg.svd(B, compute_uv=False)[0]
