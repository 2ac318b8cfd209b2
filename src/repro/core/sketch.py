"""Sketching operators (paper §2) with backend-dispatched applies.

Dense:  Gaussian, uniform-dense, SRHT (subsampled randomized Hadamard).
Sparse: CountSketch (Clarkson–Woodruff), sparse-sign(k), uniform-sparse.

All operators are functional pytrees: ``sample(kind, key, d, m)`` draws the
operator, ``op.apply(A, backend=...)`` applies it to an (m,) vector or (m, n)
matrix along axis 0, and ``op.apply_op(A)`` sketches a
``repro.core.linop`` operator (dense, BCOO-sparse or fully matrix-free —
see :class:`_OperatorApply`). Every operator is scaled so that ``E[SᵀS] = I`` (an
isometry in expectation), which is the normalization the sketch-and-solve
analysis assumes. ``op.as_dense()`` materializes S (testing / small problems
only) and is backend-independent.

Backend dispatch (see ``repro.core.backend``): every ``apply`` takes a
``backend`` knob — ``"reference"`` runs the pure-jnp path in this module;
``"pallas"`` routes kernel-backed kinds to the TPU Pallas ops in
``repro.kernels`` (``countsketch_apply`` for CountSketch, ``srht_apply`` for
SRHT, ``fused_gaussian_sketch`` for Gaussian, ``sketch_matmul`` for
uniform-dense), in ``interpret=True`` mode off-TPU; ``"auto"`` picks
``"pallas"`` on TPU and ``"reference"`` elsewhere. Both backends of an
operator realize the SAME linear map S (the Gaussian S is drawn with the
kernels' counter-based threefry + Box–Muller stream so the fused kernel
regenerates it bit-for-bit), so backends agree to accumulation-order
rounding and can be swapped under any solver. Kinds without a kernel
(sparse-sign, uniform-sparse) fall back to the reference path.

Row streaming: every kind also exposes ``apply_rows(tile, row_offset)`` —
the restriction of S to a contiguous row tile of A — and (except SRHT)
``restrict_cols(idx)``, the sub-operator S[:, idx].  These are the
primitives behind the out-of-core accumulators of ``repro.streaming``, the
session's delta-sketch row updates and the distributed per-shard sketch;
see the streaming contract on ``_OperatorApply``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from . import backend as backend_lib
from ..kernels.common import matmul

__all__ = [
    "sample",
    "fwht",
    "GaussianSketch",
    "UniformDenseSketch",
    "SRHTSketch",
    "CountSketch",
    "SparseSignSketch",
    "UniformSparseSketch",
    "AugmentedSketch",
    "StackedSketch",
    "SKETCH_KINDS",
]


def _static(default=None):
    return dataclasses.field(metadata=dict(static=True), default=default)


def _kernels():
    """Lazy kernel import: repro.kernels imports this module (srht oracle)."""
    from .. import kernels

    return kernels


def _tuned_blocks(kind: str, A, d: int, rb) -> dict:
    """Autotuned block kwargs for a pallas dispatch (``{}`` = kernel defaults)."""
    m = A.shape[0]
    n = A.shape[1] if A.ndim > 1 else 1
    return backend_lib.kernel_blocks(
        kind, m, n, d, A.dtype, interpret=rb.interpret
    )


def fwht(x: jax.Array, axis: int = 0) -> jax.Array:
    """Unnormalized fast Walsh–Hadamard transform along ``axis``.

    Length along ``axis`` must be a power of two.  O(m log m) adds.
    """
    x = jnp.moveaxis(x, axis, 0)
    m = x.shape[0]
    if m & (m - 1):
        raise ValueError(f"FWHT length must be a power of two, got {m}")
    tail = x.shape[1:]
    h = m // 2
    while h >= 1:
        x = x.reshape((-1, 2, h) + tail)
        a, b = x[:, 0], x[:, 1]
        x = jnp.concatenate([a + b, a - b], axis=1)
        x = x.reshape((m,) + tail)
        h //= 2
    return jnp.moveaxis(x, 0, axis)


def _next_pow2(m: int) -> int:
    p = 1
    while p < m:
        p *= 2
    return p


def _as_2d(A):
    """Canonicalize (m,) -> (m, 1); returns (A2d, was_vector)."""
    if A.ndim == 1:
        return A[:, None], True
    return A, False


def _maybe_squeeze(B, was_vector):
    return B[:, 0] if was_vector else B


def _bcoo_coords(M):
    """(rows, cols, data) of an unbatched 2-D BCOO, or None for layouts the
    scatter paths don't handle (batched / dense-tail BCOO)."""
    if getattr(M, "n_batch", 0) or getattr(M, "n_dense", 0):
        return None
    return M.indices[:, 0], M.indices[:, 1], M.data


class _OperatorApply:
    """Operator-aware sketching shared by every kind: B = S·A for A given as
    a :mod:`repro.core.linop` operator (dense, BCOO-sparse, Tikhonov or
    fully matrix-free) without materializing A unless the math forces it.

    Dispatch, in order:

    - ``DenseOperator``   → the classical backend-dispatched ``apply``.
    - ``SparseOperator``  → the sparse kinds scatter-add straight off A's
      BCOO coordinates in O(nnz(A)) (never jax's sparse×sparse spdot,
      whose cost explodes combinatorially); dense-S kinds run one
      dense×BCOO product; SRHT is an inherently dense transform, so it
      densifies A (documented cost).
    - ``TikhonovAugmented`` over a dense core → materialize (the augmented
      matrix is barely bigger than A) and take the fast kernel path.
    - anything else (matrix-free) → B = (Aᵀ·Sᵀ)ᵀ via one blocked rmatmat
      against the d dense columns of Sᵀ — d = O(n) adjoint products, the
      generic price of sketching an operator known only through products.
    """

    def apply_op(self, A, *, backend: str = "auto"):
        from . import linop

        A = linop.as_operator(A)
        if isinstance(A, linop.DenseOperator):
            return self.apply(A.A, backend=backend)
        if isinstance(A, linop.SparseOperator):
            return self._apply_bcoo(A.M, backend=backend)
        if isinstance(A, linop.TikhonovAugmented) and isinstance(
            A.op, linop.DenseOperator
        ):
            return self.apply(A.materialize(), backend=backend)
        St = self.as_dense_t().astype(A.dtype)
        return A.rmatmat(St).T

    def _apply_bcoo(self, M, *, backend: str = "auto"):
        S = getattr(self, "S", None)
        if S is not None:  # dense-S kinds: one dense × BCOO product
            out = S.astype(M.dtype) @ M
            return out.todense() if hasattr(out, "todense") else out
        # SRHT: the Hadamard transform is dense no matter what — densify.
        return self.apply(M.todense(), backend=backend)

    def as_dense_t(self):
        """Sᵀ as a dense (m, d) array — the generic matrix-free sketch path
        feeds these columns to the operator's rmatmat."""
        return self.as_dense().T

    # ------------------------------------------------------ row streaming
    # S is linear in the rows of A, so SA decomposes over any row tiling:
    # SA = Σ_t S[:, o_t:o_t+len(t)] · A[o_t:o_t+len(t)].  ``apply_rows``
    # is that per-tile restriction — the primitive behind the out-of-core
    # accumulators in ``repro.streaming.accumulate``.  ``row_offset`` is a
    # static Python int (the tile boundaries are host-side loop state).
    #
    # Contract per kind (see ``stream_semantics``):
    # - "add"   (five kinds): returns the (d, ncols) additive contribution;
    #   summing the tiles in any order reconstructs SA.
    # - "place" (SRHT only): the Hadamard transform couples every row, so
    #   the restriction returns the D-signed tile (t, ncols) instead; the
    #   accumulator places it at rows [offset, offset+t) of the padded
    #   buffer and applies H, P and the 1/√d scale ONCE at finalize.

    stream_semantics: str = "add"

    def apply_rows(self, tile, row_offset: int, *, backend: str = "auto"):
        raise NotImplementedError(
            f"{type(self).__name__} does not support row streaming"
        )

    def restrict_cols(self, idx):
        """S[:, idx] as a same-protocol operator over ``len(idx)`` rows, or
        ``None`` for kinds without an independent column restriction (SRHT —
        its columns couple through the Hadamard transform).  Powers the
        session's O(|idx|·n) delta-sketch row updates and the per-shard
        restriction of the distributed/streaming sketch assembly."""
        return None

    # -------------------------------------------------------- escalation
    # A failed certificate (repro.core.certify) is repaired by GROWING the
    # embedding, not redrawing it: ``extend_rows`` appends ``extra`` fresh
    # rows as the weighted stack S′ = [√(d/(d+e))·S; √(e/(d+e))·S_e].
    # The weights keep E[S′ᵀS′] = I, and the variance of ‖S′x‖² matches a
    # fresh (d+e)-row draw exactly — so the escalated operator embeds like
    # a from-scratch sketch at the larger size, while the already-paid
    # sketch B = SA is reused verbatim (``StackedSketch.extend_sketch``).

    def _fresh_like(self, key, extra: int):
        """An independent draw of this kind with ``extra`` rows over the
        same m-row space — the new block of an escalated sketch."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support extend_rows"
        )

    def extend_rows(self, key, extra: int) -> "StackedSketch":
        """Escalate to d + ``extra`` rows without touching the first d.

        Returns a :class:`StackedSketch` whose top block is THIS operator
        (reweighted) and whose bottom block is a fresh ``extra``-row draw
        from ``key``; a stored sketch B = SA extends through
        ``StackedSketch.extend_sketch`` by sketching only the new rows.
        """
        extra = int(extra)
        if extra <= 0:
            raise ValueError(f"extra must be a positive row count, got {extra}")
        d = self.d
        return StackedSketch(
            top=self,
            bottom=self._fresh_like(key, extra),
            w_top=math.sqrt(d / (d + extra)),
            w_bottom=math.sqrt(extra / (d + extra)),
        )


# --------------------------------------------------------------------------
# Dense operators
# --------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GaussianSketch(_OperatorApply):
    """S with iid N(0, 1/d) entries.

    S is drawn from the counter-based threefry2x32 + Box–Muller stream of
    ``repro.kernels.sketch_matmul`` (element (i, j) ← counter pair (i, j)),
    so the ``"pallas"`` backend's ``fused_gaussian_sketch`` regenerates the
    SAME matrix inside the kernel from ``key`` alone — the materialized S
    never has to leave HBM on that path.

    ``sample(..., materialize=False)`` skips storing S entirely (S=None):
    every column block is regenerated on demand from ``key`` via the same
    counters, bitwise identical to slicing the stored matrix.  This is the
    streaming configuration — for out-of-core m the (d, m) matrix S is as
    unstorable as A itself, and ``apply_rows`` only ever needs the (d, t)
    block of the current tile.
    """

    S: jax.Array | None
    key: jax.Array  # PRNG key the fused kernel regenerates S from
    d: int = _static()
    m: int = _static()

    @classmethod
    def sample(cls, key, d, m, dtype=jnp.float64, materialize=True):
        S = cls._gen_cols(key, d, jnp.arange(m), dtype) if materialize else None
        return cls(S=S, key=key, d=d, m=m)

    @staticmethod
    def _gen_cols(key, d, cols, dtype):
        """Columns S[:, cols] from the kernel's counter stream (exact)."""
        from ..kernels.sketch_matmul import gaussian_cols_ref

        scale = jnp.float32(1.0 / float(d) ** 0.5)
        return (gaussian_cols_ref(key, d, cols, jnp.float32) * scale).astype(dtype)

    def _cols(self, cols, dtype):
        if self.S is not None:
            return self.S[:, cols]
        return self._gen_cols(self.key, self.d, cols, dtype)

    def apply(self, A, *, backend: str = "auto"):
        rb = backend_lib.resolve(backend)
        if rb.use_pallas:
            blocks = _tuned_blocks("gaussian", A, self.d, rb)
            return _kernels().fused_gaussian_sketch(
                A, self.key, self.d, interpret=rb.interpret, **blocks
            )
        A2, vec = _as_2d(A)
        S = self.S if self.S is not None else self.as_dense().astype(A2.dtype)
        return _maybe_squeeze(matmul(S, A2), vec)

    def apply_rows(self, tile, row_offset: int, *, backend: str = "auto"):
        del backend  # one (d, t) × (t, n) block product either way
        tile2, _ = _as_2d(tile)
        t = tile2.shape[0]
        if self.S is not None:
            St = self.S[:, row_offset : row_offset + t]
        else:
            St = self._gen_cols(
                self.key, self.d, row_offset + jnp.arange(t), tile2.dtype
            )
        return matmul(St.astype(tile2.dtype), tile2)

    def restrict_cols(self, idx):
        S = self._cols(idx, jnp.float64)
        return UniformDenseSketch(S=S, d=self.d, m=S.shape[1])

    def _fresh_like(self, key, extra):
        return GaussianSketch.sample(
            key, extra, self.m,
            dtype=self.S.dtype if self.S is not None else jnp.float64,
            materialize=self.S is not None,
        )

    def as_dense(self):
        if self.S is not None:
            return self.S
        return self._gen_cols(self.key, self.d, jnp.arange(self.m), jnp.float64)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class UniformDenseSketch(_OperatorApply):
    """S with iid U(-sqrt(3/d), sqrt(3/d)) entries (unit row variance /d)."""

    S: jax.Array
    d: int = _static()
    m: int = _static()

    @classmethod
    def sample(cls, key, d, m, dtype=jnp.float64):
        lim = jnp.sqrt(jnp.asarray(3.0 / d, dtype))
        S = jax.random.uniform(key, (d, m), dtype, minval=-lim, maxval=lim)
        return cls(S=S, d=d, m=m)

    def apply(self, A, *, backend: str = "auto"):
        rb = backend_lib.resolve(backend)
        if rb.use_pallas:
            blocks = _tuned_blocks("sketch_matmul", A, self.d, rb)
            # S in A's dtype, as the fused path and the Gaussian kernel
            # do: a bf16 A (precision="mixed") keeps the single-pass MXU
            # contraction its tiles were sized for.
            return _kernels().sketch_matmul(
                self.S.astype(A.dtype), A, interpret=rb.interpret, **blocks
            )
        A2, vec = _as_2d(A)
        return _maybe_squeeze(matmul(self.S, A2), vec)

    def apply_rows(self, tile, row_offset: int, *, backend: str = "auto"):
        del backend
        tile2, _ = _as_2d(tile)
        St = self.S[:, row_offset : row_offset + tile2.shape[0]]
        return matmul(St.astype(tile2.dtype), tile2)

    def restrict_cols(self, idx):
        return UniformDenseSketch(S=self.S[:, idx], d=self.d, m=len(idx))

    def _fresh_like(self, key, extra):
        return UniformDenseSketch.sample(key, extra, self.m, dtype=self.S.dtype)

    def as_dense(self):
        return self.S


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SRHTSketch(_OperatorApply):
    """Subsampled randomized Hadamard transform: S = (1/sqrt(d)) P H D.

    H is the (unnormalized, power-of-two padded) Hadamard matrix, D a random
    sign diagonal, P a uniform row sample of size d.  Apply cost
    O(m log m · n) via the FWHT (reference) or the two-stage blocked
    Hadamard kernel (pallas).
    """

    signs: jax.Array  # (m_pad,)
    rows: jax.Array  # (d,) int32 indices into m_pad
    d: int = _static()
    m: int = _static()
    m_pad: int = _static()

    @classmethod
    def sample(cls, key, d, m, dtype=jnp.float64):
        m_pad = _next_pow2(m)
        k1, k2 = jax.random.split(key)
        signs = jax.random.rademacher(k1, (m_pad,), dtype)
        # sampling without replacement needs d <= m_pad; fall back to
        # with-replacement for oversampling sketches (valid SRHT variant)
        rows = jax.random.choice(k2, m_pad, (d,), replace=d > m_pad)
        return cls(signs=signs, rows=rows, d=d, m=m, m_pad=m_pad)

    def apply(self, A, *, backend: str = "auto"):
        rb = backend_lib.resolve(backend)
        if rb.use_pallas:
            blocks = _tuned_blocks("srht", A, self.d, rb)
            return _kernels().srht_apply(
                A, self.signs, self.rows, self.d, interpret=rb.interpret, **blocks
            )
        A2, vec = _as_2d(A)
        dtype = A2.dtype
        if self.m_pad != self.m:
            pad = [(0, self.m_pad - self.m)] + [(0, 0)] * (A2.ndim - 1)
            A2 = jnp.pad(A2, pad)
        HDx = fwht(self.signs[:, None].astype(dtype) * A2)
        B = HDx[self.rows] / jnp.sqrt(jnp.asarray(self.d, dtype))
        return _maybe_squeeze(B, vec)

    # SRHT streams by placement, not addition: H mixes every row, so the
    # per-tile restriction is the D-signed tile and the transform runs once
    # at finalize (see ``_OperatorApply`` and ``repro.streaming.accumulate``).
    stream_semantics = "place"

    def apply_rows(self, tile, row_offset: int, *, backend: str = "auto"):
        """The D-signed rows of the tile — NOT the (d, n) contribution.

        The streaming accumulator writes these at rows
        [row_offset, row_offset + t) of its (m_pad, n) buffer; the padded
        FWHT, the row subsample P and the 1/√d scale are applied once at
        ``finalize`` — bit-for-bit the reference ``apply``.
        """
        del backend
        tile2, _ = _as_2d(tile)
        t = tile2.shape[0]
        signs = self.signs[row_offset : row_offset + t]
        return signs[:, None].astype(tile2.dtype) * tile2

    def _fresh_like(self, key, extra):
        return SRHTSketch.sample(key, extra, self.m, dtype=self.signs.dtype)

    def as_dense(self):
        eye = jnp.eye(self.m, dtype=self.signs.dtype)
        return self.apply(eye, backend="reference")

    def as_dense_t(self):
        # Sᵀ = (1/√d) D H Pᵀ: the d columns are H[:, rows] (H symmetric),
        # built with ONE fwht of the (m_pad, d) selector — O(d·m log m),
        # versus O(m²·log m) for as_dense().T via apply(eye(m)).
        dtype = self.signs.dtype
        sel = jnp.zeros((self.m_pad, self.d), dtype)
        sel = sel.at[self.rows, jnp.arange(self.d)].set(1.0)
        St = self.signs[:, None] * fwht(sel) / jnp.sqrt(jnp.asarray(self.d, dtype))
        return St[: self.m]


# --------------------------------------------------------------------------
# Sparse operators
# --------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CountSketch(_OperatorApply):
    """Clarkson–Woodruff: one ±1 per column of S, at a random bucket.

    SA[k] = sum_{i : h(i)=k} s(i) · A[i]  — an exact isometry in expectation
    with no scaling.  Apply cost O(nnz(A)) via segment_sum (reference) or
    the kernel that adds each row of A into its bucket's row of a
    VMEM-resident output (pallas).
    """

    buckets: jax.Array  # (m,) int32 in [0, d)
    signs: jax.Array  # (m,)
    d: int = _static()
    m: int = _static()

    @classmethod
    def sample(cls, key, d, m, dtype=jnp.float64):
        k1, k2 = jax.random.split(key)
        buckets = jax.random.randint(k1, (m,), 0, d, dtype=jnp.int32)
        signs = jax.random.rademacher(k2, (m,), dtype)
        return cls(buckets=buckets, signs=signs, d=d, m=m)

    def apply(self, A, *, backend: str = "auto"):
        rb = backend_lib.resolve(backend)
        if rb.use_pallas:
            return _kernels().countsketch_apply(
                A, self.buckets, self.signs, self.d, interpret=rb.interpret
            )
        A2, vec = _as_2d(A)
        contrib = self.signs[:, None].astype(A2.dtype) * A2
        B = jax.ops.segment_sum(contrib, self.buckets, num_segments=self.d)
        return _maybe_squeeze(B, vec)

    def apply_rows(self, tile, row_offset: int, *, backend: str = "auto"):
        t = tile.shape[0]
        return self.restrict_cols(
            slice(row_offset, row_offset + t)
        ).apply(tile, backend=backend)

    def restrict_cols(self, idx):
        buckets, signs = self.buckets[idx], self.signs[idx]
        return CountSketch(
            buckets=buckets, signs=signs, d=self.d, m=buckets.shape[0]
        )

    def _fresh_like(self, key, extra):
        return CountSketch.sample(key, extra, self.m, dtype=self.signs.dtype)

    def as_dense(self):
        S = jnp.zeros((self.d, self.m), self.signs.dtype)
        return S.at[self.buckets, jnp.arange(self.m)].set(self.signs)

    def _apply_bcoo(self, M, *, backend: str = "auto"):
        # Row i of A lands in bucket h(i) with sign s(i); in coordinate
        # form that is one O(nnz) scatter-add — A is never densified.
        coords = _bcoo_coords(M)
        if coords is None:
            return self.apply(M.todense(), backend=backend)
        rows, cols, data = coords
        out = jnp.zeros((self.d, M.shape[1]), M.dtype)
        return out.at[self.buckets[rows], cols].add(
            self.signs[rows].astype(M.dtype) * data
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseSignSketch(_OperatorApply):
    """k nonzeros (±1/sqrt(k)) per column of S at iid random buckets.

    No Pallas kernel yet — ``backend="pallas"`` falls back to the reference
    path (see ``repro.core.backend.KERNEL_BACKED_KINDS``).
    """

    buckets: jax.Array  # (k, m) int32
    signs: jax.Array  # (k, m)
    d: int = _static()
    m: int = _static()
    k: int = _static(default=8)

    @classmethod
    def sample(cls, key, d, m, dtype=jnp.float64, k=8):
        k1, k2 = jax.random.split(key)
        buckets = jax.random.randint(k1, (k, m), 0, d, dtype=jnp.int32)
        signs = jax.random.rademacher(k2, (k, m), dtype)
        return cls(buckets=buckets, signs=signs, d=d, m=m, k=k)

    def apply(self, A, *, backend: str = "auto"):
        del backend  # no kernel for this kind — reference path only
        A2, vec = _as_2d(A)

        def one(h, s):
            return jax.ops.segment_sum(
                s[:, None].astype(A2.dtype) * A2, h, num_segments=self.d
            )

        B = jax.vmap(one)(self.buckets, self.signs).sum(0)
        B = B / jnp.sqrt(jnp.asarray(self.k, A2.dtype))
        return _maybe_squeeze(B, vec)

    def apply_rows(self, tile, row_offset: int, *, backend: str = "auto"):
        t = tile.shape[0]
        return self.restrict_cols(
            slice(row_offset, row_offset + t)
        ).apply(tile, backend=backend)

    def restrict_cols(self, idx):
        buckets, signs = self.buckets[:, idx], self.signs[:, idx]
        return SparseSignSketch(
            buckets=buckets, signs=signs, d=self.d, m=buckets.shape[1], k=self.k
        )

    def _fresh_like(self, key, extra):
        return SparseSignSketch.sample(
            key, extra, self.m, dtype=self.signs.dtype, k=self.k
        )

    def as_dense(self):
        S = jnp.zeros((self.d, self.m), self.signs.dtype)
        cols = jnp.broadcast_to(jnp.arange(self.m), (self.k, self.m))
        scale = 1.0 / jnp.sqrt(jnp.asarray(self.k, self.signs.dtype))
        return S.at[self.buckets, cols].add(self.signs * scale)

    def _apply_bcoo(self, M, *, backend: str = "auto"):
        # k scatter targets per row of A: one O(k·nnz) coordinate scatter.
        coords = _bcoo_coords(M)
        if coords is None:
            return self.apply(M.todense(), backend=backend)
        rows, cols, data = coords
        hb = self.buckets[:, rows]  # (k, nnz)
        contrib = self.signs[:, rows].astype(M.dtype) * data  # (k, nnz)
        cols_k = jnp.broadcast_to(cols, hb.shape)
        out = jnp.zeros((self.d, M.shape[1]), M.dtype)
        out = out.at[hb.ravel(), cols_k.ravel()].add(contrib.ravel())
        return out / jnp.sqrt(jnp.asarray(self.k, M.dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class UniformSparseSketch(_OperatorApply):
    """One U(-sqrt(3), sqrt(3)) entry per column at a random bucket.

    No Pallas kernel yet — ``backend="pallas"`` falls back to the reference
    path (see ``repro.core.backend.KERNEL_BACKED_KINDS``).
    """

    buckets: jax.Array
    values: jax.Array
    d: int = _static()
    m: int = _static()

    @classmethod
    def sample(cls, key, d, m, dtype=jnp.float64):
        k1, k2 = jax.random.split(key)
        buckets = jax.random.randint(k1, (m,), 0, d, dtype=jnp.int32)
        lim = jnp.sqrt(jnp.asarray(3.0, dtype))
        values = jax.random.uniform(k2, (m,), dtype, minval=-lim, maxval=lim)
        return cls(buckets=buckets, values=values, d=d, m=m)

    def apply(self, A, *, backend: str = "auto"):
        del backend  # no kernel for this kind — reference path only
        A2, vec = _as_2d(A)
        contrib = self.values[:, None].astype(A2.dtype) * A2
        B = jax.ops.segment_sum(contrib, self.buckets, num_segments=self.d)
        return _maybe_squeeze(B, vec)

    def apply_rows(self, tile, row_offset: int, *, backend: str = "auto"):
        t = tile.shape[0]
        return self.restrict_cols(
            slice(row_offset, row_offset + t)
        ).apply(tile, backend=backend)

    def restrict_cols(self, idx):
        buckets, values = self.buckets[idx], self.values[idx]
        return UniformSparseSketch(
            buckets=buckets, values=values, d=self.d, m=buckets.shape[0]
        )

    def _fresh_like(self, key, extra):
        return UniformSparseSketch.sample(
            key, extra, self.m, dtype=self.values.dtype
        )

    def as_dense(self):
        S = jnp.zeros((self.d, self.m), self.values.dtype)
        return S.at[self.buckets, jnp.arange(self.m)].set(self.values)

    def _apply_bcoo(self, M, *, backend: str = "auto"):
        coords = _bcoo_coords(M)
        if coords is None:
            return self.apply(M.todense(), backend=backend)
        rows, cols, data = coords
        out = jnp.zeros((self.d, M.shape[1]), M.dtype)
        return out.at[self.buckets[rows], cols].add(
            self.values[rows].astype(M.dtype) * data
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AugmentedSketch(_OperatorApply):
    """blockdiag(S, I_tail): the structured embedding for Tikhonov systems.

    The rows of the √λ·I regularization block of ``[A; √λI]`` are
    maximally coherent (one spike each), exactly the inputs oblivious
    sparse sketches are worst at — bucketing them randomly wrecks the
    subspace embedding (observed whitened σ_max ≈ 15 with CountSketch at
    s = 4n, vs the ≈ 2 the analysis needs) and the fixed-coefficient
    heavy-ball solvers then diverge.  The fix is structural: sketch only
    the data block with ``inner`` and keep the identity block EXACT, so
    B = [S·A; √λI] and BᵀB = (SA)ᵀSA + λI — the embedding quality for the
    augmented system is exactly the inner sketch's quality on A.

    ``SketchedFactor.build`` constructs this automatically for
    ``TikhonovAugmented`` inputs; it quacks like the other sketch
    operators (``apply``/``apply_op``/``as_dense``) with
    d = inner.d + tail rows.
    """

    inner: object  # sketch operator over the data rows
    tail: int = _static()  # identity block size (= n of the augmented op)

    @property
    def d(self) -> int:
        return self.inner.d + self.tail

    @property
    def m(self) -> int:
        return self.inner.m + self.tail

    def apply(self, A, *, backend: str = "auto"):
        mi = self.inner.m
        top = self.inner.apply(A[:mi], backend=backend)
        return jnp.concatenate([top, A[mi:]], axis=0)

    def apply_op(self, A, *, backend: str = "auto"):
        from . import linop

        A = linop.as_operator(A)
        if isinstance(A, linop.TikhonovAugmented):
            top = self.inner.apply_op(A.op, backend=backend)
            eye = jnp.eye(self.tail, A.op.shape[1], dtype=top.dtype)
            return jnp.concatenate(
                [top, A._sqrt_reg.astype(top.dtype) * eye], axis=0
            )
        return super().apply_op(A, backend=backend)

    def as_dense(self):
        Sd = self.inner.as_dense()
        top = jnp.concatenate(
            [Sd, jnp.zeros((self.inner.d, self.tail), Sd.dtype)], axis=1
        )
        bot = jnp.concatenate(
            [
                jnp.zeros((self.tail, self.inner.m), Sd.dtype),
                jnp.eye(self.tail, dtype=Sd.dtype),
            ],
            axis=1,
        )
        return jnp.concatenate([top, bot], axis=0)

    def extend_rows(self, key, extra: int) -> "AugmentedSketch":
        """Escalate the DATA block only — the exact identity tail needs no
        growing (it is not a random embedding), so ridge escalation appends
        rows to the inner sketch and keeps blockdiag structure."""
        return AugmentedSketch(
            inner=self.inner.extend_rows(key, extra), tail=self.tail
        )

    def extend_sketch(self, B_top, A, *, backend: str = "auto"):
        """Incremental extension of a stored augmented sketch [S·A; √λI]:
        the data rows extend through the stacked inner operator, the exact
        tail rows move down unchanged.  Bit-equal to ``apply_op(A)`` of the
        escalated operator recomputed from scratch."""
        from . import linop

        if not isinstance(self.inner, StackedSketch):
            raise TypeError(
                "extend_sketch needs an operator produced by extend_rows; "
                f"inner is {type(self.inner).__name__}"
            )
        A = linop.as_operator(A)
        if not isinstance(A, linop.TikhonovAugmented):
            raise TypeError(
                "AugmentedSketch.extend_sketch sketches the data block of a "
                f"TikhonovAugmented operator, got {type(A).__name__}"
            )
        d_prev = self.inner.top.d
        B_data, B_tail = B_top[:d_prev], B_top[d_prev:]
        top = self.inner.extend_sketch(B_data, A.op, backend=backend)
        return jnp.concatenate([top, B_tail], axis=0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StackedSketch(_OperatorApply):
    """Weighted stack [w_t·S_top; w_b·S_bot] — the escalated sketch.

    Produced by ``op.extend_rows(key, extra)`` with w_t = √(d/(d+e)),
    w_b = √(e/(d+e)) so that E[SᵀS] = w_t²·I + w_b²·I = I stays an exact
    expectation-isometry and Var[‖Sx‖²] matches a fresh (d+e)-row draw of
    the same kind — escalation buys the full statistical benefit of the
    larger sketch.  The payoff is :meth:`extend_sketch`: a stored
    B = S_top·A extends to the (d+e)-row sketch by sketching ONLY the new
    rows (one ``extra``-row apply), bit-equal to applying the stacked
    operator to A from scratch — the escalation analogue of the streaming
    accumulators' merge-exactness contract.

    Nested escalations stack recursively (``top`` is the previous stack);
    ``_fresh_like`` always draws the ORIGINAL kind, so an escalated
    CountSketch stays a union of CountSketch blocks.
    """

    top: object  # the pre-escalation operator (d_top, m), reweighted
    bottom: object  # the fresh block (extra, m), independent draw
    w_top: float = _static()
    w_bottom: float = _static()

    @property
    def d(self) -> int:
        return self.top.d + self.bottom.d

    @property
    def m(self) -> int:
        return self.top.m

    def apply(self, A, *, backend: str = "auto"):
        top = self.top.apply(A, backend=backend)
        bot = self.bottom.apply(A, backend=backend)
        return jnp.concatenate([self.w_top * top, self.w_bottom * bot], axis=0)

    def apply_op(self, A, *, backend: str = "auto"):
        top = self.top.apply_op(A, backend=backend)
        bot = self.bottom.apply_op(A, backend=backend)
        return jnp.concatenate([self.w_top * top, self.w_bottom * bot], axis=0)

    def extend_sketch(self, B_top, A, *, backend: str = "auto"):
        """[w_t·B_top; w_b·(S_bot·A)] — extend a STORED sketch.

        ``B_top`` must be the sketch the top operator produced for this
        same A (``top.apply_op(A)``); only the ``bottom.d`` new rows are
        sketched.  Deterministic recomputation makes the result bit-equal
        to ``self.apply_op(A)`` from scratch (pinned in tests).
        """
        B_top = jnp.asarray(B_top)
        if B_top.shape[0] != self.top.d:
            raise ValueError(
                f"B_top has {B_top.shape[0]} rows, the pre-escalation "
                f"operator has d={self.top.d}"
            )
        bot = self.bottom.apply_op(A, backend=backend)
        return jnp.concatenate(
            [self.w_top * B_top, self.w_bottom * bot], axis=0
        )

    # both blocks must stream additively for the stack to stream at all
    # (an SRHT block streams by placement — route those through their own
    # accumulators and merge instead)
    @property
    def stream_semantics(self) -> str:  # type: ignore[override]
        both_add = (
            self.top.stream_semantics == "add"
            and self.bottom.stream_semantics == "add"
        )
        return "add" if both_add else "place"

    def apply_rows(self, tile, row_offset: int, *, backend: str = "auto"):
        if self.stream_semantics != "add":
            raise NotImplementedError(
                "a stacked sketch with an SRHT block streams by placement; "
                "accumulate the blocks separately"
            )
        top = self.top.apply_rows(tile, row_offset, backend=backend)
        bot = self.bottom.apply_rows(tile, row_offset, backend=backend)
        return jnp.concatenate([self.w_top * top, self.w_bottom * bot], axis=0)

    def restrict_cols(self, idx):
        top = self.top.restrict_cols(idx)
        bot = self.bottom.restrict_cols(idx)
        if top is None or bot is None:
            return None
        return StackedSketch(
            top=top, bottom=bot, w_top=self.w_top, w_bottom=self.w_bottom
        )

    def _fresh_like(self, key, extra):
        # nested escalation keeps drawing the ORIGINAL kind
        return self.top._fresh_like(key, extra)

    def as_dense(self):
        top = self.top.as_dense()
        bot = self.bottom.as_dense()
        return jnp.concatenate(
            [self.w_top * top, self.w_bottom * bot.astype(top.dtype)], axis=0
        )


SKETCH_KINDS: dict[str, type] = {
    "gaussian": GaussianSketch,
    "uniform_dense": UniformDenseSketch,
    "srht": SRHTSketch,
    "countsketch": CountSketch,
    "clarkson_woodruff": CountSketch,  # alias — the paper's final choice
    "sparse_sign": SparseSignSketch,
    "uniform_sparse": UniformSparseSketch,
}


def sample(kind: str, key: jax.Array, d: int, m: int, dtype=jnp.float64, **kw):
    """Draw a sketching operator ``S : R^m -> R^d`` of the given kind."""
    try:
        cls = SKETCH_KINDS[kind]
    except KeyError:
        raise ValueError(f"unknown sketch kind {kind!r}; have {sorted(SKETCH_KINDS)}")
    return cls.sample(key, d, m, dtype=dtype, **kw)
