"""``sketch_qr`` — the fused sketch→QR pipeline entry point.

One call produces the sketched factor (Q, R) AND the sketch B = SA for a
``repro.core.sketch`` operator, without the unfused pipeline's HBM
round-trip of B between the sketch kernel and the QR:

- **pallas** backend, dense A, kernel-backed family → a single fused
  Pallas kernel (``countsketch_gram_kernel`` / ``matmul_gram_kernel`` /
  ``gaussian_gram_kernel``) accumulates each B panel in VMEM and folds it
  straight into the Gram G = BᵀB on its last accumulation step; B is
  written to HBM once and never re-read.  SRHT's Hadamard transform has
  its own two-stage kernel, so its fusion is the QR half: the transform
  output feeds ``panel_gram`` directly instead of a Householder QR.
- **reference** backend (and any non-kernel family or non-dense
  operator) → the standard backend-dispatched apply, then ``panel_gram``
  / a jnp Gram.  Still "fused" where it counts on CPU: the factor comes
  from the GEMM-rate shifted-CholeskyQR3 finisher instead of LAPACK
  Householder QR — the measured win ``benchmarks/kernels_bench.py``
  tracks.

Both routes end in ``ops.cholqr_finish`` (shifted CholeskyQR3 — stable
to κ(B) ≈ 1e10 in f64, validated in tests/test_tsqr.py), and both honour
``precision="mixed"``: the apply/Gram run on a bf16-rounded copy of A
with ≥ f32 accumulation, and the factor is returned upcast to A's dtype
for the fp32/fp64 refinement loops to consume.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import (
    cdiv, key_to_u32, matmul, mosaic_context, pad_to, resolve_interpret,
)
from .kernel import (
    countsketch_gram_kernel,
    make_gaussian_gram_kernel,
    matmul_gram_kernel,
)
from .ops import MAX_FUSED_COLS, cholqr_finish, panel_gram

__all__ = ["sketch_qr", "countsketch_gram", "matmul_gram", "gaussian_gram"]


def _acc_dtype(dtype):
    return jnp.float32 if dtype in (jnp.bfloat16, jnp.float16) else dtype


def _fused_call(kernel, inputs, in_specs, d, n, bd, interpret, acc):
    """Shared pallas_call plumbing: (B (d, n), G (n, n)) in acc dtype."""
    n_p = max(128, n)
    d_p = cdiv(d, bd) * bd
    m_blocks = in_specs.pop("m_blocks")
    grid = (d_p // bd, m_blocks)
    with mosaic_context(interpret):
        B, G = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs.pop("specs"),
            out_specs=[
                pl.BlockSpec((bd, n_p), lambda di, mi: (di, 0)),
                pl.BlockSpec((n_p, n_p), lambda di, mi: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((d_p, n_p), acc),
                jax.ShapeDtypeStruct((n_p, n_p), acc),
            ],
            interpret=interpret,
        )(*inputs)
    return B[:d, :n], G[:n, :n]


@partial(
    jax.jit,
    static_argnames=("d", "block_m", "block_d", "interpret"),
)
def countsketch_gram(
    A: jax.Array,
    buckets: jax.Array,
    signs: jax.Array,
    d: int,
    *,
    block_m: int = 256,
    block_d: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused CountSketch apply + Gram: (B = SA, G = BᵀB), one HBM write of B."""
    interpret = resolve_interpret(interpret, A)
    m, n = A.shape
    acc = _acc_dtype(A.dtype)
    bm = min(block_m, max(8, m))
    bd = min(block_d, max(8, d))

    # A is not padded (see countsketch_apply): a partial last m-tile is
    # masked in the kernel.
    A_p = pad_to(A, (bm if m < bm else 1, max(128, n)))
    h_p = pad_to(buckets.astype(jnp.int32)[None, :], (1, bm))
    s_p = pad_to(signs.astype(jnp.float32)[None, :], (1, bm))
    m_p, n_p = A_p.shape
    specs = dict(
        m_blocks=cdiv(m_p, bm),
        specs=[
            pl.BlockSpec((1, bm), lambda di, mi: (0, mi)),
            pl.BlockSpec((1, bm), lambda di, mi: (0, mi)),
            pl.BlockSpec((bm, n_p), lambda di, mi: (mi, 0)),
        ],
    )
    kernel = partial(countsketch_gram_kernel, m=m_p if m_p % bm else None)
    return _fused_call(
        kernel, (h_p, s_p, A_p), specs, d, n, bd,
        interpret, acc,
    )


@partial(jax.jit, static_argnames=("block_m", "block_d", "interpret"))
def matmul_gram(
    S: jax.Array,
    A: jax.Array,
    *,
    block_m: int = 512,
    block_d: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused dense-sketch apply + Gram: (B = SA, G = BᵀB)."""
    interpret = resolve_interpret(interpret, S, A)
    d, m = S.shape
    n = A.shape[1]
    acc = _acc_dtype(A.dtype)
    bm = min(block_m, max(8, m))
    bd = min(block_d, max(8, d))

    S_p = pad_to(S, (bd, bm))
    A_p = pad_to(A, (bm, max(128, n)))
    m_p, n_p = A_p.shape
    specs = dict(
        m_blocks=m_p // bm,
        specs=[
            pl.BlockSpec((bd, bm), lambda di, mi: (di, mi)),
            pl.BlockSpec((bm, n_p), lambda di, mi: (mi, 0)),
        ],
    )
    return _fused_call(
        matmul_gram_kernel, (S_p, A_p), specs, d, n, bd, interpret, acc
    )


@partial(
    jax.jit,
    static_argnames=("d", "block_m", "block_d", "interpret"),
)
def gaussian_gram(
    A: jax.Array,
    key: jax.Array,
    d: int,
    *,
    scale: float | None = None,
    block_m: int = 512,
    block_d: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused in-kernel-PRNG Gaussian apply + Gram — S never exists in HBM."""
    interpret = resolve_interpret(interpret, A)
    m, n = A.shape
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    acc = _acc_dtype(A.dtype)
    bm = min(block_m, max(8, m))
    bd = min(block_d, max(8, d))

    A_p = pad_to(A, (bm, max(128, n)))
    m_p, n_p = A_p.shape
    k0, k1 = key_to_u32(key)
    k0 = k0.reshape(1, 1)
    k1 = k1.reshape(1, 1)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    specs = dict(
        m_blocks=m_p // bm,
        specs=[
            pl.BlockSpec((1, 1), lambda di, mi: (0, 0)),
            pl.BlockSpec((1, 1), lambda di, mi: (0, 0)),
            pl.BlockSpec((1, 1), lambda di, mi: (0, 0)),
            pl.BlockSpec((bm, n_p), lambda di, mi: (mi, 0)),
        ],
    )
    return _fused_call(
        make_gaussian_gram_kernel(d), (k0, k1, scale_arr, A_p), specs,
        d, n, bd, interpret, acc,
    )


def _lowp(A_arr: jax.Array, use_pallas: bool) -> jax.Array:
    """The mixed-precision data cast: round to bf16; on the reference
    backend upcast to f32 so accumulation runs ≥ f32 there too."""
    A_lp = A_arr.astype(jnp.bfloat16)
    return A_lp if use_pallas else A_lp.astype(jnp.float32)


def sketch_qr(
    op,
    A,
    *,
    backend: str = "auto",
    precision: str = "full",
    rounds: int = 2,
):
    """Fused sketch→QR: ``(Q, R, B)`` with B = S·A = Q·R.

    ``op`` is any ``repro.core.sketch`` operator, ``A`` a dense array or
    ``repro.core.linop`` operator.  Dispatches per family (see module
    docstring); Q, R and B are returned in A's dtype regardless of
    ``precision`` so downstream refinement runs at full working
    precision.  Equivalent to ``SketchedFactor.from_sketch(op.apply_op(A))``
    up to rounding, with a deterministic diag(R) ≥ 0 sign convention.
    """
    from ...core import backend as backend_lib
    from ...core import linop, sketch as sketch_lib

    if precision not in backend_lib.PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; have {backend_lib.PRECISIONS}"
        )
    rb = backend_lib.resolve(backend)
    A_op = linop.as_operator(A)
    working = A_op.dtype
    mixed = precision == "mixed"

    dense = isinstance(A_op, linop.DenseOperator)
    fusable = (
        rb.use_pallas
        and dense
        and A_op.shape[1] <= MAX_FUSED_COLS
        and isinstance(
            op,
            (
                sketch_lib.CountSketch,
                sketch_lib.GaussianSketch,
                sketch_lib.UniformDenseSketch,
                sketch_lib.SRHTSketch,
            ),
        )
    )

    if fusable:
        A_arr = _lowp(A_op.A, True) if mixed else A_op.A
        blocks = backend_lib.kernel_blocks(
            "tsqr", A_arr.shape[0], A_arr.shape[1], op.d, A_arr.dtype,
            interpret=rb.interpret,
        )
        if isinstance(op, sketch_lib.CountSketch):
            B, G = countsketch_gram(
                A_arr, op.buckets, op.signs.astype(A_arr.dtype), op.d,
                interpret=rb.interpret, **blocks,
            )
        elif isinstance(op, sketch_lib.GaussianSketch):
            B, G = gaussian_gram(
                A_arr, op.key, op.d, interpret=rb.interpret, **blocks
            )
        elif isinstance(op, sketch_lib.UniformDenseSketch):
            B, G = matmul_gram(
                op.S.astype(A_arr.dtype), A_arr, interpret=rb.interpret,
                **blocks,
            )
        else:  # SRHT: transform via its own kernels, Gram-fused QR half
            B = op.apply(A_arr, backend=backend)
            G = panel_gram(B, interpret=rb.interpret)
        B = B.astype(working)
        G = G.astype(working)
    else:
        from ...core.precond import _sketch_apply

        B = _sketch_apply(op, A_op, backend=backend, precision=precision)
        B = B.astype(working)
        G = matmul(B.T, B)
    Q, R = cholqr_finish(B, G, rounds=rounds)
    return Q, R, B
