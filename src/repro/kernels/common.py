"""Shared utilities for the TPU Pallas kernels.

Includes a pure-jnp threefry2x32 (bit-identical to the algorithm JAX's own
PRNG uses) that is written with uint32 add/xor/shift only, so the *same
function* runs inside a Pallas kernel body (Mosaic) and in the ``ref.py``
oracles — fused generate-and-multiply kernels are therefore bitwise
testable against their references.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "pad_to",
    "cdiv",
    "threefry2x32",
    "bits_to_gaussian",
    "key_to_u32",
    "resolve_interpret",
    "mosaic_context",
    "mxu_dot",
    "matmul",
    "vdot",
]

# Every contraction of the solvers states its precision, in XLA code
# (``matmul``/``vdot``) and inside the kernels (``mxu_dot``).  On a TPU the
# default contracts float32 operands in one bfloat16 pass (relative error
# ~2^-9), which the refinement loops cannot recover from; HIGHEST keeps
# float32 work in float32, costs nothing for bfloat16 operands and is
# ignored on the CPU.  Being part of each operation, it holds in every
# thread and under every caller's ``jax.default_matmul_precision``.
HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b):
    """``a @ b`` in XLA at full working precision."""
    return jnp.matmul(a, b, precision=HIGHEST)


def vdot(a, b):
    """``jnp.vdot(a, b)`` at full working precision."""
    return jnp.vdot(a, b, precision=HIGHEST)


def mxu_dot(a, b, acc_dtype, *, contract=((1,), (0,))):
    """``a · b`` inside a kernel, accumulated in ``acc_dtype``.

    Mosaic's default contracts float32 tiles in a single bfloat16 pass
    (relative error ~2^-9), which turns a "full precision" sketch into a
    mixed-precision one.  Operands of 32 bits or more therefore ask for
    full fp32 contraction (``Precision.HIGHEST``); 16-bit operands take the
    native single pass (Mosaic refuses fp32 contraction of bf16 tiles).
    ``contract`` gives the contracting dimensions of ``a`` and ``b``.
    """
    dtype = jnp.promote_types(a.dtype, b.dtype)
    precision = HIGHEST if dtype.itemsize >= 4 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (contract, ((), ())),
        precision=precision, preferred_element_type=acc_dtype,
    )


def mosaic_context(interpret: bool):
    """The context a kernel wrapper calls ``pl.pallas_call`` in.

    Under ``jax_enable_x64`` the integer constants of the BlockSpec index
    maps trace as int64, which Mosaic cannot lower.  A compiled kernel
    never takes a 64-bit operand (``resolve_interpret`` refuses them), so
    tracing its pallas_call with 64-bit types off changes no value.
    Interpret mode keeps the caller's setting (it runs float64 kernels).
    """
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)


def resolve_interpret(interpret: bool | None, *arrays) -> bool:
    """The ``interpret=`` argument of every kernel wrapper, checked.

    ``None`` means real Mosaic on a TPU and interpret mode elsewhere (see
    ``repro.core.backend.default_interpret``).  Interpret mode is refused
    while a TPU is attached, so a timing on the chip is always the
    compiled kernel.  A compiled kernel refuses float64 operands here, at
    dispatch: Mosaic has no 64-bit floats, and rerouting them to another
    path would hide which code ran.
    """
    from ..core.backend import default_interpret

    on_tpu = not default_interpret()
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "interpret=True with a TPU attached: the Pallas kernels run "
            "compiled on the chip"
        )
    if not interpret:
        for x in arrays:
            if jnp.dtype(x.dtype) == jnp.float64:
                raise TypeError(
                    "float64 operand for a compiled TPU Pallas kernel: Mosaic "
                    "has no 64-bit floats. Cast the data to float32 (or "
                    "bfloat16), or use backend='reference'."
                )
    return bool(interpret)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x: jax.Array, multiples: tuple[int, ...], value=0) -> jax.Array:
    """Zero-pad each axis of ``x`` up to the next multiple."""
    pads = []
    for dim, mult in zip(x.shape, multiples):
        target = cdiv(dim, mult) * mult if mult else dim
        pads.append((0, target - dim))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads, constant_values=value)


def key_to_u32(key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Split a jax PRNG key into its two uint32 words."""
    data = jax.random.key_data(key).astype(jnp.uint32)
    return data[..., 0], data[..., 1]


_ROTS_A = (13, 15, 26, 6)
_ROTS_B = (17, 29, 16, 24)


def _rotl(x, r):
    r = np.uint32(r)
    return (x << r) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32, 20 rounds (the algorithm behind jax.random).

    All inputs uint32 arrays (broadcastable); returns two uint32 arrays.
    Pure uint32 add/xor/rotate — runs identically in jnp and Pallas/Mosaic.
    """
    k0 = k0.astype(jnp.uint32)
    k1 = k1.astype(jnp.uint32)
    x0 = x0.astype(jnp.uint32)
    x1 = x1.astype(jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for g in range(1, 6):
        rots = _ROTS_A if g % 2 == 1 else _ROTS_B
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[g % 3]
        x1 = x1 + ks[(g + 1) % 3] + np.uint32(g)
    return x0, x1


def _u24(bits):
    return (bits >> np.uint32(8)).astype(jnp.int32)


def bits_to_gaussian(b0, b1, dtype=jnp.float32):
    """Box–Muller on two uint32 bit streams -> one N(0,1) stream."""
    # 24-bit mantissa uniforms in (0, 1).  The top 24 bits fit an int32
    # exactly, and Mosaic has no uint32 -> float cast, so go through int32:
    # the same values in jnp and in a kernel.
    u1 = _u24(b0).astype(dtype) * dtype(2**-24) + dtype(2**-25)
    u2 = _u24(b1).astype(dtype) * dtype(2**-24)
    r = jnp.sqrt(-2.0 * jnp.log(u1)).astype(dtype)
    theta = (2.0 * np.pi * u2).astype(dtype)
    return r * jnp.cos(theta)
