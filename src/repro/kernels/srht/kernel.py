"""Blocked Walsh–Hadamard transform as a TPU Pallas kernel.

The classic FHT butterfly has stride-2^k access patterns — hostile to VMEM
tiling.  On TPU we instead use the Kronecker factorization

    H_{c₁·c₂·…·c_k} = H_{c₁} ⊗ H_{c₂} ⊗ … ⊗ H_{c_k}

(valid for Sylvester Hadamard matrices, H_{2^p} = H_2^{⊗p}), which turns the
transform into k dense ±1 **matmuls**, one per axis of the (c₁, …, c_k, n)
view of x — exactly what the MXU wants.  Each stage views x as (a, c, b),
with c the axis being transformed, and computes

    y[i] = H_c · x[i]        for every i < a        (``hadamard_axis_kernel``)

over (c, bk) column tiles of b.  Flop cost rises from O(m log m) adds to
O(m·Σcᵢ) MACs, but every stage streams each element once from HBM.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..common import mxu_dot


def hadamard_axis_kernel(h_ref, x_ref, o_ref):
    """x block (1, c, bk);  h (c, c);  o = h @ x, accumulated in ≥ f32."""
    acc = jnp.promote_types(o_ref.dtype, jnp.float32)
    o_ref[0, ...] = mxu_dot(h_ref[...], x_ref[0, ...], acc).astype(o_ref.dtype)
