"""TPU Pallas kernels for the sketch applies (the paper's compute hot path).

Each subpackage has ``kernel.py`` (pl.pallas_call body + BlockSpec tiling),
``ops.py`` (jit'd public wrapper) and ``ref.py`` (pure-jnp oracle).  The
BlockSpecs target TPU v5e VMEM/MXU geometry (128-lane tiles, ≤2 MiB working
sets).

These kernels are the ``"pallas"`` backend of the sketching operators in
``repro.core.sketch``: ``op.apply(A, backend="pallas")`` routes CountSketch
→ ``countsketch_apply``, SRHT → ``srht_apply``, Gaussian →
``fused_gaussian_sketch`` (regenerating the operator's S in-kernel from its
key) and uniform-dense → ``sketch_matmul``; the solvers (``saa_sas``,
``sap_sas``, ``sketched_lstsq``) expose the same knob as a static
``backend=`` argument.  The per-platform default — and the ``interpret=None``
resolution of every wrapper here (real Mosaic on TPU, ``interpret=True``
elsewhere, so CPU containers still execute the exact kernel semantics) —
lives in one policy module, ``repro.core.backend``.
"""
from .countsketch import countsketch_apply, countsketch_ref
from .residual_pass import residual_pass, residual_pass_ref
from .sketch_matmul import (
    fused_gaussian_ref,
    fused_gaussian_sketch,
    gaussian_cols_ref,
    gaussian_matrix_ref,
    sketch_matmul,
    sketch_matmul_ref,
)
from .srht import hadamard_matrix, hadamard_transform, srht_apply, srht_ref
from .tsqr import (
    cholqr_finish,
    panel_gram,
    panel_gram_ref,
    sketch_qr,
    tsqr,
    tsqr_ref,
)

__all__ = [
    "cholqr_finish",
    "panel_gram",
    "panel_gram_ref",
    "sketch_qr",
    "tsqr",
    "tsqr_ref",
    "countsketch_apply",
    "countsketch_ref",
    "residual_pass",
    "residual_pass_ref",
    "fused_gaussian_ref",
    "fused_gaussian_sketch",
    "gaussian_cols_ref",
    "gaussian_matrix_ref",
    "sketch_matmul",
    "sketch_matmul_ref",
    "hadamard_matrix",
    "hadamard_transform",
    "srht_apply",
    "srht_ref",
]
