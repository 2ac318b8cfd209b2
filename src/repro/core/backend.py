"""Backend policy for the sketch applies.

One place decides which implementation of each sketch apply runs:

- ``"reference"`` — the pure-jnp paths in ``repro.core.sketch`` (segment_sum
  CountSketch, recursive FWHT SRHT, materialized-S matmuls).  Always
  available, always exact, the oracle every other backend is tested against.
- ``"pallas"``   — the TPU Pallas kernels in ``repro.kernels``
  (``countsketch_apply``, ``srht_apply``, ``fused_gaussian_sketch``,
  ``sketch_matmul``).  Off-TPU these run in ``interpret=True`` mode, so CPU
  containers exercise the exact kernel semantics (same tiling, same
  accumulation order, same in-kernel PRNG) without a TPU.
- ``"auto"``     — resolve per platform: ``"pallas"`` on TPU, ``"reference"``
  everywhere else.

``resolve`` is called at trace time (``backend`` is a static argument of the
solvers), so the choice costs nothing at runtime.  The environment variable
``REPRO_SKETCH_BACKEND`` overrides ``"auto"`` — useful for flipping a whole
benchmark run without touching call sites.

Sketch kinds without a matching kernel (``sparse_sign``, ``uniform_sparse``)
fall back to the reference path under ``"pallas"``; ``kernel_backed`` tells
you which kinds actually dispatch.

The same knob picks the heavy-ball refinement's pass over A
(``repro.core.iterative.heavy_ball_refine``): under ``"pallas"`` a dense
float32 A with a vector right-hand side is read once per iteration by the
``residual_pass`` kernel (``repro.core.linop.one_pass``); under
``"reference"``, and for every other input, r = b − A x and Aᵀr are two
XLA products.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax

__all__ = [
    "BACKENDS",
    "KERNEL_BACKED_KINDS",
    "PRECISIONS",
    "ResolvedBackend",
    "resolve",
    "resolve_backend_arg",
    "resolve_fused",
    "default_interpret",
    "kernel_backed",
    "kernel_blocks",
    "INTERPRET_TARGET",
]

BACKENDS = ("auto", "reference", "pallas")

# Working precisions for the sketch/factor stage.  "full" runs everything in
# the data dtype; "mixed" rounds the data matrix to bf16 for the sketch apply
# (accumulating in >= f32) and leaves all refinement at full precision — the
# certified driver escalates mixed -> full automatically when a certificate
# fails (see core/lstsq.py).
PRECISIONS = ("full", "mixed")

# Sketch kinds whose apply has a Pallas kernel behind it.
KERNEL_BACKED_KINDS = frozenset(
    {"gaussian", "uniform_dense", "srht", "countsketch", "clarkson_woodruff"}
)


@dataclasses.dataclass(frozen=True)
class ResolvedBackend:
    """A concrete backend decision: which path, and interpret mode or not."""

    name: str  # "reference" | "pallas"
    interpret: bool  # pallas interpret mode (True off-TPU)

    @property
    def use_pallas(self) -> bool:
        return self.name == "pallas"


def default_interpret(platform: str | None = None) -> bool:
    """Pallas interpret mode default: real Mosaic on TPU, interpret elsewhere."""
    if platform is None:
        platform = jax.default_backend()
    return platform != "tpu"


def resolve(backend: str = "auto", platform: str | None = None) -> ResolvedBackend:
    """Resolve a ``backend`` knob to a concrete :class:`ResolvedBackend`.

    ``platform`` defaults to ``jax.default_backend()``; pass it explicitly to
    test the policy without that platform attached.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")
    if platform is None:
        platform = jax.default_backend()
    if backend == "auto":
        backend = os.environ.get("REPRO_SKETCH_BACKEND", "auto")
        if backend not in BACKENDS:
            raise ValueError(
                f"REPRO_SKETCH_BACKEND={backend!r} invalid; have {BACKENDS}"
            )
    if backend == "auto":
        backend = "pallas" if platform == "tpu" else "reference"
    return ResolvedBackend(name=backend, interpret=default_interpret(platform))


def kernel_backed(kind: str) -> bool:
    """True if ``kind``'s apply dispatches to a Pallas kernel under "pallas"."""
    return kind in KERNEL_BACKED_KINDS


# Off-TPU the kernels run in interpret mode with the tiles they would use
# on this chip, so CPU tests cover the chip's tiling.
INTERPRET_TARGET = "TPU v5 lite"


def kernel_blocks(
    kind: str, m: int, n: int, d: int, dtype, *, interpret: bool
) -> dict:
    """Autotuned block-shape kwargs for a kernel dispatch site.

    Consults ``repro.kernels.autotune`` (committed cache first, roofline cost
    model on miss) for the attached device — or, in interpret mode, for
    :data:`INTERPRET_TARGET` — and returns kwargs splat-able into the kernel
    wrapper.  ``{}`` means "use the kernel's hand-tuned defaults", which is
    the answer when the tuner is disabled (``REPRO_AUTOTUNE=0``).  Tuner
    errors (such as a device with no peak table) propagate.
    """
    if os.environ.get("REPRO_AUTOTUNE", "1") == "0":
        return {}
    from ..kernels.autotune import best_blocks

    device = INTERPRET_TARGET if interpret else jax.devices()[0].device_kind
    return best_blocks(kind, m, n, d, dtype, device=device)


def resolve_fused(fused: bool | None) -> bool:
    """Resolve the fused sketch->QR knob.  ``None`` reads ``REPRO_FUSED_QR``
    (default off, preserving the seed pipeline's exact numerics)."""
    if fused is None:
        return os.environ.get("REPRO_FUSED_QR", "0") not in ("0", "", "false")
    return bool(fused)


def resolve_backend_arg(fn):
    """Resolve a solver's ``backend=`` kwarg to a concrete name BEFORE jit.

    ``backend`` is a static jit argument; if the literal string "auto"
    reached the cache key, the platform/env resolution would be baked in at
    first trace and later ``REPRO_SKETCH_BACKEND`` flips silently ignored.
    Resolving at python-call time keeps the cache keyed on the concrete
    backend ("reference"/"pallas") and re-reads the policy every call.
    """

    @functools.wraps(fn)
    def wrapper(*args, backend: str = "auto", **kw):
        return fn(*args, backend=resolve(backend).name, **kw)

    return wrapper
