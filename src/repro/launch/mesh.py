"""Production meshes.

Defined as functions (not module constants) so importing never touches jax
device state.  Production target: TPU v5e pods — 16×16 = 256 chips per pod,
2 pods = 512 chips for the multi-pod dry-run.
"""
from __future__ import annotations

import jax
import numpy as np

from .. import sharding

__all__ = ["make_production_mesh", "make_mesh", "HW", "PEAKS", "peaks"]


# Per-chip peaks for the roofline models, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect).  ``ici_bw`` is
# the per-link, per-direction share of that interconnect (an assumption of
# the dry-run's collective model, not a published number).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,
        "hbm_bw": 819e9,
        "ici_bw": 50e9,
        "hbm_bytes": 16e9,
    },
}

# The production target of the dry-run (a v5e pod, described, not attached).
HW = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> dict:
    """The peak table of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} "
            "(dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count "
            "before any jax import)"
        )
    return sharding.make_mesh(shape, axes, devices=devices[:n])
