"""Tile-size autotuner for the Pallas sketch kernels.

Picks (block_m, block_d, block_n) per kernel family by sweeping candidate
block shapes against the same roofline cost model ``benchmarks/roofline.py``
reports from: predicted time = max(HBM traffic / bandwidth, flops / peak)
plus a per-grid-step launch overhead.  The traffic term is the one that
actually differentiates block shapes — grids that revisit an input tile
across an outer axis (e.g. the dense sketch re-reads S once per n-block,
and A is re-read once per d-block) pay for each revisit, so larger
blocks along the revisited axes trade VMEM footprint for HBM traffic.
Candidates that overflow the VMEM budget are discarded before costing.
The CountSketch kernel is not tuned: its blocks follow from the operand's
shape (``repro.kernels.countsketch.ops``).

Winners are cached in-repo at ``src/repro/kernels/autotune_cache.json``,
keyed ``"{kind}|m={m}|n={n}|d={d}|{dtype}|{device}"`` with ``device`` the
``jax.Device.device_kind`` string as JAX reports it (``"TPU v5 lite"`` on a
v5e).  The committed winners were chosen by the cost model, not measured.
``best_blocks`` is the runtime entry point — exact cache hits return the
committed winner, misses fall back to the cost model on the fly (memoized
per process).  The cost model reads the device's peaks from
``repro.launch.mesh.peaks`` and raises for a device it has no table for.
The backend policy (``repro.core.backend.kernel_blocks``) consults it for
every kernel dispatch; set ``REPRO_AUTOTUNE=0`` to force the kernels'
hand-tuned defaults.

Regenerate the cache after kernel/geometry changes::

    PYTHONPATH=src python -m repro.kernels.autotune --write
"""
from __future__ import annotations

import argparse
import functools
import json
import logging
from pathlib import Path

import jax
import jax.numpy as jnp

from ..launch.mesh import peaks

__all__ = ["best_blocks", "predict_cost", "CACHE_PATH", "KINDS"]

CACHE_PATH = Path(__file__).with_name("autotune_cache.json")
CACHE_SCHEMA = 1

_log = logging.getLogger(__name__)
# One warning per unseen (kind, shape, dtype, device) key per process: a
# miss means every dispatch at this shape runs on modeled blocks, which is
# worth knowing once — not once per kernel launch.
_MISS_WARNED: set[str] = set()

# Kernel families the tuner knows, with the block kwargs each accepts.
KINDS = {
    "sketch_matmul": ("block_d", "block_m", "block_n"),
    "gaussian": ("block_d", "block_m", "block_n"),
    "srht": ("block_n",),
    "tsqr": ("block_m", "block_d"),
}
_ALIASES = {"uniform_dense": "sketch_matmul"}

# VMEM budget per grid step for the model's working set below: the
# double-buffered input and output tiles plus the large in-kernel tiles.
# Mosaic's scoped-VMEM limit on v5e is 16 MiB; the margin covers what the
# model does not count.  Checked against compiles for a described v5e.
VMEM_BUDGET = 12 * 1024 * 1024
# 32-bit tiles contract at full fp32 precision (``kernels.common.mxu_dot``),
# which Mosaic splits into several bf16 passes with VMEM temporaries the
# model above does not see.  Tiles of 32-bit kernels stay under these
# element counts (A tile, S or one-hot tile, output tile), the envelope
# that compiled for a described v5e.
_FP32_TILE_ELEMS = (1 << 18, 1 << 19, 1 << 18)
_STEP_OVERHEAD_S = 5e-7  # per-grid-step launch cost; penalizes tiny blocks

_BLOCK_M = (128, 256, 512, 1024, 2048)
_BLOCK_D = (128, 256, 512, 1024)
_BLOCK_N = (128, 256, 512)


def _dtype_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _peak_flops(dtype, hw: dict) -> float:
    peak = float(hw["peak_flops_bf16"])
    # MXU fp32 runs at roughly half the bf16 rate; fp64 emulation far slower.
    itemsize = _dtype_bytes(dtype)
    if itemsize <= 2:
        return peak
    if itemsize == 4:
        return peak / 2
    return peak / 8


def predict_cost(
    kind: str, m: int, n: int, d: int, dtype, blocks: dict, device: str
) -> float:
    """Roofline-predicted seconds for one kernel launch with these blocks
    on ``device`` (a ``device_kind`` with a peak table).

    Returns ``inf`` for configs whose VMEM working set exceeds the budget,
    so infeasible candidates lose every comparison.
    """
    hw = peaks(device)
    kind = _ALIASES.get(kind, kind)
    b = _dtype_bytes(dtype)
    acc_b = max(b, 4)  # half inputs accumulate in f32
    n_p = max(128, n)
    bm = blocks.get("block_m", m)
    bd = blocks.get("block_d", d)
    bn = blocks.get("block_n", n_p)
    m_blocks = _cdiv(m, bm)
    d_blocks = _cdiv(d, bd)
    n_blocks = _cdiv(n_p, bn)

    flops = 2.0 * m * n * d
    if kind == "sketch_matmul":
        traffic = d * m * b * n_blocks + m * n * b * d_blocks + d * n * b
        # S and A tiles, output tile, and the f32 dot result
        vmem = 2 * ((bd * bm + bm * bn) * b + bd * bn * acc_b) + bd * bn * 4
        steps = m_blocks * d_blocks * n_blocks
    elif kind == "gaussian":
        # S is generated in-kernel: no S traffic, but the threefry+Box-Muller
        # pipeline costs ~32 scalar ops per S element, re-done per n-block.
        traffic = m * n * b * d_blocks + d * n * b
        flops += 32.0 * d * m * n_blocks
        # A tile, output tile, and the generated S tile (f32 and its cast)
        vmem = 2 * (bm * bn * b + bd * bn * acc_b) + 2 * bd * bm * 4
        steps = m_blocks * d_blocks * n_blocks
    elif kind == "srht":
        # two-stage FWHT over m_pad rows: log2(m) butterfly sweeps, each a
        # read+write of the full (m_pad, bn) working set per column block.
        m_pad = 1 << max(1, (m - 1).bit_length())
        sweeps = max(1, m_pad.bit_length() - 1)
        flops = 2.0 * m_pad * n * sweeps
        traffic = 4.0 * m_pad * n * b + d * n * b
        vmem = min(m_pad, 2048) * bn * b
        steps = n_blocks
    elif kind == "tsqr":
        # fused sketch→Gram: A re-read per d-block, B written once (never
        # re-read), Gram folded from VMEM-resident panels.
        traffic = m * n * b * d_blocks + d * m * b + d * n * acc_b
        flops += 2.0 * d * n * n
        vmem = bd * bm * b + bm * n_p * b + (bd * n_p + n_p * n_p) * acc_b
        steps = m_blocks * d_blocks
    else:
        raise ValueError(f"unknown autotune kind {kind!r}; have {sorted(KINDS)}")

    if vmem > VMEM_BUDGET:
        return float("inf")
    if b >= 4 and kind != "srht":
        tile_n = n_p if kind == "tsqr" else bn
        a_cap, s_cap, o_cap = _FP32_TILE_ELEMS
        if bm * tile_n > a_cap or bd * bm > s_cap or bd * tile_n > o_cap:
            return float("inf")
    hbm_bw = float(hw["hbm_bw"])
    return (
        max(traffic / hbm_bw, flops / _peak_flops(dtype, hw))
        + steps * _STEP_OVERHEAD_S
    )


def _candidates(kind: str, m: int, n: int, d: int):
    kind = _ALIASES.get(kind, kind)
    n_p = max(128, n)
    bms = sorted({min(v, max(8, m)) for v in _BLOCK_M})
    bds = sorted({min(v, max(8, d)) for v in _BLOCK_D})
    bns = sorted({min(v, n_p) for v in _BLOCK_N})
    if kind == "srht":
        for bn in bns:
            yield {"block_n": bn}
    elif kind == "tsqr":
        for bm in bms:
            for bd in bds:
                yield {"block_m": bm, "block_d": bd}
    else:
        for bm in bms:
            for bd in bds:
                for bn in bns:
                    yield {"block_m": bm, "block_d": bd, "block_n": bn}


def _device_kind() -> str:
    return jax.devices()[0].device_kind


def _key(kind: str, m: int, n: int, d: int, dtype, device: str) -> str:
    return f"{kind}|m={m}|n={n}|d={d}|{jnp.dtype(dtype).name}|{device}"


@functools.lru_cache(maxsize=1)
def _load_cache() -> dict:
    try:
        data = json.loads(CACHE_PATH.read_text())
        if data.get("schema") == CACHE_SCHEMA:
            return data.get("entries", {})
    except (OSError, ValueError):
        pass
    return {}


@functools.lru_cache(maxsize=4096)
def _model_best(
    kind: str, m: int, n: int, d: int, dtype_name: str, device: str
) -> tuple:
    best, best_cost = None, float("inf")
    for cand in _candidates(kind, m, n, d):
        c = predict_cost(kind, m, n, d, dtype_name, cand, device)
        if c < best_cost:
            best, best_cost = cand, c
    # with no VMEM-feasible candidate the kernel's own defaults ({}) run
    return tuple(sorted((best or {}).items()))


def best_blocks(
    kind: str, m: int, n: int, d: int, dtype, device: str | None = None
) -> dict:
    """Winning block kwargs for this (kind, shape, dtype, device).

    Committed-cache hit first, cost model on miss.  The returned dict uses
    the kernel wrapper's own kwarg names and can be splatted directly:
    ``sketch_matmul(S, A, **best_blocks("sketch_matmul", ...))``.
    """
    kind = _ALIASES.get(kind, kind)
    if kind not in KINDS:
        raise ValueError(f"unknown autotune kind {kind!r}; have {sorted(KINDS)}")
    if device is None:
        device = _device_kind()
    key = _key(kind, m, n, d, dtype, device)
    hit = _load_cache().get(key)
    if hit is not None:
        return {k: v for k, v in hit.items() if k in KINDS[kind]}
    blocks = dict(_model_best(kind, m, n, d, jnp.dtype(dtype).name, device))
    if key not in _MISS_WARNED:
        _MISS_WARNED.add(key)
        _log.warning(
            "autotune cache miss for %s: no committed winner, falling back "
            "to roofline-model blocks %s (run `python -m repro.kernels."
            "autotune` on this device to sweep and pin real winners)",
            key, blocks or "{} (kernel defaults)",
        )
    return blocks


# ---------------------------------------------------------------------------
# cache generation


def _sweep_shapes():
    for n in (64, 128, 256, 512):
        for m in (4096, 16384, 65536):
            d = min(4 * n, m // 2)
            yield m, n, d


def write_cache(device: str | None = None, path: Path | None = None) -> dict:
    """Sweep canonical paper shapes and write the winners JSON."""
    if device is None:
        device = _device_kind()
    entries = {}
    for kind in KINDS:
        for m, n, d in _sweep_shapes():
            for dtype in ("float32", "bfloat16"):
                entries[_key(kind, m, n, d, dtype, device)] = dict(
                    _model_best(kind, m, n, d, dtype, device)
                )
    payload = {"schema": CACHE_SCHEMA, "entries": entries}
    out = path or CACHE_PATH
    out.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    _load_cache.cache_clear()
    return entries


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--write", action="store_true", help="regenerate the cache")
    ap.add_argument("--device", default=None, help="override device kind key")
    args = ap.parse_args(argv)
    if args.write:
        entries = write_cache(device=args.device)
        print(f"wrote {len(entries)} entries to {CACHE_PATH}")
        return 0
    device = args.device or _device_kind()
    for m, n, d in _sweep_shapes():
        for kind in KINDS:
            blocks = best_blocks(kind, m, n, d, "float32", device=device)
            print(f"{kind:14s} m={m:6d} n={n:3d} d={d:4d} -> {blocks}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
