"""Sketched gradient compression for data-parallel training.

The paper's CountSketch operator applied to the framework's own
collective bottleneck: instead of all-reducing full gradients over the DP
axis, each worker sketches large gradient tensors into a fixed s-bucket
space (CountSketch is linear, so psum-of-sketches == sketch-of-psum),
all-reduces the sketches, and unsketches with the transpose (SᵀS has unit
diagonal; E[SᵀSx] = x).  The unsketch error is kept *local* via standard
error feedback (the residual is added to the next step's gradient), so
compression changes the optimization trajectory only transiently.

Collective-bytes reduction: ratio = numel / sketch_size per tensor.
Small tensors (norms, biases) bypass compression.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["CompressionConfig", "compress_state_init", "sketched_psum_grads"]


class CompressionConfig(NamedTuple):
    ratio: int = 8  # sketch_size = numel // ratio
    min_size: int = 65536  # tensors smaller than this go uncompressed
    error_feedback: bool = True
    seed: int = 17


def _buckets_signs(key, numel, s):
    kb, ks = jax.random.split(key)
    buckets = jax.random.randint(kb, (numel,), 0, s, dtype=jnp.int32)
    signs = jax.random.rademacher(ks, (numel,), jnp.float32)
    return buckets, signs


def compress_state_init(cfg: CompressionConfig, params):
    """Error-feedback residual buffers (zeros, like-sharded with params)."""
    def init(p):
        if p.size < cfg.min_size:
            return None
        return jnp.zeros(p.shape, jnp.float32)

    return jax.tree.map(init, params)


def sketched_psum_grads(
    cfg: CompressionConfig,
    grads,
    ef_state,
    axis_names,
    step=0,
):
    """psum gradients over ``axis_names`` with CountSketch compression.

    Must be called inside shard_map/pmap context where ``axis_names`` are
    bound.  Returns (avg_grads, new_ef_state).

    ``step`` MUST vary per call (fresh sketch per step).

    The applied reconstruction is **SᵀS(g+e)/ratio**: the raw unsketch is
    unbiased but has ‖x − SᵀSx‖ ≈ √(ratio−1)·‖x‖ > ‖x‖ — NOT a
    contraction, so error feedback amplifies geometrically (measured:
    ‖e‖² → 1e8 in 12 steps).  Scaling by 1/ratio gives
    ‖x − C(x)‖² ≈ (1 − 1/ratio)·‖x‖² — contractive with δ = 1/ratio, the
    standard EF treatment of unbiased high-variance compressors; the
    1/ratio gain is recovered over ~ratio steps through the feedback.
    """
    n_dev = 1
    for ax in axis_names:
        n_dev *= jax.lax.axis_size(ax)

    flat, treedef = jax.tree.flatten(grads)
    flat_ef = treedef.flatten_up_to(ef_state) if ef_state is not None else [None] * len(flat)
    out, out_ef = [], []
    for i, (g, ef) in enumerate(zip(flat, flat_ef)):
        if g.size < cfg.min_size:
            out.append(jax.lax.psum(g, axis_names) / n_dev)
            out_ef.append(ef)
            continue
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(cfg.seed), i), step
        )
        numel = g.size
        s = max(numel // cfg.ratio, 1)
        buckets, signs = _buckets_signs(key, numel, s)

        gf = g.astype(jnp.float32).reshape(-1)
        if cfg.error_feedback and ef is not None:
            gf = gf + ef.reshape(-1)
        sk = jax.ops.segment_sum(signs * gf, buckets, num_segments=s)
        sk_global = jax.lax.psum(sk, axis_names) / n_dev
        recon = (signs * sk_global[buckets]).astype(jnp.float32) / cfg.ratio
        if cfg.error_feedback and ef is not None:
            # local error: my contribution minus what the global recon
            # carries of it (same 1/ratio scaling -> contraction)
            local_recon = (signs * sk[buckets]) / cfg.ratio
            new_ef = (gf - local_recon).reshape(g.shape)
            out_ef.append(new_ef)
        else:
            out_ef.append(ef)
        out.append(recon.reshape(g.shape).astype(g.dtype))

    new_ef = treedef.unflatten(out_ef) if ef_state is not None else None
    return treedef.unflatten(out), new_ef
