"""Inputs of a cell, made on the device from the seed in one jitted call.

The sec. 5.1 generator of arXiv:2409.14309 ('fast' variant): A = G Sigma V^T
with G Gaussian scaled by 1/sqrt(m), V Haar (QR of a Gaussian), Sigma
log-spaced in [1/cond, 1]; every right-hand side b_j = A x_j + beta r_j/|r_j|
with x_j a random unit vector.  The benchmark makes its own data: the
reference that decides ``correct`` takes nothing the program made.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number of 64 bits (``--seed`` may pass 2**32)."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _generate(key, *, m, n, pool, cond, beta, dtype):
    k_g, k_v, k_x, k_r = jax.random.split(key, 4)
    sigma = jnp.logspace(0.0, -jnp.log10(cond), n, dtype=jnp.float32)
    V, _ = jnp.linalg.qr(jax.random.normal(k_v, (n, n), jnp.float32))
    G = jax.random.normal(k_g, (m, n), jnp.float32)
    right = (sigma[:, None] * V.T) / jnp.sqrt(jnp.float32(m))
    A = jnp.matmul(G, right, precision=lax.Precision.HIGHEST).astype(dtype)
    X = jax.random.normal(k_x, (n, pool), jnp.float32)
    X = X / jnp.linalg.norm(X, axis=0)
    R = jax.random.normal(k_r, (m, pool), jnp.float32)
    R = beta * R / jnp.linalg.norm(R, axis=0)
    B = jnp.matmul(A.astype(jnp.float32), X, precision=lax.Precision.HIGHEST) + R
    B = B.astype(dtype)
    return A, tuple(B[:, j] for j in range(pool)), X


def generate(key, cfg: dict, pool: int, devices) -> tuple:
    """(A, bs, X): A (m x n), ``pool`` right-hand sides as separate arrays,
    and their x_j (n x pool) by construction.

    With ``cfg["layout"] == "rows"`` A and each b are row-sharded over a 1-D
    mesh of ``devices`` as they are made, so no chip holds more than its
    share; otherwise everything lives on ``devices[0]``.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    kw = dict(m=int(cfg["m"]), n=int(cfg["n"]), pool=int(pool),
              cond=float(cfg["cond"]), beta=float(cfg["beta"]),
              dtype=jnp.dtype(cfg["dtype"]))
    if cfg.get("generator", "fast") != "fast":
        raise ValueError(f"unknown generator {cfg.get('generator')!r}")
    if cfg["layout"] == "rows":
        mesh = row_mesh(cfg, devices)
        rows = NamedSharding(mesh, P(cfg["mesh_axis"], None))
        vec = NamedSharding(mesh, P(cfg["mesh_axis"]))
        rep = NamedSharding(mesh, P())
        out = (rows, (vec,) * pool, rep)
    elif cfg["layout"] == "single":
        one = SingleDeviceSharding(devices[0])
        out = (one, (one,) * pool, one)
    else:
        raise ValueError(f"unknown layout {cfg['layout']!r}")
    gen = jax.jit(partial(_generate, **kw), out_shardings=out)
    A, bs, X = gen(key)
    jax.block_until_ready((A, bs, X))
    return A, list(bs), X


def row_mesh(cfg: dict, devices):
    """The 1-D mesh over ``devices`` that A's rows are sharded on."""
    from repro.sharding import make_mesh

    return make_mesh((len(devices),), (cfg["mesh_axis"],), devices=devices)
