"""Distributed sketched least-squares: scaling + comm accounting.

Runs the shard_map SAA-SAS on however many host devices this process has
(1 on the default CPU container — the multi-device path is exercised by the
dry-run and tests/test_distributed_lsq.py, which spawn dedicated
processes), and reports the collective payload per solve: one s×(n+1)
all-reduce + one n-vector psum per LSQR iteration — independent of m.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import generate_problem, sketched_lstsq
from repro.core.distributed import shard_rows
from repro.sharding import make_mesh

from .common import emit, time_fn


def run(m=32768, n=128, seed=0):
    ndev = len(jax.devices())
    mesh = make_mesh((ndev,), ("data",))
    prob = generate_problem(
        jax.random.key(seed), m, n, cond=1e10, beta=1e-10, method="fast"
    )
    A, b = shard_rows(mesh, ("data",), prob.A, prob.b)
    key = jax.random.key(seed + 1)

    t = time_fn(lambda: sketched_lstsq(A, b, key, mesh=mesh).x)
    r = sketched_lstsq(A, b, key, mesh=mesh)
    s = 4 * n
    sketch_bytes = s * (n + 1) * 8
    per_iter_bytes = (n + 3) * 8
    emit(
        "dist/sketched_lstsq",
        t,
        f"devices={ndev};itn={int(r.itn)};allreduce_bytes_sketch={sketch_bytes};"
        f"allreduce_bytes_per_lsqr_iter={per_iter_bytes};m_independent=True",
    )
