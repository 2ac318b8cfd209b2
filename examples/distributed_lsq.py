"""Distributed sketch-and-solve: row-sharded A over every device JAX sees.

``lstsq`` sees that A's rows are split over the mesh and runs the
row-sharded SAA-SAS (``repro.core.distributed.sketched_lstsq``).  Each shard
applies the shared ``CountSketch`` operator to its local rows
(into the global bucket space); one s x (n+1) all-reduce assembles the
sketch; LSQR runs distributed with psum-reduced inner products.
Communication is independent of m.  ``--backend pallas`` routes the local
applies through the Pallas kernel (interpret mode off-TPU).  On a CPU host,
simulate devices by setting ``XLA_FLAGS`` before the run:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_lsq.py [--backend auto]
"""
import argparse

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp

from repro.core import generate_problem, lstsq, qr_solve
from repro.core.distributed import shard_rows
from repro.sharding import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=("auto", "reference", "pallas"),
                    default="auto", help="local sketch-apply backend")
    args = ap.parse_args()

    mesh = make_mesh((len(jax.devices()),), ("data",))
    m, n = 65536, 128
    prob = generate_problem(jax.random.key(0), m, n, cond=1e8, beta=1e-10)
    A, b = shard_rows(mesh, ("data",), prob.A, prob.b)
    print(f"A: {A.shape} sharded as {A.sharding.spec} over {len(jax.devices())} devices")

    res = lstsq(A, b, jax.random.key(1), backend=args.backend)
    print(f"lstsq -> {res.method}")
    x_ref = qr_solve(prob.A, prob.b)
    err_vs_truth = float(jnp.linalg.norm(res.x - prob.x_true) / jnp.linalg.norm(prob.x_true))
    err_vs_qr = float(jnp.linalg.norm(res.x - x_ref) / jnp.linalg.norm(x_ref))
    s = 4 * n
    print(f"converged istop={int(res.istop)} in {int(res.itn)} LSQR iterations")
    print(f"relative error vs x_true: {err_vs_truth:.3e}   vs QR: {err_vs_qr:.3e}")
    print(f"comm per solve: one all-reduce of {s*(n+1)*8/1e6:.2f} MB (sketch) "
          f"+ {int(res.itn)} x {(n+3)*8} B (LSQR) — independent of m={m}")


if __name__ == "__main__":
    main()
