"""Production training launcher.

    python -m repro.launch.train --arch qwen3-0.6b --smoke \\
        --mesh 1x1 --steps 50 --ckpt /tmp/ck

On real fleets: one process per host, jax.distributed.initialize() picks
up the pod topology, ``--mesh 16x16`` / ``--mesh 2x16x16`` selects the
production mesh; elastic restart = same command after rescheduling (the
checkpoint restores onto whatever mesh the surviving slice supports, see
repro.train.elastic).  On this CPU container use --smoke + a 1x1/2x2 mesh
with XLA_FLAGS device forcing.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ..configs import get_config, smoke_config
from ..data import SyntheticConfig, batch_at
from ..optim import AdamWConfig
from ..sharding import logical_to_spec
from ..train import checkpoint as ckpt_lib
from ..train.elastic import restore_elastic
from ..train.step import batch_pspec, init_train_state, jit_train_step, state_pspecs
from .mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--mesh", default="1x1", help="e.g. 16x16 or 2x16x16")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    dims = tuple(int(d) for d in args.mesh.split("x"))
    names = ("pod", "data", "model")[-len(dims):] if len(dims) > 1 else ("data",)
    mesh = make_mesh(dims, names)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dcfg = SyntheticConfig(vocab=cfg.vocab, seq_len=args.seq,
                           global_batch=args.batch, kind="bigram")
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps)

    start = 0
    if args.ckpt and ckpt_lib.latest_step(args.ckpt) is not None:
        state, start = restore_elastic(args.ckpt, cfg, mesh)
        print(f"[resume] step {start} onto mesh {dims}")
    else:
        state = init_train_state(cfg, jax.random.key(0))
        sspec = state_pspecs(cfg, mesh)
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            state, sspec,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )

    step_fn = jit_train_step(cfg, ocfg, mesh, n_micro=args.micro)
    writer = ckpt_lib.AsyncCheckpointer(args.ckpt) if args.ckpt else None
    bspec = NamedSharding(mesh, batch_pspec(mesh))
    with jax.set_mesh(mesh):
        for step in range(start, args.steps):
            batch = jax.tree.map(lambda x: jax.device_put(x, bspec), batch_at(dcfg, step))
            state, metrics = step_fn(state, batch)
            if (step + 1) % 10 == 0 or step + 1 == args.steps:
                print(f"step {step+1:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}")
            if writer and (step + 1) % args.ckpt_every == 0:
                writer.submit(step + 1, state)
    if writer:
        writer.submit(args.steps, state)
        writer.finalize()
    print("done")


if __name__ == "__main__":
    main()
