"""Train / serve step factories.

``make_train_step`` — production path: pjit with 2D-sharded params
(TP over 'model', FSDP over 'data'), gradient accumulation over
microbatches via lax.scan (+ per-layer remat inside the model), f32
AdamW, donated state.

``make_dp_train_step`` — pure data-parallel shard_map path with optional
**CountSketch gradient compression** (the paper's operator on the DP
all-reduce; see repro.optim.compression).  Used where compression applies:
replicated params, batch sharded over ('pod','data').

``make_prefill_step`` / ``make_decode_step`` — serving entry points.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..models import transformer as tfm
from ..models.common import maybe_scan
from ..optim import (
    AdamWConfig,
    CompressionConfig,
    adamw_init,
    adamw_update,
    compress_state_init,
    sketched_psum_grads,
)
from ..sharding import DEFAULT_RULES, OPT_RULES, logical_to_spec, tree_pspecs

__all__ = [
    "TrainState",
    "init_train_state",
    "state_pspecs",
    "state_shapes",
    "batch_pspec",
    "make_train_step",
    "make_dp_train_step",
    "make_prefill_step",
    "make_decode_step",
]


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt: Any


def init_train_state(cfg: ModelConfig, key) -> TrainState:
    params = tfm.init_params(cfg, key)
    from ..models.common import DTYPES

    opt = adamw_init(params, moments_dtype=DTYPES[cfg.opt_moments_dtype])
    return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt=opt)


def state_shapes(cfg: ModelConfig) -> TrainState:
    return jax.eval_shape(lambda: init_train_state(cfg, jax.random.key(0)))


def state_pspecs(cfg: ModelConfig, mesh: Mesh, rules=None) -> TrainState:
    axes = tfm.params_axes(cfg)
    shapes = tfm.params_shapes(cfg)
    pspecs = tree_pspecs(axes, mesh, rules, shapes_tree=shapes)
    ospecs = tree_pspecs(axes, mesh, rules or OPT_RULES, shapes_tree=shapes)
    if rules is None:
        ospecs = tree_pspecs(axes, mesh, OPT_RULES, shapes_tree=shapes)
    return TrainState(
        step=P(),
        params=pspecs,
        opt={"master": ospecs, "m": ospecs, "v": ospecs},
    )


def batch_pspec(mesh: Mesh, rules=None) -> P:
    return logical_to_spec(("batch", "seq"), mesh, rules)


def _constrain_like_opt(grads, cfg):
    """Shard gradient buffers like the optimizer state (ZeRO-2 over pod)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "pod" not in mesh.axis_names:
        return grads
    axes = tfm.params_axes(cfg)
    specs = tree_pspecs(axes, mesh, OPT_RULES, shapes_tree=grads)
    return jax.tree.map(
        lambda g, s: jax.lax.with_sharding_constraint(
            g, NamedSharding(mesh, s)
        ),
        grads,
        specs,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )


def _microbatch(batch, n_micro: int):
    """(B, ...) -> (n_micro, B/n_micro, ...) for every leaf."""
    return jax.tree.map(
        lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]), batch
    )


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: AdamWConfig,
    *,
    n_micro: int = 1,
):
    """Returns train_step(state, batch) -> (state, metrics).  Jit/pjit-ready."""

    def train_step(state: TrainState, batch):
        params = state.params

        def loss_of(p, mb):
            return tfm.loss_fn(cfg, p, mb)

        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch
            )
            grads = _constrain_like_opt(grads, cfg)
        else:
            mbs = _microbatch(batch, n_micro)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, _), g = jax.value_and_grad(loss_of, has_aux=True)(params, mb)
                g = _constrain_like_opt(g, cfg)
                g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
                return (g_acc, l_acc + l), None

            from ..models.common import DTYPES

            acc_dtype = DTYPES[cfg.grad_accum_dtype]
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype), params)
            (grads, loss_sum), _ = maybe_scan(acc_body, (g0, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro
            metrics = {}

        new_opt, opt_metrics = adamw_update(opt_cfg, grads, state.opt, state.step)
        new_params = jax.tree.map(
            lambda m, p: m.astype(p.dtype), new_opt["master"], params
        )
        metrics = {"loss": loss, **opt_metrics}
        return TrainState(step=state.step + 1, params=new_params, opt=new_opt), metrics

    return train_step


def jit_train_step(cfg, opt_cfg, mesh, *, n_micro=1, rules=None):
    """pjit-wrapped train step with explicit state/batch shardings."""
    step_fn = make_train_step(cfg, opt_cfg, n_micro=n_micro)
    sspec = state_pspecs(cfg, mesh, rules)
    bspec = {"tokens": batch_pspec(mesh, rules), "labels": batch_pspec(mesh, rules)}
    mspec = None  # metrics replicated
    return jax.jit(
        step_fn,
        in_shardings=(
            jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                         is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(lambda s: NamedSharding(mesh, s), bspec,
                         is_leaf=lambda x: isinstance(x, P)),
        ),
        donate_argnums=(0,),
    )


# ===========================================================================
# Pure-DP path with sketched gradient compression
# ===========================================================================


def make_dp_train_step(
    cfg: ModelConfig,
    opt_cfg: AdamWConfig,
    mesh: Mesh,
    *,
    axes=("data",),
    compression: CompressionConfig | None = None,
):
    """shard_map DP train step: params replicated, batch row-sharded.

    Gradients are combined with a plain psum or, when ``compression`` is
    given, with CountSketch-compressed psum + error feedback.
    """
    if isinstance(axes, str):
        axes = (axes,)

    def local_step(state_and_ef, batch):
        state, ef = state_and_ef

        def loss_of(p):
            return tfm.loss_fn(cfg, p, batch)

        (loss, _), grads = jax.value_and_grad(loss_of, has_aux=True)(state.params)
        loss = lax.pmean(loss, axes)
        if compression is None:
            grads = jax.tree.map(lambda g: lax.pmean(g, axes), grads)
            new_ef = ef
        else:
            grads, new_ef = sketched_psum_grads(
                compression, grads, ef, axes, step=state.step
            )
        new_opt, om = adamw_update(opt_cfg, grads, state.opt, state.step)
        new_params = jax.tree.map(
            lambda m, p: m.astype(p.dtype), new_opt["master"], state.params
        )
        new_state = TrainState(step=state.step + 1, params=new_params, opt=new_opt)
        return (new_state, new_ef), {"loss": loss, **om}

    rep = P()
    row = P(axes)

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def step(state, ef, batch):
        fn = jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=((specs_like(state, rep), specs_like(ef, rep)),
                      specs_like(batch, row)),
            out_specs=((specs_like(state, rep), specs_like(ef, rep)),
                       {"loss": rep, "grad_norm": rep, "lr": rep}),
            check_vma=False,
        )
        return fn((state, ef), batch)

    return step


# ===========================================================================
# Serving steps
# ===========================================================================


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        return tfm.prefill(cfg, params, batch)

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, cache, tokens, step, embeds=None, img=None):
        return tfm.decode_step(
            cfg, params, cache, tokens, step, embeds=embeds, img=img
        )

    return decode_step
