"""Mixture-of-Experts FFN: top-k routing with capacity-based dispatch.

Two implementations, selected by ``cfg.moe_impl``:

**GSPMD path** (`'gspmd'`; the paper-faithful/naive baseline): one global
sort-based dispatch — flatten (token, choice) assignments, rank within
expert via segment-cumsum over the sorted order, drop beyond capacity,
gather into a dense [E, C, D] buffer for grouped matmuls.  Compiles under
bare jit anywhere, but at 32k contexts GSPMD must replicate the token
array across devices to partition the global sort/gather (≈10 GB/device
at deepseek-v2 prefill) — measured in EXPERIMENTS.md §Perf as the
baseline.

**shard_map expert-parallel path** (`'shard_map'`, auto-selected under a
mesh with a 'model' axis): dispatch runs *locally* per data shard — no
global sort, no token replication.  Expert weights are sharded over
'model' on the expert axis (or on the FFN axis when E < model-axis size,
e.g. mixtral's 8 experts on 16-way TP), FSDP-gathered over 'data'
explicitly, and each device computes only its expert (or FFN) slice; a
single psum over 'model' combines contributions — Megatron-style EP with
explicit collectives.

Both support shared experts (DeepSeek-V2) and top-k renormalization
(Mixtral); router in f32; Switch-style load-balance aux loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, MoEConfig
from .common import PSpec, activation, constrain, rms_norm
from .mlp import GATED


def moe_specs(cfg: ModelConfig) -> dict:
    D = cfg.d_model
    m: MoEConfig = cfg.moe
    E, F = m.n_experts, m.d_expert
    specs = {
        "ln": PSpec((D,), ("embed",), "zeros"),
        "router": PSpec((D, E), ("embed", None), dtype=jnp.float32),
        "w_in": PSpec((E, D, F), ("experts", "embed", "expert_mlp")),
        "w_out": PSpec((E, F, D), ("experts", "expert_mlp", "embed")),
    }
    if cfg.act in GATED:
        specs["w_gate"] = PSpec((E, D, F), ("experts", "embed", "expert_mlp"))
    if m.n_shared:
        Fs = m.n_shared * m.d_expert
        specs["shared_in"] = PSpec((D, Fs), ("embed", "mlp"))
        specs["shared_out"] = PSpec((Fs, D), ("mlp", "embed"))
        if cfg.act in GATED:
            specs["shared_gate"] = PSpec((D, Fs), ("embed", "mlp"))
    return specs


def _capacity(T: int, m: MoEConfig) -> int:
    c = int(m.capacity_factor * T * m.top_k / m.n_experts)
    return max(8, -(-c // 8) * 8)  # multiple of 8 for tiling


def _route(p_router, h, m: MoEConfig):
    logits = h.astype(jnp.float32) @ p_router
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def _rank_in_expert(flat_e, E):
    """Stable rank of each assignment within its target expert."""
    A = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jax.ops.segment_sum(jnp.ones((A,), jnp.int32), sorted_e, E)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(A, dtype=jnp.int32) - starts[sorted_e]
    return jnp.zeros((A,), jnp.int32).at[order].set(rank_sorted)


def _expert_ffn(xe, p, cfg, f_slice=None):
    """xe (E?, C, D) -> (E?, C, D) through the (possibly F-sliced) experts."""
    w_in, w_out = p["w_in"], p["w_out"]
    up = jnp.einsum("ecd,edf->ecf", xe, w_in)
    if cfg.act in GATED:
        act = activation(cfg.act, up, jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    else:
        act = activation(cfg.act, up)
    return jnp.einsum("ecf,efd->ecd", act, w_out)


def _shared_ffn(h, p, cfg):
    s_up = h @ p["shared_in"]
    if cfg.act in GATED:
        s_act = activation(cfg.act, s_up, h @ p["shared_gate"])
    else:
        s_act = activation(cfg.act, s_up)
    return s_act @ p["shared_out"]


def _aux_loss(probs, flat_e, m: MoEConfig):
    A = flat_e.shape[0]
    frac = jax.ops.segment_sum(
        jnp.ones((A,), jnp.float32) / A, flat_e, num_segments=m.n_experts
    )
    return m.aux_weight * m.n_experts * jnp.sum(frac * probs.mean(0))


# ===========================================================================
# GSPMD (global-dispatch) path — the measured baseline
# ===========================================================================


def _moe_gspmd(p, x, cfg: ModelConfig, return_aux: bool):
    m: MoEConfig = cfg.moe
    orig_shape = x.shape
    squeeze = x.ndim == 3
    h_in = rms_norm(x, p["ln"], cfg.norm_eps)
    h = h_in.reshape(-1, orig_shape[-1])
    T, D = h.shape
    E, K = m.n_experts, m.top_k
    C = _capacity(T, m)

    probs, gate_vals, gate_idx = _route(p["router"], h, m)

    A = T * K
    flat_e = gate_idx.reshape(A)
    token_of = jnp.arange(A, dtype=jnp.int32) // K
    rank = _rank_in_expert(flat_e, E)
    keep = rank < C
    dest = jnp.where(keep, flat_e * C + rank, E * C)

    slot_src = jnp.full((E * C + 1,), T, jnp.int32).at[dest].set(token_of)
    h_pad = jnp.concatenate([h, jnp.zeros((1, D), h.dtype)], axis=0)
    xe = h_pad[slot_src[:-1]].reshape(E, C, D)
    xe = constrain(xe, ("act_experts", "cap", None))

    ye = _expert_ffn(xe, p, cfg)
    ye = constrain(ye, ("act_experts", "cap", None))

    ye_flat = jnp.concatenate(
        [ye.reshape(E * C, D), jnp.zeros((1, D), ye.dtype)], axis=0
    )
    y_assign = ye_flat[dest] * (
        gate_vals.reshape(A, 1).astype(ye.dtype) * keep[:, None]
    )
    y = jax.ops.segment_sum(y_assign, token_of, num_segments=T)

    if m.n_shared:
        y = y + _shared_ffn(h, p, cfg)

    y = y.reshape(orig_shape).astype(x.dtype)
    out = x + (constrain(y, ("batch", "seq", "act_embed")) if squeeze else y)
    if not return_aux:
        return out
    return out, _aux_loss(probs, flat_e, m)


# ===========================================================================
# shard_map expert-parallel path
# ===========================================================================


def _current_mesh():
    """The mesh set by ``jax.set_mesh`` (abstract inside ``jit``), or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _moe_shard_map(p, x, cfg: ModelConfig, mesh, return_aux: bool):
    m: MoEConfig = cfg.moe
    E, K, D = m.n_experts, m.top_k, cfg.d_model
    names = mesh.axis_names
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    tp = "model"
    tp_size = dict(zip(names, mesh.axis_sizes))[tp]
    expert_mode = E % tp_size == 0 and E >= tp_size
    E_loc = E // tp_size if expert_mode else E
    gated = cfg.act in GATED

    def body(x_loc, p_loc):
        # ---- FSDP-gather weights over the data axis (explicit) ----------
        def gather_embed(w, axis):
            return lax.all_gather(w, "data", axis=axis, tiled=True) if "data" in names else w

        ln = gather_embed(p_loc["ln"], 0)
        router = gather_embed(p_loc["router"], 0)
        w = {
            "w_in": gather_embed(p_loc["w_in"], 1),
            "w_out": gather_embed(p_loc["w_out"], 2),
        }
        if gated:
            w["w_gate"] = gather_embed(p_loc["w_gate"], 1)

        B_loc, S, _ = x_loc.shape
        h_in = rms_norm(x_loc, ln, cfg.norm_eps)
        h = h_in.reshape(-1, D)
        T_loc = h.shape[0]
        C = _capacity(T_loc, m)

        probs, gate_vals, gate_idx = _route(router, h, m)
        A = T_loc * K
        flat_e = gate_idx.reshape(A)
        token_of = jnp.arange(A, dtype=jnp.int32) // K
        rank = _rank_in_expert(flat_e, E)
        keep = rank < C

        if expert_mode:
            # keep only assignments targeting MY experts
            e0 = lax.axis_index(tp) * E_loc
            mine = (flat_e >= e0) & (flat_e < e0 + E_loc) & keep
            dest = jnp.where(mine, (flat_e - e0) * C + rank, E_loc * C)
        else:
            dest = jnp.where(keep, flat_e * C + rank, E_loc * C)

        slot_src = jnp.full((E_loc * C + 1,), T_loc, jnp.int32).at[dest].set(token_of)
        h_pad = jnp.concatenate([h, jnp.zeros((1, D), h.dtype)], axis=0)
        xe = h_pad[slot_src[:-1]].reshape(E_loc, C, D)

        ye = _expert_ffn(xe, w, cfg)

        ye_flat = jnp.concatenate(
            [ye.reshape(E_loc * C, D), jnp.zeros((1, D), ye.dtype)], axis=0
        )
        y_assign = ye_flat[dest] * (
            gate_vals.reshape(A, 1).astype(ye.dtype)
            * (mine if expert_mode else keep)[:, None]
        )
        y = jax.ops.segment_sum(y_assign, token_of, num_segments=T_loc)

        if m.n_shared:
            ws = {
                "shared_in": gather_embed(p_loc["shared_in"], 0),
                "shared_out": gather_embed(p_loc["shared_out"], 1),
            }
            if gated:
                ws["shared_gate"] = gather_embed(p_loc["shared_gate"], 0)
            # shared FFN dim is model-sharded -> contribution is partial too
            y = y + _shared_ffn(h, ws, cfg)

        # one combine psum over the model axis
        y = lax.psum(y, tp)
        out = x_loc + y.reshape(x_loc.shape).astype(x_loc.dtype)

        aux = _aux_loss(probs, flat_e, m)
        if dp_axes:
            aux = lax.pmean(aux, dp_axes)
        return out, aux

    # ---- specs ------------------------------------------------------------
    xspec = P(dp_axes if dp_axes else None, None, None)
    d_fsdp = "data" if "data" in names else None
    pspecs = {
        "ln": P(d_fsdp),
        "router": P(d_fsdp, None),
    }
    if expert_mode:
        pspecs["w_in"] = P(tp, d_fsdp, None)
        pspecs["w_out"] = P(tp, None, d_fsdp)
        if gated:
            pspecs["w_gate"] = P(tp, d_fsdp, None)
    else:
        pspecs["w_in"] = P(None, d_fsdp, tp)
        pspecs["w_out"] = P(None, tp, d_fsdp)
        if gated:
            pspecs["w_gate"] = P(None, d_fsdp, tp)
    if m.n_shared:
        pspecs["shared_in"] = P(d_fsdp, tp)
        pspecs["shared_out"] = P(tp, d_fsdp)
        if gated:
            pspecs["shared_gate"] = P(d_fsdp, tp)
    p_in = {k: p[k] for k in pspecs}

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(xspec, pspecs),
        out_specs=(xspec, P()),
        check_vma=False,
    )
    out, aux = fn(x, p_in)
    if return_aux:
        return out, aux
    return out


def _ffn_shardable(cfg, tp_size):
    m = cfg.moe
    ok_expert = m.n_experts % tp_size == 0 and m.n_experts >= tp_size
    ok_ffn = m.d_expert % tp_size == 0
    return ok_expert or ok_ffn


def moe_apply(p, x, cfg: ModelConfig, return_aux: bool = False):
    """x (B, S, D) or (T, D).  Returns y (+ aux loss if requested)."""
    impl = cfg.moe_impl
    if impl in ("auto", "shard_map") and x.ndim == 3:
        mesh = _current_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            tp_size = dict(zip(mesh.axis_names, mesh.axis_sizes))["model"]
            if _ffn_shardable(cfg, tp_size):
                return _moe_shard_map(p, x, cfg, mesh, return_aux)
        if impl == "shard_map":
            raise RuntimeError("moe_impl='shard_map' requires a mesh with a 'model' axis")
    return _moe_gspmd(p, x, cfg, return_aux)
