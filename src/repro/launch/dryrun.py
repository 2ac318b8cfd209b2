# The host-platform device count must be pinned before ANY jax import —
# jax locks the device topology on first initialization.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we build three artifacts:

  full  — the production config (all periods, full grad-accumulation):
          ``.lower().compile()`` success proves the sharding is coherent;
          ``memory_analysis()`` proves it fits per-device HBM.
  c1/c2 — 1-period and 2-period reductions (single microbatch): XLA counts
          while-loop bodies once, so per-period costs are obtained by
          differencing (c2 − c1) and scaled analytically:

            total = outer · (base + n_periods · per_period),
            base  = c1 − per_period,   outer = n_micro (train) else 1.

          The same differencing applies to the HLO-parsed collective bytes.

Results land in JSON (one file per cell) consumed by the roofline report.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ModelConfig, ShapeConfig, get_config, get_shape, registry
from ..models import transformer as tfm
from ..models.common import unrolled_scans
from ..optim import AdamWConfig
from ..sharding import OPT_RULES, logical_to_spec, tree_pspecs
from ..train.step import make_train_step, state_pspecs, state_shapes
from .hlo_stats import collective_stats
from .mesh import HW, make_production_mesh

# ---------------------------------------------------------------------------


def dp_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)


def pick_micro(shape: ShapeConfig, mesh) -> int:
    if shape.kind != "train" or not shape.microbatch:
        return 1
    return max(1, min(shape.microbatch, shape.global_batch // dp_size(mesh)))


def _sds(shape, dtype, spec, mesh):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec)
    )


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, batch_size=None):
    """ShapeDtypeStruct stand-ins for the step inputs (no allocation)."""
    B = batch_size or shape.global_batch
    S = shape.seq_len
    bspec = logical_to_spec(("batch", "seq"), mesh, shape=(B, S))
    especs = logical_to_spec(("batch", "seq", "act_embed"), mesh, shape=(B, S, cfg.d_model))
    batch = {}
    if cfg.frontend == "frames":
        batch["embeds"] = _sds((B, S, cfg.d_model), jnp.bfloat16, especs, mesh)
    else:
        batch["tokens"] = _sds((B, S), jnp.int32, bspec, mesh)
    if cfg.frontend == "vision":
        ispec = logical_to_spec(
            ("batch", "patches", "act_embed"), mesh, shape=(B, cfg.n_patches, cfg.d_model)
        )
        batch["image_embeds"] = _sds(
            (B, cfg.n_patches, cfg.d_model), jnp.bfloat16, ispec, mesh
        )
    if shape.kind == "train":
        batch["labels"] = _sds((B, S), jnp.int32, bspec, mesh)
    return batch


def _sharded_shapes(tree_shapes, tree_axes, mesh):
    pspecs = tree_pspecs(tree_axes, mesh, shapes_tree=tree_shapes)
    return jax.tree.map(
        lambda sds, spec: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)
        ),
        tree_shapes,
        pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def build_lowerable(cfg: ModelConfig, shape: ShapeConfig, mesh, n_micro: int):
    """Returns (jitted_fn, example_args_SDS) for this cell."""
    if shape.kind == "train":
        step_fn = make_train_step(cfg, AdamWConfig(), n_micro=n_micro)
        sshapes = state_shapes(cfg)
        saxes = jax.tree.map(lambda _: None, sshapes)  # placeholder
        # params/opt sharded by logical axes; step replicated
        axes = tfm.params_axes(cfg)
        pshapes = tfm.params_shapes(cfg)
        pspecs = tree_pspecs(axes, mesh, shapes_tree=pshapes)
        shard = lambda tree: jax.tree.map(
            lambda sds, spec: jax.ShapeDtypeStruct(
                sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)
            ),
            tree,
            pspecs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        params_s = shard(pshapes)
        ospecs = tree_pspecs(axes, mesh, OPT_RULES, shapes_tree=pshapes)
        from ..models.common import DTYPES

        def opt_sds(dtype):
            return jax.tree.map(
                lambda sds, spec: jax.ShapeDtypeStruct(
                    sds.shape, dtype, sharding=NamedSharding(mesh, spec)
                ),
                pshapes, ospecs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )

        mdt = DTYPES[cfg.opt_moments_dtype]
        state = {
            "step": jax.ShapeDtypeStruct((), jnp.int32),
            "params": params_s,
            "opt": {"master": opt_sds(jnp.float32), "m": opt_sds(mdt), "v": opt_sds(mdt)},
        }
        from ..train.step import TrainState

        state = TrainState(step=state["step"], params=state["params"], opt=state["opt"])
        batch = input_specs(cfg, shape, mesh)
        fn = jax.jit(step_fn, donate_argnums=(0,))
        return fn, (state, batch)

    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            return tfm.prefill(cfg, params, batch)

        pshapes = tfm.params_shapes(cfg)
        params_s = _sharded_shapes(pshapes, tfm.params_axes(cfg), mesh)
        batch = input_specs(cfg, shape, mesh)
        return jax.jit(prefill_fn), (params_s, batch)

    # decode
    def decode_fn(params, cache, tokens, step, embeds, img):
        return tfm.decode_step(
            cfg, params, cache, tokens, step, embeds=embeds, img=img
        )

    B, S = shape.global_batch, shape.seq_len
    pshapes = tfm.params_shapes(cfg)
    params_s = _sharded_shapes(pshapes, tfm.params_axes(cfg), mesh)
    cshapes = tfm.cache_shapes(cfg, B, S)
    caxes = tfm.cache_axes(cfg)
    cache_s = _sharded_shapes(cshapes, caxes, mesh)
    bspec = logical_to_spec(("batch",), mesh, shape=(B,))
    tokens = _sds((B,), jnp.int32, bspec, mesh)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    embeds = (
        _sds((B, cfg.d_model), jnp.bfloat16,
             logical_to_spec(("batch", "act_embed"), mesh, shape=(B, cfg.d_model)), mesh)
        if cfg.frontend == "frames" else None
    )
    img = (
        _sds((B, cfg.n_patches, cfg.d_model), jnp.bfloat16,
             logical_to_spec(("batch", "patches", "act_embed"), mesh,
                             shape=(B, cfg.n_patches, cfg.d_model)), mesh)
        if cfg.frontend == "vision" else None
    )
    return jax.jit(decode_fn, donate_argnums=(1,)), (
        params_s, cache_s, tokens, step, embeds, img,
    )


def _compile_cell(cfg, shape, mesh, n_micro):
    fn, args = build_lowerable(cfg, shape, mesh, n_micro)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    coll = collective_stats(text)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": coll["total_bytes"],
        "coll_by_kind": coll["by_kind"],
        "t_lower_s": t_lower,
        "t_compile_s": t_compile,
        "memory": None
        if ma is None
        else {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        },
    }


def n_params(cfg: ModelConfig) -> tuple[float, float]:
    """(total, active) parameter counts from the spec tree."""
    shapes = jax.tree.leaves(tfm.params_shapes(cfg))
    total = sum(float(np.prod(s.shape)) for s in shapes)
    active = total
    if cfg.moe is not None:
        m = cfg.moe
        # routed-expert params activated: top_k of n_experts
        expert = 0.0
        for path, s in jax.tree_util.tree_flatten_with_path(tfm.params_shapes(cfg))[0]:
            kp = jax.tree_util.keystr(path)
            if "w_in" in kp or "w_out" in kp or "w_gate" in kp:
                if "'ffn'" in kp and f"{m.n_experts}" in str(s.shape):
                    expert += float(np.prod(s.shape))
        active = total - expert * (1 - m.top_k / m.n_experts)
    return total, active


def _window_max(cfg: ModelConfig) -> int:
    w = 0
    for spec in cfg.prefix + cfg.pattern + cfg.suffix:
        if spec.mixer == "attn" and spec.window:
            w = max(w, spec.window)
    return w


def _cost_compile(cfg, shape, mesh):
    with unrolled_scans():
        return _compile_cell(cfg, shape, mesh, 1)


def _derive_costs(cfg, shape, mesh, n_micro, rec):
    keys = ("flops", "bytes", "coll_bytes")

    if shape.kind == "decode":
        c1 = _cost_compile(cfg.replace(n_periods=1), shape, mesh)
        c2 = _cost_compile(cfg.replace(n_periods=2), shape, mesh)
        rec["cost_artifacts"] = {"c1": c1, "c2": c2}
        out = {}
        for k in keys:
            per = max(c2[k] - c1[k], 0.0)
            base = max(c1[k] - per, 0.0)
            out[k] = base + cfg.n_periods * per
            out[f"{k}_per_period"] = per
            out[f"{k}_base"] = base
        return out

    # train / prefill: two sequence lengths, minimal batch, linear B scaling
    S = shape.seq_len
    w = _window_max(cfg)
    S_a = min(max(2048, 2 * w), S)
    S_b = min(2 * S_a, S)
    if S_b == S_a:
        S_a = max(S_b // 2, 512)

    if shape.kind == "train":
        B_full = shape.global_batch // n_micro  # per-microbatch tokens
        outer = n_micro
    else:
        B_full = shape.global_batch
        outer = 1
    B_cost = max(dp_size(mesh), 1)
    while B_full % B_cost:
        B_cost += 1
    b_scale = B_full / B_cost

    pts = {}
    arts = {}
    for S_c in sorted({S_a, S_b}):
        cost_shape = dataclasses.replace(
            shape, seq_len=S_c, global_batch=B_cost, microbatch=1
        )
        p1 = _cost_compile(cfg.replace(n_periods=1), cost_shape, mesh)
        p2 = _cost_compile(cfg.replace(n_periods=2), cost_shape, mesh)
        arts[f"S{S_c}"] = {"c1": p1, "c2": p2}
        pts[S_c] = (p1, p2)
    rec["cost_artifacts"] = arts
    rec["cost_fit"] = {"S_a": S_a, "S_b": S_b, "B_cost": B_cost, "b_scale": b_scale}

    out = {}
    for k in keys:
        def fit(vals):  # vals: {S: v}; v(S) = alpha*S + beta*S^2
            (s1, v1), (s2, v2) = sorted(vals.items())
            det = s1 * s2 * s2 - s2 * s1 * s1
            beta = (v2 * s1 - v1 * s2) / det
            alpha = (v1 - beta * s1 * s1) / s1
            return alpha * S + beta * S * S

        per_v = {s_c: max(p2[k] - p1[k], 0.0) for s_c, (p1, p2) in pts.items()}
        base_v = {
            s_c: max(p1[k] - max(p2[k] - p1[k], 0.0), 0.0)
            for s_c, (p1, p2) in pts.items()
        }
        per_full = max(fit(per_v), 0.0)
        base_full = max(fit(base_v), 0.0)
        out[k] = outer * b_scale * (base_full + cfg.n_periods * per_full)
        out[f"{k}_per_period"] = b_scale * per_full
        out[f"{k}_base"] = b_scale * base_full
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force=False, overrides=None, micro=None):
    mesh_name = "multi" if multi_pod else "single"
    out_path = os.path.join(out_dir, mesh_name, f"{arch}__{shape_name}.json")
    if os.path.exists(out_path) and not force:
        print(f"[skip] {out_path} exists")
        return json.load(open(out_path))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    cfg = get_config(arch)
    if overrides:
        typed = {}
        for k, v in overrides.items():
            cur = getattr(cfg, k)
            typed[k] = type(cur)(v) if cur is not None and not isinstance(cur, str) else v
        cfg = cfg.replace(**typed)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_micro = micro if micro else pick_micro(shape, mesh)
    n_chips = int(np.prod(mesh.devices.shape))
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": n_chips,
        "n_micro": n_micro,
        "n_layers": cfg.n_layers,
        "overrides": overrides or {},
        "status": "error",
    }
    try:
        # ---- full artifact: compile proof + memory ----
        full = _compile_cell(cfg, shape, mesh, n_micro)
        rec["full"] = full
        print(f"[{arch}/{shape_name}/{mesh_name}] full compile OK "
              f"({full['t_compile_s']:.1f}s) mem={full['memory']}")

        # ---- cost artifacts (single-pod only; roofline table is single-pod).
        # XLA's cost analysis counts while bodies once, so cost artifacts run
        # with every scan UNROLLED.  Per-period costs come from 1-vs-2-period
        # differencing; train/prefill costs are measured at two sequence
        # lengths and reconstructed as per_period(S) = α·S + β·S² (exact for
        # the op mix we emit: attention quadratic + everything-else linear;
        # S_a is chosen above 2·window so windowed attention sits in its
        # linear regime).  Batch scales exactly linearly (no cross-batch
        # ops), so cost artifacts run at the minimal shardable batch.
        if multi_pod:
            rec["roofline"] = None
            rec["status"] = "ok"
            with open(out_path, "w") as f:
                json.dump(rec, f, indent=1, default=str)
            return rec

        derived = _derive_costs(cfg, shape, mesh, n_micro, rec)
        rec["derived"] = derived

        # ---- roofline terms (per chip; cost_analysis is per-program =
        #      per-device for SPMD modules) ----
        total, active = n_params(cfg)
        tokens = shape.global_batch * shape.seq_len if shape.kind == "train" else (
            shape.global_batch * shape.seq_len if shape.kind == "prefill"
            else shape.global_batch
        )
        model_flops = (6.0 if shape.kind == "train" else 2.0) * active * tokens
        t_comp = derived["flops"] / HW["peak_flops_bf16"]
        t_mem = derived["bytes"] / HW["hbm_bw"]
        # 2D/3D torus: ~3 usable link pairs per chip on v5e -> treat the
        # per-chip ICI budget as 3 links x 50 GB/s aggregated.
        t_coll = derived["coll_bytes"] / (3 * HW["ici_bw"])
        rec["roofline"] = {
            "params_total": total,
            "params_active": active,
            "model_flops_global": model_flops,
            "model_flops_per_chip": model_flops / n_chips,
            "hlo_flops_per_chip": derived["flops"],
            "useful_flops_ratio": (model_flops / n_chips) / max(derived["flops"], 1.0),
            "t_compute_s": t_comp,
            "t_memory_s": t_mem,
            "t_collective_s": t_coll,
            "bottleneck": max(
                [("compute", t_comp), ("memory", t_mem), ("collective", t_coll)],
                key=lambda kv: kv[1],
            )[0],
        }
        rec["status"] = "ok"
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()
        print(f"[{arch}/{shape_name}/{mesh_name}] FAILED: {rec['error']}")
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="ModelConfig overrides, e.g. moe_impl=shard_map")
    ap.add_argument("--micro", type=int, default=None,
                    help="override gradient-accumulation microbatch count")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.override)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = registry.all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for mesh_name in meshes:
        for arch, shape in cells:
            rec = run_cell(arch, shape, mesh_name == "multi", args.out,
                           args.force, overrides, micro=args.micro)
            failures += rec["status"] != "ok"
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
