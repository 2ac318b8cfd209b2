"""Logical-axis sharding rules (MaxText-style).

Params and activations are annotated with *logical* axis names; a rules
table maps them to physical mesh axes.  Axes absent from the current mesh
(e.g. 'pod' on the single-pod mesh) are dropped automatically, so the same
model code lowers on any mesh.

Default layout: 2D-sharded weights — tensor-parallel over 'model'
(heads / mlp / vocab / experts dims) and FSDP over 'data' (the weights'
d_model dim); activations batch-sharded over ('pod','data') and
head-sharded over 'model' inside mixer blocks.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES",
    "OPT_RULES",
    "logical_to_spec",
    "constrain",
    "make_mesh",
    "named_sharding",
    "tree_pspecs",
]


def make_mesh(shape, axis_names, *, devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto`` (GSPMD-propagated).

    ``jax.make_mesh`` makes ``Explicit`` axes by default; the sharding
    rules here, the model code and the shard_map solvers place arrays with
    ``NamedSharding``/``with_sharding_constraint`` and let XLA propagate,
    which is the ``Auto`` contract.
    """
    return jax.make_mesh(
        tuple(shape), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices,
    )


# logical axis -> physical mesh axis (or tuple of axes), None = replicated
DEFAULT_RULES: dict[str, object] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "act_embed": None,
    "act_heads": "model",
    "act_ff": "model",
    "act_experts": "model",
    "cap": ("pod", "data"),
    "cache_seq": "model",  # decode KV caches: sequence-sharded over TP
    # weights
    "embed": "data",  # FSDP dim of every weight
    "vocab": "model",
    "heads": "model",
    "kv_heads": None,  # GQA kv count < model axis -> replicate
    "mlp": "model",
    "experts": "model",
    "expert_mlp": None,
    "rnn": "model",
    "inner": "model",  # ssm d_inner
    "layers": None,
    "head_dim": None,
    "state": None,
    "conv": None,
    "lora": None,
    "patches": None,
    None: None,
}


# Optimizer-state rules: ZeRO-1 — master/m/v additionally sharded over the
# pod axis via the weights' embed dim (on single-pod meshes 'pod' is absent
# and this degenerates to DEFAULT_RULES).
OPT_RULES = dict(DEFAULT_RULES)
OPT_RULES["embed"] = ("pod", "data")


def logical_to_spec(axes: tuple, mesh: Mesh, rules=None, shape=None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec on ``mesh``.

    If ``shape`` is given, any mapping whose mesh-axis product does not
    divide the dimension is dropped (replicated) — e.g. batch=1 long-context
    decode, or vocab sizes not divisible by the model axis.
    """
    rules = rules or DEFAULT_RULES
    mesh_axes = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    out = []
    for i, ax in enumerate(axes):
        phys = rules.get(ax, None)
        if phys is None:
            out.append(None)
            continue
        if not isinstance(phys, tuple):
            phys = (phys,)
        present = tuple(a for a in phys if a in mesh_axes)
        if shape is not None and present:
            prod = 1
            for a in present:
                prod *= sizes[a]
            if prod == 0 or shape[i] % prod:
                present = ()
        if not present:
            out.append(None)
        elif len(present) == 1:
            out.append(present[0])
        else:
            out.append(present)

    # Expert-weight fallback: when the expert count does not divide the
    # model axis (e.g. mixtral's 8 experts on 16-way TP), shard the expert
    # FFN dim over 'model' instead — otherwise MoE weights (and their
    # optimizer state) end up replicated across the whole TP axis.
    if shape is not None and "experts" in axes and "model" in mesh_axes:
        e_dim = axes.index("experts")
        if out[e_dim] != "model" and "expert_mlp" in axes:
            f_dim = axes.index("expert_mlp")
            if out[f_dim] is None and shape[f_dim] % sizes["model"] == 0:
                out[f_dim] = "model"
    return P(*out)


def named_sharding(axes: tuple, mesh: Mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(axes, mesh, rules))


def constrain(x: jax.Array, axes: tuple, mesh: Mesh | None = None, rules=None):
    """with_sharding_constraint by logical axes (no-op without a mesh)."""
    if mesh is None:
        mesh = _current_mesh()
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_to_spec(axes, mesh, rules, shape=x.shape))
    )


def _current_mesh():
    """The mesh set by ``jax.set_mesh`` (abstract inside ``jit``), or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def tree_pspecs(axes_tree, mesh: Mesh, rules=None, shapes_tree=None):
    """Map a pytree of logical-axis tuples to PartitionSpecs.

    ``shapes_tree``: optional matching tree of ShapeDtypeStructs for
    divisibility-aware mapping.
    """
    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x
    )
    if shapes_tree is None:
        return jax.tree.map(
            lambda axes: logical_to_spec(axes, mesh, rules), axes_tree,
            is_leaf=is_axes,
        )
    return jax.tree.map(
        lambda axes, sh: logical_to_spec(axes, mesh, rules, shape=sh.shape),
        axes_tree, shapes_tree, is_leaf=is_axes,
    )
