"""Logical-axis sharding system (t5x/MaxText-style, dependency-free).

Every parameter/activation carries a tuple of *logical* axis names; a rules
dict maps logical names to mesh axes.  This keeps model code mesh-agnostic —
the same model lowers on 1 CPU device, a 16×16 pod, or a 2×16×16 multi-pod
mesh just by swapping rules.
"""
from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Rules = Mapping[str, Union[str, Tuple[str, ...], None]]


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """`jax.make_mesh` with Auto axes: shardings propagate through jit
    and logical-axis rules, as every mesh in this repo assumes (jax's own
    default is Explicit axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))

# ---------------------------------------------------------------------------
# Rule sets
# ---------------------------------------------------------------------------

# Tensor-parallel baseline: weights TP over "model", replicated over data/pod.
BASE_RULES: Dict[str, Any] = {
    # parameter axes
    "layer": None,          # stacked-layer leading axis (scanned)
    "vocab": "model",
    "embed": None,          # d_model
    "heads": "model",       # flattened G*d_head (head-group-major)
    "head_dim": "model",    # per-kv-head d_head columns
    "kv": "model",          # flattened n_kv*d_head
    "mlp": "model",         # dense FFN hidden
    "expert": "model",      # MoE expert axis (EP)
    "moe_mlp": None,        # per-expert FFN hidden (expert axis already TP)
    "norm": None,
    "ssm": None,            # small SSM/decay params
    # activation axes
    "batch": ("data",),
    "act_seq": "model",     # sequence-parallel activations
    "act_heads": "model",
    "act_embed": None,
    "act_mlp": "model",
    "kv_pages": "model",    # paged KV cache page axis (the paper's G2 shards)
}

# FSDP addition: shard the d_model axis of params over "data" as well
# (ZeRO-3 style; XLA inserts the all-gathers).  Used for ≥30B configs.
FSDP_RULES: Dict[str, Any] = dict(BASE_RULES, embed="data", moe_mlp="data")


def make_rules(*, fsdp: bool = False, multi_pod: bool = False,
               overrides: Optional[Rules] = None) -> Dict[str, Any]:
    rules = dict(FSDP_RULES if fsdp else BASE_RULES)
    if multi_pod:
        # batch data-parallel over both pod and data axes
        rules["batch"] = ("pod", "data")
    if overrides:
        rules.update(overrides)
    return rules


# ---------------------------------------------------------------------------
# Spec resolution
# ---------------------------------------------------------------------------

def logical_to_spec(axes: Sequence[Optional[str]], rules: Rules) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    mesh_axes = []
    used: set = set()
    for ax in axes:
        if ax is None:
            mesh_axes.append(None)
            continue
        m = rules.get(ax, None)
        if m is None:
            mesh_axes.append(None)
            continue
        flat = (m,) if isinstance(m, str) else tuple(m)
        # a mesh axis may appear only once in a PartitionSpec
        avail = tuple(a for a in flat if a not in used)
        used.update(avail)
        if not avail:
            mesh_axes.append(None)
        elif len(avail) == 1:
            mesh_axes.append(avail[0])
        else:
            mesh_axes.append(avail)
    return P(*mesh_axes)


def _divisible(dim: int, n_shards: int) -> bool:
    return n_shards > 0 and dim % n_shards == 0


def spec_for_shape(shape: Sequence[int], axes: Sequence[Optional[str]],
                   rules: Rules, mesh: Mesh) -> P:
    """logical_to_spec + divisibility fallback: drop sharding on any dim the
    mesh does not divide (keeps odd vocab/head counts compiling)."""
    spec = logical_to_spec(axes, rules)
    fixed = []
    for dim, m in zip(shape, spec):
        if m is None:
            fixed.append(None)
            continue
        names = (m,) if isinstance(m, str) else tuple(m)
        n = 1
        for name in names:
            n *= mesh.shape[name]
        fixed.append(m if _divisible(dim, n) else None)
    return P(*fixed)


def tree_shardings(abstract_tree: Any, spec_tree: Any, rules: Rules,
                   mesh: Mesh) -> Any:
    """Build a NamedSharding pytree for (abstract params, logical-axes) trees.

    QuantizedWeight leaves expand into matching QuantizedWeight sharding
    nodes (q + per-channel scale)."""
    def one(leaf, axes):
        if type(leaf).__name__ == "QuantizedWeight":
            from repro.core.quant import QuantizedWeight
            q_sh = one(leaf.q, tuple(axes.q))
            s_sh = one(leaf.scale, tuple(axes.scale))
            return QuantizedWeight(q_sh, s_sh, leaf.scheme, leaf.orig_shape)
        if axes is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, spec_for_shape(leaf.shape, axes, rules, mesh))

    is_leaf = lambda x: x is None or type(x).__name__ == "QuantizedWeight"  # noqa
    return jax.tree.map(one, abstract_tree, spec_tree, is_leaf=is_leaf)


def constrain(x, axes: Sequence[Optional[str]], rules: Rules,
              mesh: Optional[Mesh] = None):
    """with_sharding_constraint by logical axes (no-op off-mesh)."""
    mesh = mesh or get_current_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return x
    spec = spec_for_shape(x.shape, axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def get_current_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The ambient mesh installed by `jax.set_mesh` (None without one).

    The abstract mesh, which is what jit traces see: axis names, sizes
    and types (Manual inside a `shard_map`), enough for specs and
    nested `shard_map`s."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m
