"""Logical-axis → mesh-axis sharding rules.

Every parameter carries logical axis names (see repro.models.layers
ParamSpec). A *rule set* maps logical names to mesh axes; unmapped axes are
replicated. A mapping is dropped (axis replicated) when the dimension size
is not divisible by the mesh-axis size (e.g. 2 KV heads on a 16-way model
axis).

Rule sets are a hillclimb knob (RunConfig.sharding_rules).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes)
RULE_SETS: Dict[str, Dict[str, object]] = {
    # Megatron-style tensor parallelism + fsdp-style weight sharding over
    # the data axis on the embed dimension (needed to fit 236B params).
    "megatron_fsdp": {
        "vocab": "model",
        "heads_flat": "model",
        "kv_heads_flat": "model",
        "ff": "model",
        "experts": "model",
        "ssm_inner": "model",
        "ssm_heads": "model",
        "embed": "data",  # fsdp: gather on use
        "layers": None,
    },
    # pure tensor parallelism (params replicated over data)
    "megatron": {
        "vocab": "model",
        "heads_flat": "model",
        "kv_heads_flat": "model",
        "ff": "model",
        "experts": "model",
        "ssm_inner": "model",
        "ssm_heads": "model",
        "embed": None,
        "layers": None,
    },
    # serving: 2D expert sharding (experts→model, ff→data) — weights stay
    # fully sharded but are never gathered; MoE down-projections reduce
    # with a small activation psum over data (§Perf hillclimb #2).
    "serving_2d": {
        "vocab": "model",
        "heads_flat": "model",
        "kv_heads_flat": "model",
        "ff": "data",
        "experts": "model",
        "ssm_inner": "model",
        "ssm_heads": "model",
        "embed": None,
        "layers": None,
    },
    # fsdp over the layer stack axis instead of the embed axis
    "fsdp_layers": {
        "vocab": "model",
        "heads_flat": "model",
        "kv_heads_flat": "model",
        "ff": "model",
        "experts": "model",
        "ssm_inner": "model",
        "ssm_heads": "model",
        "embed": None,
        "layers": "data",
    },
}


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


def spec_for_axes(
    mesh: Mesh, axes: Tuple[Optional[str], ...], shape: Tuple[int, ...],
    rules: Dict[str, object],
) -> P:
    parts = []
    used = set()
    for dim, name in zip(shape, axes):
        mesh_axis = rules.get(name) if name else None
        if mesh_axis is None or mesh_axis in used:
            parts.append(None)
            continue
        if dim % _axis_size(mesh, mesh_axis) != 0:
            parts.append(None)  # indivisible: replicate
            continue
        parts.append(mesh_axis)
        used.add(mesh_axis)
    return P(*parts)


def data_axes(mesh: Mesh) -> tuple:
    """All mesh axes used for batch/data parallelism ((pod, data) if present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def param_shardings(mesh: Mesh, specs_tree, rules_name: str):
    """ParamSpec pytree -> NamedSharding pytree."""
    from repro.models.layers import ParamSpec, tree_map_specs

    base_rules = dict(RULE_SETS[rules_name])
    # multi-pod: fsdp over ("pod","data") jointly when embed->data
    if "pod" in getattr(mesh, "axis_names", ()):
        for k, v in list(base_rules.items()):
            if v == "data":
                base_rules[k] = ("pod", "data")

    def one(s: ParamSpec):
        return NamedSharding(mesh, spec_for_axes(mesh, s.axes, s.shape, base_rules))

    return tree_map_specs(one, specs_tree)


def batch_spec(mesh: Mesh, global_batch: int) -> P:
    """Sharding for the leading batch dim of activations/inputs."""
    da = data_axes(mesh)
    size = math.prod(mesh.shape[a] for a in da)
    if da and global_batch % size == 0:
        return P(da)
    # try pod-only / data-only before giving up
    for sub in (("data",), ("pod",)):
        if all(a in mesh.axis_names for a in sub):
            s = math.prod(mesh.shape[a] for a in sub)
            if global_batch % s == 0:
                return P(sub)
    return P(None)


def activation_sharding(mesh: Mesh, global_batch: int, extra_dims: int):
    """(B, ..., d) activations: batch over data axes, trailing dims replicated."""
    return NamedSharding(mesh, P(*batch_spec(mesh, global_batch), *([None] * extra_dims)))


def cache_shardings(mesh: Mesh, cfg, cache_tree, global_batch: int):
    """KV-cache sharding: batch dim over data axes; the cache sequence dim
    over "model" (flash-decode style: each model shard owns a slice of the
    context and the softmax reduction runs as a collective)."""
    from repro.models.transformer import SLOT_AXIS

    da = data_axes(mesh)
    bsize = math.prod(mesh.shape[a] for a in da)
    bspec = da if (da and global_batch % bsize == 0) else None

    def one(path, leaf):
        nd = len(leaf.shape)
        if nd == 0:  # length scalar
            return NamedSharding(mesh, P())
        # (L, B, ...): the ring-slot axis of K/V (L, B, kv, W, hd) and of
        # the MLA latent (L, B, W, r); axis 2 of the state leaves
        axis = SLOT_AXIS.get(getattr(path[-1], "key", None), 2)
        parts = [None] * nd
        if nd >= 2:
            parts[1] = bspec
        if nd > axis and leaf.shape[axis] % mesh.shape["model"] == 0:
            parts[axis] = "model"
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(one, cache_tree)
