"""Sharding rules: logical axis names -> mesh PartitionSpecs.

Logical axes used by the model code:
    "batch"   -> ("pod", "data")   activations' batch dim
    "seq"     -> "model"           sequence parallelism (KV caches, long ctx)
    "heads"   -> "model"           attention-head tensor parallelism
    "ff"      -> "model"           FFN hidden tensor parallelism
    "expert"  -> "model"           expert parallelism
    "vocab"   -> "model"           embedding/logits sharding
    "data"    -> "data"            dispatch-buffer token sharding
    "fsdp"    -> ("pod", "data")   ZeRO/FSDP param dim (the sortdest grad sync)

Rules silently fall back to replication when a dim is not divisible by the
assigned mesh axes (e.g. hubert's vocab=504 on model=16, gemma3's 4 heads).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat

LOGICAL = {
    "batch": ("pod", "data"),
    "seq": ("model",),
    "heads": ("model",),
    "ff": ("model",),
    "expert": ("model",),
    "vocab": ("model",),
    "data": ("data",),
    "fsdp": ("pod", "data"),
    None: (),
}


def _mesh_axis_sizes(mesh) -> dict:
    # works for both Mesh and AbstractMesh (inside jit traces)
    return dict(mesh.shape)


def resolve(logical_axes, dims, mesh) -> P:
    """Map logical axis names to a PartitionSpec, dropping non-divisible or
    absent mesh axes (replication fallback).  A mesh axis is used at most
    once per spec (first dim wins)."""
    sizes = _mesh_axis_sizes(mesh)
    used: set = set()
    spec = []
    for ax, dim in zip(logical_axes, dims):
        names = [n for n in LOGICAL.get(ax, ()) if n in sizes and n not in used]
        total = int(np.prod([sizes[n] for n in names])) if names else 1
        if names and dim % total == 0 and total > 1:
            spec.append(tuple(names) if len(names) > 1 else names[0])
            used.update(names)
        else:
            spec.append(None)
    return P(*spec)


def constrain(x, *logical_axes):
    """with_sharding_constraint by logical names; no-op without a mesh and
    inside shard_map bodies (Manual axes -- sharding is already explicit)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return x
    if compat.mesh_has_manual_axes(mesh):
        return x
    spec = resolve(logical_axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter sharding rules (keyed by leaf path names)
# ---------------------------------------------------------------------------

# name -> logical axes per dim (excluding any leading scan/stack dim)
_PARAM_RULES = {
    "table": ("vocab", None),
    "wq": (None, "heads", None),
    "wk": (None, "heads", None),
    "wv": (None, "heads", None),
    "wo": ("heads", None, None),
    "bq": ("heads", None),
    "bk": ("heads", None),
    "bv": ("heads", None),
    "w_gate": (None, "ff"),
    "w_in": (None, "ff"),
    "w_out": ("ff", None),
    "router": (None, None),
    "scale": (None,),
    # mamba
    "in_proj": (None, "ff"),
    "out_proj": ("ff", None),
    "conv_w": ("ff", None),
    "conv_b": ("ff",),
    "a_log": ("ff", None),
    "d_skip": ("ff",),
    "w_bc": ("ff", None),
    "w_dt": ("ff",),
    "b_dt": ("ff",),
    # xlstm
    "w_qkv": (None, "ff"),
    "w_gates": (None, None),
    "r_gates": (None,),
}

# MoE expert tensors carry a leading expert dim; the expert axis takes the
# model mesh axis, so inner dims are left for fsdp (d or ff is picked by
# _fsdp_axes) -- mapping ff to model too would double-book the axis.
_MOE_RULES = {
    "w_gate": ("expert", None, None),
    "w_in": ("expert", None, None),
    "w_out": ("expert", None, None),
}


def _rule_for(path_names, leaf_ndim):
    name = path_names[-1]
    # MoE expert tensors share leaf names with the dense MLP; they are
    # distinguished by their path (model.py nests them under "moe").  Do NOT
    # key on rank: a scanned dense w_gate [repeats, d, ff] and an unscanned
    # expert w_gate [E, d, ff] have the same rank.
    in_moe = any("moe" in p for p in path_names)
    rules = _MOE_RULES if (in_moe and name in _MOE_RULES) else _PARAM_RULES
    axes = rules.get(name)
    if axes is None:
        return (None,) * leaf_ndim
    # stacked (scanned) params have one extra leading repeat dim
    extra = leaf_ndim - len(axes)
    return (None,) * extra + tuple(axes)


def _fsdp_axes(axes, dims, sizes):
    """Add the fsdp logical axis on the first large, divisible, unsharded dim
    (the ZeRO-3 / sort-destination parameter sharding)."""
    total = int(np.prod([sizes.get(n, 1) for n in LOGICAL["fsdp"] if n in sizes]))
    if total <= 1:
        return axes
    out = list(axes)
    for i, (ax, dim) in enumerate(zip(axes, dims)):
        if ax is None and dim % total == 0 and dim >= 1024:
            out[i] = "fsdp"
            break
    return tuple(out)


def param_specs(params_shape, mesh, zero=True):
    """PartitionSpec pytree for a params pytree (of ShapeDtypeStructs)."""
    sizes = _mesh_axis_sizes(mesh)

    def leaf_spec(path, leaf):
        names = [getattr(pp, "key", getattr(pp, "name", str(pp)))
                 for pp in path]
        axes = _rule_for(names, len(leaf.shape))
        if zero:
            axes = _fsdp_axes(axes, leaf.shape, sizes)
        return resolve(axes, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


def named_shardings(spec_tree, mesh):
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))
