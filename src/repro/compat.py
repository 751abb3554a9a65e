"""Mesh and grouped-collective helpers for the installed JAX.

The repo is written for one JAX release (0.9) and calls ``jax.shard_map``,
``jax.set_mesh`` and ``jax.sharding.get_abstract_mesh`` directly.  This
module keeps the mesh conveniences several call sites share, and
``grouped_reduce``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AxisType


def auto_axes(n: int):
    """``axis_types`` tuple for an all-Auto mesh."""
    return (AxisType.Auto,) * n


def make_mesh(axis_shapes, axis_names, axis_types=None, devices=None):
    """``jax.make_mesh`` with list-friendly shapes and names."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=axis_types, devices=devices)


def mesh_has_manual_axes(mesh) -> bool:
    """True when any mesh axis is Manual (inside a shard_map body)."""
    return any(t == AxisType.Manual for t in getattr(mesh, "axis_types", ()))


# --------------------------------------------------------------------------
# Grouped collectives (axis_index_groups under shard_map)
# --------------------------------------------------------------------------
#
# ``jax.lax.psum/pmin(..., axis_index_groups=...)`` reduces over a *subset*
# of an axis (grid2d's column groups); it lowers to one all-reduce with
# ``replica_groups`` of the group size.  ``grouped_reduce`` uses it and lets
# any lowering failure surface.  ``mode="emulate"`` -- asked for by name,
# only by tests -- instead runs the group-expanded full-axis reduce: each
# shard scatters its contribution into its own group's row of a
# ``[n_groups, ...]`` buffer (identity elsewhere), one full-axis reduce
# combines all groups at once, and the shard reads its group's row back.
# Same result at full-axis wire cost: a reference, not a path.

_GROUPED_IDENT = {
    "add": lambda dt: jnp.zeros((), dt),
    "min": lambda dt: (jnp.asarray(jnp.inf, dt)
                       if jnp.issubdtype(dt, jnp.floating)
                       else jnp.asarray(jnp.iinfo(dt).max, dt)),
}


def _emulated_grouped_reduce(x, axis, groups, combine):
    import numpy as np

    total = sum(len(g) for g in groups)
    group_of = np.empty(total, dtype=np.int32)
    for gi, g in enumerate(groups):
        group_of[list(g)] = gi
    me = jax.lax.axis_index(axis)
    gid = jnp.asarray(group_of)[me]
    ident = _GROUPED_IDENT[combine](x.dtype)
    big = jnp.full((len(groups),) + x.shape, ident, x.dtype)
    big = big.at[gid].set(x)
    full = jax.lax.psum(big, axis) if combine == "add" \
        else jax.lax.pmin(big, axis)
    return full[gid]


def grouped_reduce(x, axis, groups, combine, mode="native"):
    """Reduce ``x`` over ``axis`` within each index group.

    ``groups`` is a static partition of the axis indices (list of lists,
    every index exactly once); ``combine`` is ``"add"`` or ``"min"``.  Each
    shard receives the reduction over its own group.  Min-monoid results are
    bit-exact against the full-axis lowering in either mode (min is
    order-free); add may differ by float reassociation only.
    """
    if combine not in _GROUPED_IDENT:
        raise ValueError(f"grouped_reduce: unsupported combine {combine!r}")
    if mode not in ("native", "emulate"):
        raise ValueError(f"grouped_reduce: unknown mode {mode!r}")
    groups = [list(g) for g in groups]
    op = jax.lax.psum if combine == "add" else jax.lax.pmin
    if len(groups) == 1:  # degenerate: one group == the whole axis
        return op(x, axis)
    if mode == "emulate":
        return _emulated_grouped_reduce(x, axis, groups, combine)
    return op(x, axis, axis_index_groups=groups)
