"""Elastic scaling: checkpoint-mediated re-meshing after capacity change.

The contract that makes elasticity work (DESIGN.md section 5):
  1. checkpoints are *mesh-agnostic* -- leaves are saved unsharded-logical,
     so any mesh can load them (checkpoint/store.py);
  2. the data pipeline is *step-indexed* -- batch_at(step) is pure, so the
     resumed job replays the stream exactly with no data state;
  3. shardings are *derived from the mesh*, not stored -- param_specs(mesh)
     recomputes the placement for whatever mesh survives.

``remesh_restore`` is the whole mechanism: given the surviving device set,
rebuild the mesh, recompute specs, restore, continue.  The simulation in
tests/test_elastic.py shrinks 8 -> 4 devices mid-run and verifies the loss
trajectory continues bit-compatibly for the data stream.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import restore_checkpoint
from repro.models import train as T


def _named(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda s: isinstance(s, P))


def remesh_restore(ckpt_dir: str, cfg, new_mesh, optimizer=None):
    """Restore the latest checkpoint onto ``new_mesh`` (any shape/size).

    Returns (state, step). Batch size must stay divisible by the new data
    axes; callers adjust microbatching to keep the global batch constant
    (gradient-equivalent elasticity).
    """
    optimizer = optimizer or T.make_optimizer()
    state_shape = T.abstract_state(cfg, optimizer)
    with jax.set_mesh(new_mesh):
        specs = T.train_state_specs(state_shape, new_mesh, zero=cfg.zero)
        shardings = _named(specs, new_mesh)
        state, step = restore_checkpoint(ckpt_dir, state_shape,
                                         shardings=shardings)
    return state, step


def plan_elastic_batch(global_batch: int, old_dp: int, new_dp: int,
                       microbatches: int = 1):
    """Keep the global batch (and thus the optimizer trajectory) constant
    when the data-parallel width changes: scale microbatching instead.

    Returns (per_step_batch, new_microbatches).  E.g. 256 @ dp=16 mb=1
    -> dp=8 gives mb=2: each device processes 2x the tokens per step,
    gradients are identical in expectation and the step count is unchanged.
    """
    if global_batch % new_dp:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"surviving dp width {new_dp}")
    scale = max(1, old_dp // max(new_dp, 1))
    return global_batch, microbatches * scale
