"""Training driver: config -> mesh -> data -> train loop, with fault
tolerance (checkpoint/restart, async saves, per-step watchdog) and elastic
restart hooks.

Usage (CPU example -- any arch's SMOKE config):
    PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume

At scale the same driver runs the full config on the production mesh; device
count and mesh shape are the only differences (see launch/dryrun.py for the
compile-level proof).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint)
from repro.data import make_pipeline
from repro.launch.mesh import make_host_mesh
from repro.models import train as T
from repro.models.config import ModelConfig


def _named(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda s: isinstance(s, P))


class StepWatchdog:
    """Straggler/hang mitigation at the driver level: if a step exceeds
    ``factor`` x the rolling median, log a warning (at scale: report the
    slow host to the controller for replacement; here: surface it)."""

    def __init__(self, factor: float = 3.0, warmup: int = 5):
        self.durations = []
        self.factor = factor
        self.warmup = warmup
        self.flagged = 0

    def observe(self, seconds: float) -> bool:
        self.durations.append(seconds)
        if len(self.durations) < self.warmup:
            return False
        med = float(np.median(self.durations[-50:]))
        if seconds > self.factor * med:
            self.flagged += 1
            print(f"[watchdog] step took {seconds:.3f}s "
                  f"(median {med:.3f}s) -- straggler suspected")
            return True
        return False


def train_loop(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
               ckpt_dir: str | None = None, resume: bool = False,
               ckpt_every: int = 50, log_every: int = 10,
               peak_lr: float = 3e-4, microbatches: int = 1,
               mesh=None, seed: int = 0) -> dict:
    mesh = mesh or make_host_mesh()
    optimizer = T.make_optimizer(peak_lr=peak_lr, warmup=min(100, steps // 10),
                                 total=steps)
    step_fn = T.make_train_step(cfg, optimizer, microbatches=microbatches)

    with jax.set_mesh(mesh):
        state_shape = T.abstract_state(cfg, optimizer)
        specs = T.train_state_specs(state_shape, mesh, zero=cfg.zero)
        shardings = _named(specs, mesh)
        start = 0
        if resume and ckpt_dir and latest_step(ckpt_dir) is not None:
            state, start = restore_checkpoint(ckpt_dir, state_shape,
                                              shardings=shardings)
            print(f"[train] resumed from step {start}")
        else:
            state = jax.jit(
                lambda k: T.init_state(k, cfg, optimizer),
                out_shardings=shardings)(jax.random.key(seed))

        pipe = make_pipeline(cfg, batch, seq, seed=seed)
        jit_step = jax.jit(step_fn, donate_argnums=(0,))
        ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        watchdog = StepWatchdog()
        history = []
        t_train0 = time.time()
        for s in range(start, steps):
            t0 = time.time()
            # step-indexed pipeline: resume replays the exact stream
            state, metrics = jit_step(state, pipe.batch_at(s))
            metrics = {k: float(v) for k, v in metrics.items()}
            dt = time.time() - t0
            watchdog.observe(dt)
            history.append(metrics["loss"])
            if log_every and (s + 1) % log_every == 0:
                print(f"[train] step {s + 1:5d} loss={metrics['loss']:.4f} "
                      f"ce={metrics['ce']:.4f} gnorm={metrics['grad_norm']:.3f} "
                      f"{dt * 1e3:.0f}ms")
            if ckpt and (s + 1) % ckpt_every == 0:
                ckpt.save(s + 1, state)
        if ckpt:
            ckpt.save(steps, state)
            ckpt.wait()
        wall = time.time() - t_train0
    return {"final_loss": history[-1] if history else None,
            "first_loss": history[0] if history else None,
            "steps": steps - start, "wall_s": wall,
            "straggler_flags": watchdog.flagged,
            "history": history}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--peak-lr", type=float, default=3e-4)
    ap.add_argument("--out-json", default=None)
    args = ap.parse_args()

    cfg = (configs.smoke_config if args.smoke else configs.get_config)(args.arch)
    res = train_loop(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                     ckpt_dir=args.ckpt_dir, resume=args.resume,
                     ckpt_every=args.ckpt_every, peak_lr=args.peak_lr,
                     microbatches=args.microbatches)
    print(f"[train] done: loss {res['first_loss']:.4f} -> "
          f"{res['final_loss']:.4f} in {res['steps']} steps "
          f"({res['wall_s']:.1f}s)")
    if args.out_json:
        with open(args.out_json, "w") as f:
            json.dump({k: v for k, v in res.items() if k != "history"}, f)


if __name__ == "__main__":
    main()
