"""Elastic scaling: train on 8 devices, lose half the fleet, resume on 4.

Runs in a subprocess (forced host devices must not leak into other tests).
Verifies the three elasticity contracts from launch/elastic.py:
mesh-agnostic checkpoints, step-indexed data, derived shardings.
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat

from repro.models.config import ModelConfig
from repro.models import train as T
from repro.data import SyntheticLM
from repro.checkpoint import save_checkpoint
from repro.launch.elastic import remesh_restore, plan_elastic_batch

cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256)
opt = T.make_optimizer(peak_lr=1e-3, warmup=2, total=40)
pipe = SyntheticLM(256, batch=8, seq_len=32, seed=0)
results = {}

def mk_mesh(n):
    return compat.make_mesh((n,), ("data",), axis_types=compat.auto_axes(1))

# ---- reference: uninterrupted 10-step run on 8 devices --------------------
mesh8 = mk_mesh(8)
with jax.set_mesh(mesh8):
    state = T.init_state(jax.random.key(0), cfg, opt)
    step = jax.jit(T.make_train_step(cfg, opt))
    ref_losses = []
    for s in range(10):
        state, m = step(state, pipe.batch_at(s))
        ref_losses.append(float(m["loss"]))

# ---- elastic: 5 steps on 8 devices, checkpoint, resume on 4 ----------------
ckdir = tempfile.mkdtemp()
with jax.set_mesh(mesh8):
    state = T.init_state(jax.random.key(0), cfg, opt)
    step = jax.jit(T.make_train_step(cfg, opt))
    for s in range(5):
        state, m = step(state, pipe.batch_at(s))
    save_checkpoint(ckdir, 5, state)

mesh4 = mk_mesh(4)
state4, start = remesh_restore(ckdir, cfg, mesh4, optimizer=opt)
results["resume_step"] = start
_, mb = plan_elastic_batch(8, old_dp=8, new_dp=4)
results["new_microbatches"] = mb
with jax.set_mesh(mesh4):
    step4 = jax.jit(T.make_train_step(cfg, opt, microbatches=mb))
    el_losses = []
    for s in range(start, 10):
        state4, m = step4(state4, pipe.batch_at(s))
        el_losses.append(float(m["loss"]))

# elastic continuation must track the uninterrupted run's trajectory
results["max_loss_delta"] = max(abs(a - b) for a, b
                                in zip(el_losses, ref_losses[5:]))
print("RESULTS " + json.dumps(results))
"""


@pytest.mark.slow
def test_elastic_remesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULTS ")][-1]
    res = json.loads(line[len("RESULTS "):])
    assert res["resume_step"] == 5
    assert res["new_microbatches"] == 2
    assert res["max_loss_delta"] < 0.05, res
