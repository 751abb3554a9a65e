"""Dry-run machinery on an 8-device subprocess with reduced configs:
build_cell lowers+compiles for train/prefill/decode kinds, and the roofline
record has all required fields.  (The full 512-device x full-size sweep is
the deliverable run by `python -m repro.launch.dryrun --all`.)"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat, configs
from repro.launch import hloanalysis
from repro.launch.dryrun import build_cell, model_flops, roofline
from repro.models.config import ModelConfig, ShapeConfig

mesh = compat.make_mesh((2, 4), ("data", "model"),
                        axis_types=compat.auto_axes(2))

results = {}
shapes = {
    "train": ShapeConfig("t", 64, 8, "train"),
    "prefill": ShapeConfig("p", 64, 8, "prefill"),
    "decode": ShapeConfig("d", 64, 8, "decode"),
}
for arch in ("gemma2-9b", "kimi-k2-1t-a32b", "xlstm-350m", "hubert-xlarge"):
    cfg = configs.smoke_config(arch)
    for kind, shape in shapes.items():
        if cfg.encoder_only and kind == "decode":
            continue
        with jax.set_mesh(mesh):
            jitted, args = build_cell(cfg, shape, mesh)
            compiled = jitted.lower(*args).compile()
        ana = hloanalysis.analyze(compiled.as_text(), 8)
        rl = roofline(ana, 8)
        key = f"{arch}:{kind}"
        results[key] = {
            "flops": ana.flops, "bottleneck": rl["bottleneck"],
            "collective_count": ana.collective_count,
        }
print("RESULTS " + json.dumps(results))
"""


@pytest.mark.slow
def test_dryrun_cells_reduced():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULTS ")][-1]
    res = json.loads(line[len("RESULTS "):])
    # 4 archs x 3 kinds - 1 encoder-only decode = 11 cells
    assert len(res) == 11
    for key, rec in res.items():
        assert rec["flops"] > 0, key
        assert rec["bottleneck"] in ("compute", "memory", "collective")
    # training must communicate (grad sync at minimum)
    assert res["gemma2-9b:train"]["collective_count"] > 0
