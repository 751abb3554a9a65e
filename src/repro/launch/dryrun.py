import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, prove it fits, and extract the roofline terms.

The two lines above MUST run before any other import (jax locks the device
count on first init); do not move them.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

Per cell this prints/records:
    memory_analysis()   per-device argument/temp/output bytes (proves fit)
    cost_analysis()     XLA's aggregate flops/bytes (while bodies counted 1x)
    hloanalysis         trip-count-corrected flops / bytes / collective wire
                        bytes parsed from compiled.as_text()
    roofline            compute / memory / collective seconds + bottleneck
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch import hloanalysis
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models import model as M
from repro.models import serve as SV
from repro.models import train as T
from repro.models.config import SHAPES
from repro.models.sharding import param_specs


def _named(tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda s: isinstance(s, P))


def build_cell(cfg, shape, mesh):
    """-> (jitted fn ready to .lower, abstract args tuple)."""
    specs = configs.input_specs(cfg, shape)

    if shape.kind == "train":
        state = T.abstract_state(cfg)
        state_specs = _named(T.train_state_specs(state, mesh, zero=cfg.zero),
                             mesh)
        batch_sh = _named(T.batch_specs(specs, mesh), mesh)
        fn = T.make_train_step(cfg)
        jitted = jax.jit(fn, in_shardings=(state_specs, batch_sh),
                         out_shardings=(state_specs, None),
                         donate_argnums=(0,))
        return jitted, (state, specs)

    params = M.abstract_params(cfg)
    pspecs = _named(param_specs(params, mesh,
                                zero=cfg.serve_zero), mesh)

    if shape.kind == "prefill":
        batch_sh = _named(T.batch_specs(specs, mesh), mesh)
        fn = SV.make_prefill_step(cfg)
        jitted = jax.jit(fn, in_shardings=(pspecs, batch_sh))
        return jitted, (params, specs)

    # decode
    cache = specs["cache"]
    cache_specs = _named(SV.cache_specs(cache, cfg, mesh), mesh)
    tok_spec = _named(T.batch_specs({"tokens": specs["tokens"]}, mesh),
                      mesh)["tokens"]
    pos_spec = NamedSharding(mesh, P())
    fn = SV.make_decode_step(cfg)
    jitted = jax.jit(fn,
                     in_shardings=(pspecs, tok_spec, pos_spec, cache_specs),
                     out_shardings=(None, cache_specs),
                     donate_argnums=(3,))
    return jitted, (params, specs["tokens"], specs["pos"], cache)


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS per the brief: 6*N_active*D train, 2*N_active*D
    inference (D = tokens processed by the step)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def roofline(analysis: hloanalysis.Analysis, chips: int):
    """Three terms in seconds (per-device program values)."""
    compute_s = analysis.flops / PEAK_FLOPS_BF16
    memory_s = analysis.bytes_accessed / HBM_BW
    collective_s = analysis.collective_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    terms["bottleneck"] = max(terms, key=lambda k: terms[k]).replace("_s", "")
    # TPU-equivalent collective time (bf16-native wire; see hloanalysis)
    terms["collective_tpu_s"] = \
        analysis.collective_bytes_bf16equiv / ICI_BW
    return terms


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             outdir: str | None = None, verbose: bool = True) -> dict:
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    status = configs.cell_status(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": status}
    if status != "run":
        if verbose:
            print(f"[{mesh_name}] {arch} x {shape_name}: SKIP ({status})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted, args = build_cell(cfg, shape, mesh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    ana = hloanalysis.analyze(hlo_text, total_devices=chips)
    rl = roofline(ana, chips)
    mf = model_flops(cfg, shape)

    rec.update({
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "xla_cost": {"flops": cost.get("flops"),
                     "bytes_accessed": cost.get("bytes accessed")},
        "hlo": ana.to_dict(),
        "roofline": rl,
        "model_flops_global": mf,
        "model_flops_per_chip": mf / chips,
        "useful_flop_ratio": (mf / chips) / ana.flops if ana.flops else None,
    })
    if verbose:
        m = rec["memory"]
        per_dev_gb = ((m["argument_bytes"] or 0) + (m["temp_bytes"] or 0)
                      + (m["output_bytes"] or 0) - (m["alias_bytes"] or 0)) / 1e9
        print(f"[{mesh_name}] {arch} x {shape_name}: compile={t_compile:.1f}s "
              f"mem/dev={per_dev_gb:.2f}GB "
              f"compute={rl['compute_s']:.4f}s memory={rl['memory_s']:.4f}s "
              f"collective={rl['collective_s']:.4f}s -> {rl['bottleneck']} "
              f"useful={rec['useful_flop_ratio'] and round(rec['useful_flop_ratio'], 3)}")
    if outdir:
        os.makedirs(outdir, exist_ok=True)
        path = os.path.join(outdir, f"{mesh_name}__{arch}__{shape_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (or --all)")
    ap.add_argument("--shape", default=None,
                    help="shape cell (default: all four)")
    ap.add_argument("--all", action="store_true", help="every arch x shape")
    ap.add_argument("--multi-pod", choices=("on", "off", "both"),
                    default="off")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--continue-on-error", action="store_true")
    args = ap.parse_args()

    archs = configs.list_archs() if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"on": [True], "off": [False], "both": [False, True]}[args.multi_pod]

    failures = []
    for multi_pod in pods:
        for arch in archs:
            for shape in shapes:
                try:
                    run_cell(arch, shape, multi_pod, outdir=args.out)
                except Exception as e:  # noqa: BLE001 -- report, keep going
                    failures.append((arch, shape, multi_pod, repr(e)))
                    print(f"FAIL {arch} x {shape} multi_pod={multi_pod}: {e}")
                    if not args.continue_on_error:
                        traceback.print_exc()
                        raise
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()
