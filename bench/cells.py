"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; every
piece sits in a file of its own under this directory, so a new cell, mix,
metric or reference is a new file and an entry, never an edit:

    configs/<config>.json    the deployment (graph, placement), via "file"
    traffic/<traffic>.json   the mix, read by ``traffic.py``
    metrics/<metric>.py      one reader per metric, ``read(ctx)``
    refs/<reference>.py      the plain reference a mix names
    peaks.json               the chip's peaks, keyed by ``device_kind``
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic file's contents
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files read."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    e2e = tuple(m for m in bench["end_to_end"] if _applies(m, name))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _applies(m, name) and m["moves"] in reported)
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def _module(path: str, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx) -> float | None``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    return _module(path, "bench_metric_" + name.replace(".", "_")).read


def reference(name: str):
    """The plain reference module ``refs/<name>.py``."""
    return _module(os.path.join(BENCH_DIR, "refs", name + ".py"),
                   "bench_ref_" + name.replace(".", "_"))


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind the table lacks is an error."""
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]
