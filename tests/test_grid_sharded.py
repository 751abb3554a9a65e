"""The grid layout built where it lives (``graph._build_grid_sharded``):
each rectangle's stable sort, pack and band table on the device that holds
it, equal to the host build bit for bit.

One device (this process): ``grid(1,1)`` takes the sharded build on a mesh
of one, ``partition`` leaves the layout to the engine, the streamed engine
keeps the host build, every edge's rectangle is computed once.  Four
devices (one subprocess with four forced host devices, as in
``test_multidevice.py``): ``grid(2,2)`` and ``grid(2,2,degree_sorted)``,
weighted and unweighted, against the host build; each rectangle's planes
on its own device; PageRank over the degree-sorted grid against the
benchmark's plain reference and the one-chip engine.
"""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import repro.core as C
from repro import obs
from repro.core import Engine, StreamConfig, partition

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLANES = ("gr_src_local", "gr_dst_col", "gr_edge_weight", "gr_band",
          "gr_edge_valid")


def _graph(weighted):
    g = C.rmat(11, 24 * 2048, seed=5)
    return C.random_weights(g, seed=6) if weighted else g


@pytest.mark.parametrize("weighted", [False, True])
def test_one_device_sharded_build_equals_host_build(weighted, monkeypatch):
    g = _graph(weighted)
    monkeypatch.setenv("REPRO_DEVICE_BUILD", "device")
    pg = partition(g, 1, "grid(1,1)")
    eng = Engine(pg)
    assert eng.dispatch["grid"]["layout"] == "sharded"
    monkeypatch.setenv("REPRO_DEVICE_BUILD", "host")
    host = partition(g, 1, "grid(1,1)")
    for name in PLANES:
        assert np.array_equal(np.asarray(eng.arrays[name]),
                              getattr(host, name)), name
    assert np.array_equal(pg.gr_band, host.gr_band)
    want, want_it = Engine(host).run("pagerank", iters=20)
    got, it = eng.run("pagerank", iters=20)
    assert it == want_it == 20
    assert np.array_equal(got, want)


def test_partition_leaves_a_device_built_grid_layout_to_the_engine(
        monkeypatch):
    g = _graph(False)
    monkeypatch.setenv("REPRO_DEVICE_BUILD", "device")
    assert "grid" not in partition(g, 1, "grid(1,1)")._lazy
    monkeypatch.setenv("REPRO_DEVICE_BUILD", "host")
    assert "grid" in partition(g, 1, "grid(1,1)")._lazy
    # more rectangles than devices: no engine could shard it, so eager
    monkeypatch.setenv("REPRO_DEVICE_BUILD", "device")
    assert "grid" in partition(g, 4, "grid(2,2)")._lazy


def test_streamed_engine_keeps_the_host_build(monkeypatch):
    g = _graph(True)
    monkeypatch.setenv("REPRO_DEVICE_BUILD", "device")
    pg = partition(g, 1, "grid(1,1)")
    eng = Engine(pg, residency="stream", stream=StreamConfig(windows=2))
    assert eng.dispatch["grid"]["layout"] == "stream"
    assert "grid" in pg._lazy and not any(
        k.startswith("sharded") for k in pg._dev)
    ref, ref_it = Engine(partition(g, 1, "grid(1,1)")).run("sssp", source=3)
    got, it = eng.run("sssp", source=3)
    assert np.array_equal(got, ref) and it == ref_it


def test_host_grid_layout_span_records_site_and_rectangles(monkeypatch):
    g = _graph(False)
    monkeypatch.setenv("REPRO_DEVICE_BUILD", "host")
    obs.reset()
    pg = partition(g, 4, "grid(2,2,degree_sorted)")
    (rec,) = obs.records("graph.layout")
    assert rec.attrs["which"] == "grid" and rec.attrs["where"] == "host"
    assert rec.attrs["rect_edges"] == pg.plan.rect_counts.tolist()
    assert sum(rec.attrs["rect_edges"]) == g.num_edges
    # the 1-D layouts name only their order
    obs.reset()
    partition(g, 2)
    assert [r.attrs for r in obs.records("graph.layout")] == [
        {"which": "basic"}, {"which": "sd"}]


def test_grid_dispatch_records_geometry_and_lowering(monkeypatch):
    monkeypatch.setenv("REPRO_DEVICE_BUILD", "host")
    eng = Engine(partition(_graph(False), 1, "grid(1,1)"))
    assert eng.dispatch["grid"] == {
        "rows": 1, "cols": 1, "row_chunk": eng.pg.chunk_size,
        "col_chunk": eng.pg.col_chunk_size, "collectives": "grouped",
        "layout": "host"}
    assert "grid" not in Engine(partition(_graph(False), 1)).dispatch


def test_edge_rectangles_are_computed_once(monkeypatch):
    from repro.kernels import blocks

    calls = []
    orig = blocks.edge_rectangles
    monkeypatch.setattr(blocks, "edge_rectangles",
                        lambda *a: calls.append(1) or orig(*a))
    g = _graph(False)
    pg = partition(g, 4, "grid(2,2)")
    assert len(calls) == 1
    assert pg._base.owner is pg.plan.edge_rect
    assert pg.plan.edge_rect.dtype == np.int8
    assert np.array_equal(np.bincount(pg.plan.edge_rect, minlength=4),
                          pg.plan.rect_counts)


def test_grid_row_width_is_one_shape_per_balanced_configuration():
    from repro.core.graph import _grid_row_width as width

    # the rectangles of six seeds of twitter-rv.grid22 at 2**20
    # (grid(2,2,degree_sorted), 25,003,769 edges) share one width
    seeds = ([6294121, 6249330, 6251311, 6209007],
             [6292201, 6251115, 6253843, 6206610],
             [6290165, 6253269, 6249786, 6210549],
             [6294320, 6249124, 6250347, 6209978],
             [6292847, 6250692, 6253235, 6206995],
             [6293753, 6249639, 6250144, 6210233])
    assert {sum(c) for c in seeds} == {25003769}
    assert {width(np.array(c)) for c in seeds} == {6313472}
    # an unbalanced placement keeps its largest rectangle
    assert width(np.array([57000, 20000, 13000, 10000])) == 57856
    # every width lies half a 1024 tile past a multiple of 1024
    for counts in ([0, 0, 0, 0], [1, 2, 3, 4], [1024, 0, 0, 0],
                   [896, 896, 896, 896], [10**7, 1, 1, 1]):
        w = width(np.array(counts))
        assert w % 1024 == 512 and 0 <= w - max(counts) < 1024 + \
            sum(counts) // 100


def test_grid_layout_takes_the_row_width(monkeypatch):
    from repro.core.graph import _grid_row_width

    monkeypatch.setenv("REPRO_DEVICE_BUILD", "host")
    pg = partition(_graph(True), 4, "grid(2,2,degree_sorted)")
    emax = _grid_row_width(pg.plan.rect_counts)
    assert pg.gr_src_local.shape == pg.edge_valid.shape == (4, emax)
    assert np.array_equal(pg.edge_valid.sum(axis=1), pg.plan.rect_counts)


# ---------------------------------------------------------------------------
# Four devices, one subprocess
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, json
import numpy as np
import jax
import repro.core as C
from repro import obs
from repro.core import Engine, partition
from repro.core.engine import make_pe_mesh

PLANES = %(planes)r
res = {"build": {}}
mesh = make_pe_mesh(4)
g = C.rmat(12, 24 * 4096, seed=3)
for name in ("grid(2,2)", "grid(2,2,degree_sorted)"):
    for weighted in (False, True):
        gg = C.random_weights(g, seed=4) if weighted else g
        os.environ["REPRO_DEVICE_BUILD"] = "host"
        host = partition(gg, 4, name)
        os.environ["REPRO_DEVICE_BUILD"] = "device"
        pg = partition(gg, 4, name)
        dev = pg.device_arrays("grid", mesh=mesh)
        res["build"][f"{name}-{weighted}"] = {
            k: bool(np.array_equal(np.asarray(dev[k]), getattr(host, k)))
            for k in PLANES}
        res["build"][f"{name}-{weighted}"]["host_band"] = bool(
            np.array_equal(pg.gr_band, host.gr_band))
# where each rectangle's rows sit (the last build above)
devices = list(mesh.devices.flat)
res["placement"] = {
    k: sorted([sh.index[0].start, devices.index(sh.device),
               int(np.asarray(sh.data).shape[0])]
              for sh in dev[k].addressable_shards)
    for k in PLANES}

# PageRank over the degree-sorted grid, 20 supersteps
os.environ["REPRO_DEVICE_BUILD"] = "device"
obs.reset()
eng = Engine(partition(g, 4, "grid(2,2,degree_sorted)"))
got, it = eng.run("pagerank", alpha=0.85, iters=20)
res["iters"] = int(it)
res["dispatch"] = eng.dispatch["grid"]
res["layout_span"] = [r.attrs for r in obs.records("graph.layout")]
res["num_edges"] = g.num_edges
one, one_it = Engine(partition(g, 1)).run("pagerank", alpha=0.85, iters=20)
res["one_chip_rel"] = float(np.max(np.abs(got - one) / one))
res["one_chip_iters"] = int(one_it)
spec = importlib.util.spec_from_file_location(
    "ref", os.path.join(%(root)r, "bench", "refs", "pagerank.py"))
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)


class Edges:
    num_vertices = g.num_vertices
    num_edges = g.num_edges
    src, dst = g.src, g.dst


want = ref.pagerank(Edges, 0.85, 20)
res["ref_rel"] = float(np.max(np.abs(got - want) / want))
print("RESULTS " + json.dumps(res))
"""


@functools.lru_cache(maxsize=None)
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("REPRO_DEVICE_BUILD", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"planes": PLANES, "root": ROOT}],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


@pytest.mark.parametrize("case", [
    f"{p}-{w}" for p in ("grid(2,2)", "grid(2,2,degree_sorted)")
    for w in (False, True)])
def test_four_device_sharded_build_equals_host_build(case):
    got = four_devices()["build"][case]
    assert all(got.values()), got


def test_each_rectangle_sits_on_its_own_device():
    for name, shards in four_devices()["placement"].items():
        # rectangle k's row, and only it, on mesh device k
        assert shards == [[k, k, 1] for k in range(4)], name


def test_degree_sorted_grid_pagerank_matches_the_reference():
    res = four_devices()
    assert res["iters"] == 20
    assert res["ref_rel"] <= 5e-4


def test_degree_sorted_grid_pagerank_matches_one_chip():
    res = four_devices()
    assert res["one_chip_iters"] == 20
    assert res["one_chip_rel"] <= 1e-5


def test_four_device_span_and_dispatch_name_the_sharded_build():
    res = four_devices()
    assert res["dispatch"] == {"rows": 2, "cols": 2, "row_chunk": 2048,
                               "col_chunk": 2048, "collectives": "grouped",
                               "layout": "sharded"}
    (span,) = res["layout_span"][:1]
    assert span["which"] == "grid" and span["where"] == "sharded"
    assert sum(span["rect_edges"]) == res["num_edges"]
    assert max(span["rect_edges"]) / res["num_edges"] < 0.3
