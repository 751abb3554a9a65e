"""The fused push kernel compiled by Mosaic for a described TPU v5e.

No chip is needed: the TPU compiler compiles for the ``v5e:2x2`` topology
described in the ``topo`` fixture (skipped where it cannot be described),
from shapes alone.  Each case is compiled at the largest size
``blocks.fused_footprint`` admits on a v5e and must contain the Mosaic
``tpu_custom_call``; the rejection cases hold the footprint check to the
compiler, which must refuse shapes the check declines.

Only a fixture touches the TPU library -- never an import, a ``skipif`` or
a ``parametrize`` -- and the persistent compilation cache is off around
these compiles (a described-device entry cannot be read back here).
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import blocks, push_fused
from repro.kernels.blocks import BLOCK_E, BLOCK_V, EDGE_ROWS

KIND = "TPU v5 lite"  # what a v5e reports as its device_kind


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    assert topo.devices[0].device_kind == KIND
    return SingleDeviceSharding(topo.devices[0])


def _largest(fits, step):
    """Largest multiple of ``step`` for which ``fits`` holds (monotone)."""
    lo, hi = step, step
    while fits(hi):
        lo, hi = hi, hi * 2
    while hi - lo > step:
        mid = (lo + hi) // 2 // step * step
        lo, hi = (mid, hi) if fits(mid) else (lo, mid)
    return lo


def _admitted(batch, weight, init):
    """(edge blocks, vertices) at the footprint check's v5e limits."""
    cap = blocks.TPU_FAST_MEMORY[KIND]

    def fp(nb, v):
        return blocks.fused_footprint(nb, v, v, batch=batch, weight=weight,
                                      init=init)

    nb = _largest(lambda n: fp(n, BLOCK_V)["smem"] <= cap["smem"], EDGE_ROWS)
    v = _largest(lambda n: fp(EDGE_ROWS, n)["vmem"] <= cap["vmem"], BLOCK_V)
    assert blocks.fused_misfit(fp(nb, v), KIND) is None
    return nb, v


def _compile(sharding, nb, V, combine, dtype, batch=None, weight=False,
             unit=False, init=False):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    E = nb * BLOCK_E
    plane = (V,) if batch is None else (V, batch)
    out_dt = dtype if combine == "min" or jnp.issubdtype(dtype, jnp.integer) \
        else jnp.float32

    def step(band, src, dst, valid, w, vals, seed):
        return push_fused.fused_push(band, src, dst, valid, w, vals, V,
                                     combine=combine, unit_weight=unit,
                                     init=seed, interpret=False)

    lowered = jax.jit(step).lower(
        arg((4, nb), jnp.int32), arg((E,), jnp.int32), arg((E,), jnp.int32),
        arg((E,), jnp.int32), arg((E,), jnp.float32) if weight else None,
        arg(plane, dtype), arg(plane, out_dt) if init else None)
    return lowered.compile().as_text()


CASES = {
    # name: (combine, dtype, batch, weight, unit, init)
    "add_f32_weighted": ("add", jnp.float32, None, True, False, False),
    "add_f32_b16_init": ("add", jnp.float32, 16, False, False, True),
    "add_i32": ("add", jnp.int32, None, False, False, False),
    "min_f32_weighted": ("min", jnp.float32, None, True, False, False),
    "min_f32_weighted_b16_init": ("min", jnp.float32, 16, True, False, True),
    "min_i32_unit": ("min", jnp.int32, None, False, True, False),
    "min_i32_unit_b16": ("min", jnp.int32, 16, False, True, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_push_compiles_at_admitted_limit(one_chip, case):
    combine, dtype, batch, weight, unit, init = CASES[case]
    nb, V = _admitted(batch, weight, init)
    text = _compile(one_chip, nb, V, combine, dtype, batch=batch,
                    weight=weight, unit=unit, init=init)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("space", ["vmem", "smem"])
def test_footprint_rejection_is_a_compiler_refusal(one_chip, space):
    """One step past what fits -- the planes alone over VMEM, or the band
    table alone over SMEM -- is declined by the check AND by Mosaic."""
    cap = blocks.TPU_FAST_MEMORY[KIND]
    nb, V = _admitted(None, True, False)
    if space == "vmem":
        V = (cap["vmem"] // 8 // BLOCK_V + 8) * BLOCK_V  # vals + out > cap
    else:
        nb = (cap["smem"] // 16 // EDGE_ROWS + 1) * EDGE_ROWS  # 4*nb*4 > cap
    fp = blocks.fused_footprint(nb, V, V, weight=True)
    assert blocks.fused_misfit(fp, KIND) == space
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _compile(one_chip, nb, V, "min", jnp.float32, weight=True)
