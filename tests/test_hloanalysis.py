"""HLO analyzer: parsing, trip counts, byte models, end-to-end vs XLA."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hloanalysis as H

SYNTH = """
HloModule test, num_partitions=4

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,128]{1,0} get-tuple-element(%p), index=1
  %c1 = s32[] constant(1)
  %i2 = s32[] add(%i, %c1)
  %w = f32[128,128]{1,0} constant({...})
  %y = f32[8,128]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,128]{1,0} all-reduce(%y), replica_groups=[2,2]<=[4], to_apply=%add
  ROOT %t = (s32[], f32[8,128]{1,0}) tuple(%i2, %ar)
}

%cond (p: (s32[], f32[8,128])) -> pred[] {
  %p = (s32[], f32[8,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,128]) -> f32[8,128] {
  %x = f32[8,128]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %init = (s32[], f32[8,128]{1,0}) tuple(%c0, %x)
  %wh = (s32[], f32[8,128]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,128]{1,0} get-tuple-element(%wh), index=1
}
"""


def test_synthetic_while_trip_multiplication():
    a = H.analyze(SYNTH, total_devices=4)
    # dot: 2*8*128*128 flops x 5 trips
    assert a.flops == 5 * 2 * 8 * 128 * 128
    # all-reduce f32[8,128] over groups of 2: 2*4096*(1/2) bytes x 5
    assert a.collective_bytes == 5 * 2 * (8 * 128 * 4) * 0.5
    assert a.unresolved_loops == 0


def test_shape_bytes():
    assert H._shape_bytes("f32[8,128]{1,0}") == 4096
    assert H._shape_bytes("bf16[2,3]") == 12
    assert H._shape_bytes("(s32[], f32[4])") == 20
    assert H._shape_bytes("pred[7]") == 7
    assert H._shape_bytes("f32[]") == 4


def test_group_size_formats():
    assert H._group_size("replica_groups=[2,4]<=[8]", 8) == 4
    assert H._group_size("replica_groups=[4,2]<=[2,4]T(1,0)", 8) == 2
    assert H._group_size("replica_groups={{0,1,2,3}}", 8) == 4
    assert H._group_size("no groups here", 8) == 8


def test_real_compile_matches_xla_flops():
    """Unrolled program (no loops): analyzer flops == XLA cost flops."""

    def f(a, b):
        return jnp.dot(a, b).sum()

    a = jnp.ones((64, 32), jnp.float32)
    b = jnp.ones((32, 16), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    ana = H.analyze(c.as_text(), total_devices=1)
    want = c.cost_analysis()["flops"]
    assert abs(ana.flops - want) / want < 0.05


def test_real_scan_trip_correction():
    """Scanned matmul: analyzer = L x per-layer flops; XLA counts once."""
    L, D = 4, 64

    def f(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), ()
        out, _ = jax.lax.scan(body, x, w)
        return out.sum()

    w = jnp.ones((L, D, D), jnp.float32)
    x = jnp.ones((8, D), jnp.float32)
    c = jax.jit(f).lower(w, x).compile()
    ana = H.analyze(c.as_text(), total_devices=1)
    per_layer = 2 * 8 * D * D
    assert ana.flops == pytest.approx(L * per_layer, rel=0.05)


def test_dus_byte_model():
    """Touched-bytes: a scan writing slices must not charge the full
    accumulator per iteration."""
    N = 1024

    def f(x):
        def body(acc, i):
            acc = jax.lax.dynamic_update_slice_in_dim(
                acc, x[None] * i.astype(jnp.float32), i, axis=0)
            return acc, ()
        acc0 = jnp.zeros((N, 128), jnp.float32)
        out, _ = jax.lax.scan(body, acc0, jnp.arange(N))
        return out.sum()

    c = jax.jit(f).lower(jnp.ones((128,), jnp.float32)).compile()
    ana = H.analyze(c.as_text(), total_devices=1)
    full_charge = N * (N * 128 * 4)  # what naive counting would give
    assert ana.bytes_accessed < 0.05 * full_charge
