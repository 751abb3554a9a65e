"""Optimizer transforms, schedules, int8 compression."""

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim as O
from repro import compat


def numpy_adamw(params, grads, steps, lr=1e-2, b1=0.9, b2=0.95, eps=1e-8,
                wd=0.1):
    mu = np.zeros_like(params)
    nu = np.zeros_like(params)
    p = params.copy()
    for t in range(1, steps + 1):
        g = grads[t - 1]
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        mu_hat = mu / (1 - b1 ** t)
        nu_hat = nu / (1 - b2 ** t)
        step = mu_hat / (np.sqrt(nu_hat) + eps)
        if p.ndim > 1:
            step = step + wd * p
        p = p - lr * step
    return p


def test_adamw_matches_numpy():
    rng = np.random.default_rng(0)
    p0 = rng.normal(size=(4, 8)).astype(np.float32)
    grads = [rng.normal(size=(4, 8)).astype(np.float32) for _ in range(5)]
    opt = O.adamw(O.constant_schedule(1e-2))
    params = {"w": jnp.asarray(p0)}
    state = opt.init(params)
    for g in grads:
        updates, state = opt.update({"w": jnp.asarray(g)}, state, params)
        params = O.apply_updates(params, updates)
    want = numpy_adamw(p0, grads, 5)
    np.testing.assert_allclose(np.asarray(params["w"]), want, rtol=1e-4,
                               atol=1e-5)


def test_no_weight_decay_on_1d():
    opt = O.adamw(O.constant_schedule(1e-2), weight_decay=1.0)
    params = {"scale": jnp.ones((8,))}
    state = opt.init(params)
    updates, _ = opt.update({"scale": jnp.zeros((8,))}, state, params)
    # zero grads + no decay on 1-D -> zero update
    assert float(jnp.max(jnp.abs(updates["scale"]))) == 0.0


def test_clip_by_global_norm():
    clip = O.clip_by_global_norm(1.0)
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((4,), -10.0)}
    out, _ = clip.update(g, clip.init(g))
    norm = float(O.global_norm(out))
    np.testing.assert_allclose(norm, 1.0, rtol=1e-5)
    # below max: untouched
    g2 = {"a": jnp.full((4,), 0.01), "b": jnp.full((4,), 0.01)}
    out2, _ = clip.update(g2, clip.init(g2))
    np.testing.assert_allclose(np.asarray(out2["a"]), 0.01, rtol=1e-6)


def test_schedules():
    wsd = O.wsd_schedule(1.0, warmup=10, total=100, decay_frac=0.2)
    # first step trains at peak/warmup (not 0 -- a 1-step run must move)
    np.testing.assert_allclose(float(wsd(jnp.asarray(0))), 0.1)
    np.testing.assert_allclose(float(wsd(jnp.asarray(10))), 1.0)
    np.testing.assert_allclose(float(wsd(jnp.asarray(50))), 1.0)
    assert float(wsd(jnp.asarray(99))) < 0.1
    cos = O.cosine_schedule(1.0, warmup=10, total=100)
    np.testing.assert_allclose(float(cos(jnp.asarray(4))), 0.5)
    assert 0.09 < float(cos(jnp.asarray(100))) < 0.11


def test_quantize_roundtrip_error_bound_deterministic():
    # the randomized version lives in test_properties.py (hypothesis)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=777) * 10, jnp.float32)
    q, s = O.quantize_int8(x)
    back = O.dequantize_int8(q, s, x.shape)
    err = np.abs(np.asarray(back) - np.asarray(x))
    maxabs = np.abs(np.asarray(x)).max()
    bound = maxabs * (1 / 254 + 1e-3) + 1e-6
    assert err.max() <= bound


def test_error_feedback_reduces_bias():
    """With EF, the accumulated quantization error stays bounded (doesn't
    grow linearly)."""
    ef_init, ef_apply = O.make_error_feedback()
    # single-device: compressed_psum over a trivial axis via shard_map
    mesh = compat.make_mesh((1,), ("dp",), axis_types=compat.auto_axes(1))
    g = {"w": jnp.full((256,), 0.001, jnp.float32)}  # tiny grads: worst case
    res = ef_init(g)
    total_sent = jnp.zeros((256,))
    import functools
    from jax.sharding import PartitionSpec as P

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def step(gw, rw):
        synced, new_res = ef_apply({"w": gw}, {"w": rw}, "dp")
        return synced["w"], new_res["w"]

    for _ in range(50):
        sent, res_w = step(g["w"], res["w"])
        total_sent = total_sent + sent
        res = {"w": res_w}
    # after 50 steps, total transmitted ~= 50 * g (error bounded, not drift)
    np.testing.assert_allclose(np.asarray(total_sent),
                               50 * 0.001 * np.ones(256), rtol=0.05)
