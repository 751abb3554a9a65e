"""Core transformer layers: norms, RoPE, GQA attention (full / sliding-window,
train / decode), SwiGLU MLP.  Pure-function style: ``init_*`` builds a param
pytree (usable under ``jax.eval_shape`` for allocation-free dry-runs),
``*_fwd`` applies it.

Attention is *chunked with online softmax* (the FlashAttention recurrence in
pure JAX, scanned over KV chunks): scores never materialize beyond
[B, heads, q_chunk, kv_chunk], which is what makes the 32k-prefill and
500k-decode cells compile within HBM.  A Pallas version of the same
recurrence is the natural kernel hot-spot -- see kernels/flash.py.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

PDT = jnp.bfloat16  # parameter/activation dtype

NEG_INF = -1e30


def _split(key, n):
    return jax.random.split(key, n)


# ---------------------------------------------------------------------------
# Norm / rope / softcap
# ---------------------------------------------------------------------------


def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def rope(x, positions, theta=10_000.0):
    """x: [..., S, H, hd]; positions: [..., S] int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x, cap):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = _split(key, 4)
    sc = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, h, hd)) * sc).astype(PDT),
        "wk": (jax.random.normal(ks[1], (d, kv, hd)) * sc).astype(PDT),
        "wv": (jax.random.normal(ks[2], (d, kv, hd)) * sc).astype(PDT),
        "wo": (jax.random.normal(ks[3], (h, hd, d)) * (h * hd) ** -0.5).astype(PDT),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), PDT)
        p["bk"] = jnp.zeros((kv, hd), PDT)
        p["bv"] = jnp.zeros((kv, hd), PDT)
    return p


def _qkv(p, x, positions, cfg):
    from repro.models.sharding import constrain

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "heads", None)
    return q, k, v


def _mask(q_pos, k_pos, causal, window):
    """[..., Sq, Sk] additive mask."""
    m = jnp.zeros((q_pos.shape[-1], k_pos.shape[-1]), jnp.float32)
    d = q_pos[:, None] - k_pos[None, :]
    if causal:
        m = jnp.where(d < 0, NEG_INF, m)
    if window:
        m = jnp.where(d >= window, NEG_INF, m)
    return m


def chunked_attention(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                      logit_softcap=0.0, kv_chunk=1024, q_chunk=1024):
    """Online-softmax attention; q:[B,Sq,H,hd] k,v:[B,Sk,KV,hd] GQA.

    K/V are broadcast to H heads up front (flat-head layout): a grouped
    [KV, G] split cannot be sharded across a model axis larger than KV
    (kimi: KV=8 on model=16 replicated whole score tensors -- 23.8 GB/block
    backward, see EXPERIMENTS.md), whereas flat H=64 shards cleanly.  The
    broadcast itself is free under sharding (each shard repeats only its
    own KV slice).

    Memory high-water: [B, H, q_chunk, kv_chunk] f32 scores per scan step.
    """
    from repro.models.sharding import constrain

    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        k = constrain(k, "batch", None, "heads", None)
        v = constrain(v, "batch", None, "heads", None)
    kv_chunk = min(kv_chunk, Sk)
    q_chunk = min(q_chunk, Sq)
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    scale = hd ** -0.5

    qg = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 3, 2, 4)
    kc = k.reshape(B, nk, kv_chunk, H, hd).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, nk, kv_chunk, H, hd).transpose(1, 0, 3, 2, 4)
    qp = q_pos.reshape(nq, q_chunk)
    kp = k_pos.reshape(nk, kv_chunk)

    # banded local attention: a causal sliding-window layer only needs the
    # kv chunks covering [qpos0 - window + 1, qpos_last] -- visit that band
    # (dynamic start, static size) instead of all nk chunks.  gemma3 at 32k
    # prefill: 2 of 32 chunks per q chunk, a ~16x cut in score-tile compute
    # and traffic for its 25 local layers (see EXPERIMENTS.md section Perf D1).
    nb = nk
    if causal and window and nk > 1:
        nb = min(nk, (window + q_chunk - 2) // kv_chunk + 2)

    # flash-style rematerialization: both loop bodies are checkpointed, so
    # the backward pass recomputes each [qc, c] score tile from (q, k, v)
    # chunks instead of saving nq*nk tiles -- without this, one kimi-size
    # attention block keeps ~20 GB of f32 scores live for backward.
    @partial(jax.checkpoint, prevent_cse=False)
    def q_step(qi_args):
        qi, qpos = qi_args  # [B,H,qc,hd], [qc]

        @partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, kv_args):
            m_run, l_run, acc = carry
            kc_i, vc_i, kpos = kv_args  # [B,H,c,hd]
            s = jnp.einsum("bhqd,bhcd->bhqc", qi, kc_i).astype(jnp.float32)
            s = softcap(s * scale, logit_softcap)
            ok = _mask(qpos, kpos, causal, window) == 0.0  # [qc, c] bool
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            # fully-masked-so-far rows: keep the exp argument finite
            m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
            p = jnp.where(ok, jnp.exp(s - m_safe[..., None]), 0.0)
            corr = jnp.where(m_run <= NEG_INF, 0.0,
                             jnp.exp(m_run - m_safe))
            l_new = l_run * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqc,bhcd->bhqd", p.astype(vc_i.dtype), vc_i
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        if nb < nk:  # banded: slice the needed kv-chunk window
            start = jnp.clip((qpos[0] - window + 1) // kv_chunk, 0, nk - nb)
            kcb = jax.lax.dynamic_slice_in_dim(kc, start, nb, axis=0)
            vcb = jax.lax.dynamic_slice_in_dim(vc, start, nb, axis=0)
            kpb = jax.lax.dynamic_slice_in_dim(kp, start, nb, axis=0)
        else:
            kcb, vcb, kpb = kc, vc, kp
        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kcb, vcb, kpb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)

    out = jax.lax.map(q_step, (qg, qp))  # [nq,B,H,qc,hd]
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hd)
    return out


def attention_fwd(p, x, positions, cfg, mixer):
    """Training / prefill self-attention over the full sequence.

    When the head count does not divide the model axis (qwen 20H, llama4
    40H, paligemma 8H, gemma3 4H on a 16-way axis), head tensor parallelism
    is impossible and GSPMD replicates the whole attention computation 16x.
    In that case the block switches to *ring attention*: the sequence is
    sharded over the model axis and K/V blocks rotate via ppermute -- the
    paper's `pairs` variant (one buffer per ordered pair of chares, hop per
    step) applied at LM scale.  See EXPERIMENTS.md section Perf (qwen cell).
    """
    S = x.shape[1]
    n_model, mesh = _model_axis_size()
    if (n_model > 1 and cfg.num_heads % n_model != 0
            and S % n_model == 0 and S // n_model >= 128):
        return ring_attention_block(p, x, cfg, mixer, mesh, n_model), None
    q, k, v = _qkv(p, x, positions, cfg)
    out = chunked_attention(
        q, k, v, positions, positions,
        causal=not cfg.encoder_only,
        window=cfg.window if mixer == "local" else 0,
        logit_softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k, v)


def _model_axis_size():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1, None
    sizes = dict(mesh.shape)
    return sizes.get("model", 1), mesh


def ring_attention_block(p, x, cfg, mixer, mesh, n_model):
    """Whole attention block under sequence sharding: QKV projection, ring
    flash attention, and the output projection all run on S/n_model rows per
    shard; only the K/V blocks (and the block's output at the boundary) move.

    Wire bytes/device: (P-1) * |K+V block| per layer -- identical to the
    paper's pairs/ring analysis in core/strategies.py.
    """
    from jax.sharding import PartitionSpec as Pspec

    causal = not cfg.encoder_only
    window = cfg.window if mixer == "local" else 0
    cap = cfg.attn_logit_softcap
    sizes = dict(mesh.shape)
    batch_axes = tuple(n for n in ("pod", "data") if n in sizes)
    n_data = 1
    for n in batch_axes:
        n_data *= sizes[n]
    if batch_axes and x.shape[0] % n_data != 0:
        batch_axes = ()
    bspec = (batch_axes if len(batch_axes) > 1 else
             (batch_axes[0] if batch_axes else None))
    x_spec = Pspec(bspec, "model", None)
    w_specs = jax.tree.map(lambda _: Pspec(), p)

    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    G = H // KV
    Pn = n_model

    @partial(jax.shard_map, mesh=mesh, in_specs=(w_specs, x_spec),
             out_specs=x_spec, check_vma=False)
    def body(pp, x_loc):
        B, c, d = x_loc.shape
        me = jax.lax.axis_index("model")
        qpos = me * c + jnp.arange(c, dtype=jnp.int32)
        q, k, v = _qkv(pp, x_loc, qpos, cfg)  # [B,c,H,hd], [B,c,KV,hd]
        scale = hd ** -0.5
        qf = q.transpose(0, 2, 1, 3)  # [B,H,c,hd]

        perm = [(s, (s + 1) % Pn) for s in range(Pn)]

        @partial(jax.checkpoint, prevent_cse=False)
        def hop_update(carry_m, carry_l, carry_acc, k_blk, v_blk, kpos):
            kh = jnp.repeat(k_blk, G, axis=2).transpose(0, 2, 1, 3)
            vh = jnp.repeat(v_blk, G, axis=2).transpose(0, 2, 1, 3)
            s = jnp.einsum("bhqd,bhcd->bhqc", qf, kh).astype(jnp.float32)
            s = softcap(s * scale, cap)
            ok = _mask(qpos, kpos, causal, window) == 0.0
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(carry_m, s.max(axis=-1))
            m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
            pmat = jnp.where(ok, jnp.exp(s - m_safe[..., None]), 0.0)
            corr = jnp.where(carry_m <= NEG_INF, 0.0,
                             jnp.exp(carry_m - m_safe))
            l_new = carry_l * corr + pmat.sum(axis=-1)
            acc = carry_acc * corr[..., None] + jnp.einsum(
                "bhqc,bhcd->bhqd", pmat.astype(vh.dtype), vh
            ).astype(jnp.float32)
            return m_new, l_new, acc

        def hop(t, carry):
            m, l, acc, k_blk, v_blk, kpos = carry
            m, l, acc = hop_update(m, l, acc, k_blk, v_blk, kpos)
            # rotate the K/V block around the ring (paper's pairs variant)
            k_blk = jax.lax.ppermute(k_blk, "model", perm)
            v_blk = jax.lax.ppermute(v_blk, "model", perm)
            kpos = jax.lax.ppermute(kpos, "model", perm)
            return m, l, acc, k_blk, v_blk, kpos

        m0 = jnp.full((B, H, c), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, c), jnp.float32)
        a0 = jnp.zeros((B, H, c, hd), jnp.float32)
        m, l, acc, *_ = jax.lax.fori_loop(0, Pn, hop,
                                          (m0, l0, a0, k, v, qpos))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(x_loc.dtype)
        out = out.transpose(0, 2, 1, 3)  # [B,c,H,hd]
        return jnp.einsum("bshk,hkd->bsd", out, pp["wo"])

    return body(p, x)


def attention_decode(p, x, pos, cache_k, cache_v, cfg, mixer):
    """One-token decode against a [B, W, KV, hd] cache; returns out, new cache.

    The cache is a *ring buffer*: the new K/V land in slot ``pos % W``.  When
    W >= pos+1 this degenerates exactly to a plain full cache (slot == pos,
    reconstructed position == slot), so one code path serves both full-cache
    decode and sliding-window decode with W == cfg.window.

    The cache is stored seq-sharded (see sharding.py); the softmax reductions
    over the sharded S axis become the flash-decode LSE-combine collectives
    under GSPMD.
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k1, v1 = _qkv(p, x, positions, cfg)  # [B,1,H,hd], [B,1,KV,hd]
    W = cache_k.shape[1]
    slot = pos % W
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k1.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v1.astype(cache_v.dtype), slot, axis=1)
    KV, H, hd = cache_k.shape[2], q.shape[2], q.shape[3]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, cache_k).astype(jnp.float32)
    s = softcap(s * hd ** -0.5, cfg.attn_logit_softcap)
    # position actually held by ring slot j (== j for a full cache)
    j = jnp.arange(W)
    kpos = pos - (pos - j) % W
    valid = kpos >= 0
    if mixer == "local" and cfg.window:
        valid &= kpos > pos - cfg.window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", w, cache_v).reshape(B, 1, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (cache_k, cache_v)


# ---------------------------------------------------------------------------
# MLP / embedding
# ---------------------------------------------------------------------------


def init_mlp(key, d, ff):
    ks = _split(key, 3)
    return {
        "w_gate": (jax.random.normal(ks[0], (d, ff)) * d ** -0.5).astype(PDT),
        "w_in": (jax.random.normal(ks[1], (d, ff)) * d ** -0.5).astype(PDT),
        "w_out": (jax.random.normal(ks[2], (ff, d)) * ff ** -0.5).astype(PDT),
    }


def mlp_fwd(p, x):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
    h = h * jnp.einsum("bsd,df->bsf", x, p["w_in"])
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"])


def init_embedding(key, vocab, d):
    return {"table": (jax.random.normal(key, (vocab, d))).astype(PDT)}


def embed(p, tokens, d):
    return p["table"][tokens] * jnp.asarray(d ** 0.5, PDT)


def logits_fwd(p, x, final_cap=0.0):
    out = jnp.einsum("bsd,vd->bsv", x, p["table"]).astype(jnp.float32)
    return softcap(out, final_cap)
