"""Mixture-of-Experts with sort-by-destination dispatch (the paper's
technique at LM scale -- see DESIGN.md section 2).

Tokens are *messages*, experts are *chares*.  Routing slots are ranked by
destination expert (the paper's sort-destination edge layout) so each
expert's payload is one contiguous capacity buffer -- Listing 2's
``outgoing[CHUNKINDEX(dest)]`` -- and each expert shard locally combines its
outputs into a partial token buffer before ONE ``psum`` over the model axis
puts the reduced result on the wire (combine-locally-then-send).

Execution strategy (chosen for 1000-chip memory sanity, see the kimi-k2
dry-run log in EXPERIMENTS.md):
  * experts are sharded over the "model" mesh axis; activations enter the
    layer replicated across "model" (batch-sharded only), so *no token
    all_to_all is needed at all*: each shard routes every local token, but
    builds capacity buffers ONLY for its own E/num_shards experts, runs its
    expert FFNs, and locally combines into a [T, d] partial that a single
    psum reduces across shards.
  * dispatch/combine never materialize [T*k, d]: slot->capacity positions
    are computed with integer sorts only, and the actual row movement is
    chunked over tokens with lax.scan (TOKEN_CHUNK rows at a time).

``moe_fwd`` picks the shard_map path whenever the surrounding mesh has a
model axis > 1; ``moe_fwd_dense`` is the small/oracle path (identical math,
global capacity) used on single devices and as the test reference.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import PDT, _split
from repro.models.sharding import constrain

TOKEN_CHUNK = 2048  # dispatch/combine rows moved per scan step


def init_moe(key, cfg):
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.expert_ff
    ks = _split(key, 4)
    return {
        "router": (jax.random.normal(ks[0], (d, E)) * d ** -0.5).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, ff)) * d ** -0.5).astype(PDT),
        "w_in": (jax.random.normal(ks[2], (E, d, ff)) * d ** -0.5).astype(PDT),
        "w_out": (jax.random.normal(ks[3], (E, ff, d)) * ff ** -0.5).astype(PDT),
    }


def capacity(tokens: int, cfg) -> int:
    c = int(tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor) + 1
    return max(c, cfg.top_k)


def _route(xt, router, cfg):
    """-> (top_vals [T,k] normalized, top_idx [T,k], gates [T,E] f32)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    gates = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, cfg.top_k)
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    return top_vals, top_idx, gates


def _aux_loss(gates, top_idx, cfg):
    """Switch-style load-balance loss from the (replicated) routing."""
    T = gates.shape[0]
    E, k = cfg.num_experts, cfg.top_k
    me = gates.mean(0)
    ce = jax.ops.segment_sum(
        jnp.ones((T * k,), jnp.float32), top_idx.reshape(-1),
        num_segments=E) / (T * k)
    return E * jnp.sum(me * ce)


def _slot_positions(e_ids, num_buckets):
    """Rank of each slot within its bucket (sort-destination, ints only).

    e_ids: [N] bucket id per slot (num_buckets = dummy bucket for drops).
    Returns pos [N]: 0-based arrival index of the slot in its bucket.
    """
    n = e_ids.shape[0]
    order = jnp.argsort(e_ids, stable=True)          # paper's edge sort
    sorted_e = e_ids[order]
    counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e,
                                 num_segments=num_buckets + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - \
        starts[sorted_e].astype(jnp.int32)
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    return pos


def _moe_local(xt, p, cfg, e_local, n_local: int, C: int, top_vals):
    """Expert compute + local combine for one shard.

    xt [T, d]: every local token; e_local [T*k]: slot -> local expert id in
    [0, n_local) or n_local for foreign/dummy; top_vals [T, k] gate weights.
    Returns the shard's locally-combined partial [T, d] f32 (zeros where no
    local expert contributed).

    Dispatch/combine iterate over the k routing slots (k scatters + k
    gathers of [T, d]) rather than materializing [T*k, d] or scanning token
    chunks -- both of which blow the backward high-water mark (scan carries
    the capacity table per chunk; see EXPERIMENTS.md kimi-k2 log).
    """
    T, d = xt.shape
    k = cfg.top_k
    pos = _slot_positions(e_local, n_local)
    keep = (e_local < n_local) & (pos < C)
    # flat row index into the [(n_local * C) + 1] capacity table; last = dummy
    flat_idx = jnp.where(keep, e_local * C + pos, n_local * C).astype(jnp.int32)
    idx2 = flat_idx.reshape(T, k)

    # ---- dispatch: one scatter per routing slot ---------------------------
    xe = jnp.zeros((n_local * C + 1, d), xt.dtype)
    for j in range(k):
        xe = xe.at[idx2[:, j]].set(xt)  # duplicate dummy rows: last wins
    xe = xe[:-1].reshape(n_local, C, d)

    # ---- expert FFN --------------------------------------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_out"])
    y = jnp.concatenate([y.reshape(n_local * C, d),
                         jnp.zeros((1, d), y.dtype)], axis=0)

    # ---- combine: gather + weighted accumulate per slot --------------------
    # checkpointed (backward re-gathers rows) and kept in the activation
    # dtype: an f32 combine drags the whole backward chain -- weight grads,
    # dispatch scatters, psum -- to f32, doubling every big MoE buffer
    # (46 GB -> 9 GB per block at kimi dims, see EXPERIMENTS.md).  Each
    # capacity slot receives exactly one contribution and a token sums only
    # k slot outputs, so bf16 accumulation is benign.
    w = top_vals * keep.reshape(T, k).astype(top_vals.dtype)  # [T, k]

    @partial(jax.checkpoint, prevent_cse=False)
    def _combine(y, w, idx2):
        wl = w.astype(y.dtype)
        out = jnp.zeros((T, d), y.dtype)
        for j in range(k):
            out = out + wl[:, j, None] * y[idx2[:, j]]
        return out

    return _combine(y, w, idx2)


def moe_fwd_dense(p, x, cfg):
    """Single-shard reference: all experts local, global capacity."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    C = capacity(T, cfg)
    top_vals, top_idx, gates = _route(xt, p["router"], cfg)
    e_flat = top_idx.reshape(-1).astype(jnp.int32)
    out = _moe_local(xt, p, cfg, e_flat, cfg.num_experts, C, top_vals)
    return out.reshape(B, S, d).astype(x.dtype), _aux_loss(gates, top_idx, cfg)


def _model_axis_size():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1, None
    sizes = dict(mesh.shape)
    return sizes.get("model", 1), mesh


def moe_fwd(p, x, cfg):
    """x: [B, S, d] -> ([B, S, d], aux).  Expert-parallel over "model"."""
    n_model, mesh = _model_axis_size()
    if n_model <= 1 or cfg.num_experts % n_model != 0:
        return moe_fwd_dense(p, x, cfg)

    from jax.sharding import PartitionSpec as P

    E = cfg.num_experts
    n_local = E // n_model
    B, S, d = x.shape
    T = B * S

    sizes = dict(mesh.shape)
    batch_axes = tuple(n for n in ("pod", "data") if n in sizes)
    n_data = 1
    for n in batch_axes:
        n_data *= sizes[n]
    if batch_axes and B % n_data != 0:  # replicated batch (e.g. B=1 cells)
        batch_axes, n_data = (), 1
    # per-shard capacity over the shard's local tokens (global budget / dp)
    C = capacity(T // n_data, cfg)
    bspec = (batch_axes if len(batch_axes) > 1 else
             (batch_axes[0] if batch_axes else None))
    x_spec = P(bspec, None, None)
    w_specs = {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_in": P("model", None, None),
        "w_out": P("model", None, None),
    }

    @partial(jax.shard_map, mesh=mesh, in_specs=(w_specs, x_spec),
             out_specs=(x_spec, P()), check_vma=False)
    def sharded(pp, x_loc):
        Bl, Sl, dl = x_loc.shape
        Tl = Bl * Sl
        xt = x_loc.reshape(Tl, dl)
        top_vals, top_idx, gates = _route(xt, pp["router"], cfg)
        shard = jax.lax.axis_index("model")
        e_local = top_idx.reshape(-1).astype(jnp.int32) - shard * n_local
        e_local = jnp.where((e_local >= 0) & (e_local < n_local),
                            e_local, n_local)
        partial_out = _moe_local(xt, pp, cfg, e_local, n_local, C, top_vals)
        # the paper's sortdest move: combine locally, put only the reduced
        # [T, d] partial on the wire (bf16: half the bytes of an f32 psum)
        out = jax.lax.psum(partial_out, "model")
        out = out.astype(x_loc.dtype)
        aux = _aux_loss(gates, top_idx, cfg)
        if batch_axes:  # routing differs per data shard -> average
            aux = jax.lax.pmean(aux, batch_axes)
        return out.reshape(Bl, Sl, dl).astype(x_loc.dtype), aux

    weights = {k: p[k] for k in ("router", "w_gate", "w_in", "w_out")}
    return sharded(weights, x)
