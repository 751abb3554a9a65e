"""Jitted wrappers for the push kernels: padding, dispatch, engine hooks.

Two kernel paths serve the paper's per-chare hot loop:

  * ``fused``  (default) -- one band-pruned ``pallas_call`` per superstep
    (``repro.kernels.push_fused``): gather, edge-value transform, and segment
    combine in one VMEM-resident pass, tile work pruned to the layout's
    (source block, segment block) bands.
  * ``staged`` -- the legacy two-kernel dense grid (``push_sum``/``push_min``)
    with the ``[E]`` intermediate between the halves; kept as the reference
    for the fused-vs-staged comparisons in ``benchmarks.kernelbench``.

``interpret=None`` (every default here) resolves when the kernel is traced:
Mosaic on a TPU backend, the Pallas interpreter elsewhere (it executes the
kernel body faithfully, as an emulation).  Pass a bool to force either.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import push_fused as fused_mod
from repro.kernels import push_min, push_sum
from repro.kernels.blocks import BLOCK_E, BLOCK_S, BLOCK_V


def _interpret(interpret) -> bool:
    """``interpret=None`` -> the Pallas interpreter unless JAX's default
    backend is a TPU.  Asked when the caller traces, never at import."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _pad_to(x, mult, fill):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])


def _sat_add(c, w):
    """Saturating add for the min semiring: never wraps past the sentinel.

    Ints clamp the (non-negative) weight to the headroom below SENTINEL --
    computed in int32 since x64 may be disabled; floats ride on inf
    arithmetic (>= SENTINEL is "unreached" either way).
    """
    w = w.astype(c.dtype)
    if jnp.issubdtype(c.dtype, jnp.floating):
        return c + w
    return c + jnp.minimum(w, push_min.SENTINEL - c)


def _min_restore_identity(out):
    """Map sentinel-range float results back to +inf (the FMIN identity).

    The min kernels fill empty/masked lanes with the int32 sentinel, which a
    float buffer stores as ~2.15e9; callers expect unreached == +inf."""
    if jnp.issubdtype(out.dtype, jnp.floating):
        return jnp.where(out >= push_min.SENTINEL, jnp.inf, out)
    return out


def _bands_on_device(src, dst, valid, num_blocks):
    """jnp twin of ``blocks.edge_bands`` for standalone (layout-less) calls:
    per-edge-block (src_lo, src_hi, seg_lo, seg_hi) over valid edges only."""
    eb = jnp.arange(src.shape[0], dtype=jnp.int32) // BLOCK_E
    live = valid != 0
    big = jnp.int32(jnp.iinfo(jnp.int32).max)

    def lo(blk):
        x = jax.ops.segment_min(jnp.where(live, blk, big), eb,
                                num_segments=num_blocks)
        return jnp.where(x == big, 0, x)  # empty block -> (0, -1)

    def hi(blk):
        return jax.ops.segment_max(jnp.where(live, blk, -1), eb,
                                   num_segments=num_blocks)

    sb, db = src // BLOCK_V, dst // BLOCK_S
    return jnp.stack([lo(sb), hi(sb), lo(db), hi(db)]).astype(jnp.int32)


def _push_staged(vals_p, src_p, dst_p, valid_p, weight, nseg_p, combine,
                 interpret):
    """Legacy two-kernel dense-grid path (3 jitted stages, [E] intermediate)."""
    if combine == "add":
        c = push_sum.gather_sum(src_p, valid_p, vals_p, interpret=interpret)
        if weight is not None:
            c = c * _pad_to(weight, BLOCK_E, 1).astype(c.dtype)
        return push_sum.scatter_sum(dst_p, c, nseg_p, interpret=interpret)
    c = push_min.gather_min(src_p, valid_p, vals_p, interpret=interpret)
    if weight is not None:
        c = _sat_add(c, _pad_to(weight, BLOCK_E, 0))
    return push_min.scatter_min(dst_p, c, nseg_p, interpret=interpret)


@partial(jax.jit, static_argnames=("num_segments", "combine", "interpret",
                                   "fused", "unit_weight"))
def push(vals, src, dst, valid, num_segments, combine="add", weight=None,
         band=None, interpret=None, fused=True, unit_weight=False,
         init=None):
    """out[s] = combine_{e: dst[e]==s, valid[e]==1} edge_value(vals[src[e]]).

    The paper's per-chare hot loop; arbitrary (unpadded) shapes accepted.
    ``vals`` may be ``[V]`` or a batched ``[V, B]`` query plane -- the fused
    kernel streams the edge layout once for all B columns; the staged pair
    (1-D kernels) falls back to a static per-column loop.
    ``weight`` (optional, per-edge) applies the semiring edge transform
    between the gather and scatter halves: ``c * w`` for the add monoid,
    saturating ``c + w`` for min -- the same ``edge_value`` hook the dense
    strategies expose (see repro.core.programs).  Float min treats values
    at/above the int32 sentinel as unreached and returns them as +inf.

    ``fused`` selects the one-launch band-pruned kernel (default); ``band``
    is the precomputed ``[4, E/BLOCK_E]`` metadata from the partition-time
    layout build (``blocks.edge_bands``), derived on the fly when absent.
    ``unit_weight`` applies the semiring transform with a constant 1 and no
    streamed weight operand (BFS hop counts).  ``fused=False`` runs the
    legacy staged pair.

    ``init`` (optional, ``[num_segments(, B)]``) seeds the accumulator with
    a prior partial instead of the combiner identity: chaining edge-slice
    calls through it equals one call over all the edges (exactly for min,
    up to float association for add) -- the streamed window schedule's
    buffer-recycling contract (DESIGN.md section 13).
    """
    interpret = _interpret(interpret)
    identity = 0 if combine == "add" else push_min.SENTINEL
    vals_p = _pad_to(vals, BLOCK_V, identity)
    src_p = _pad_to(src, BLOCK_E, 0)
    dst_p = _pad_to(dst, BLOCK_E, 0)
    valid_p = _pad_to(valid, BLOCK_E, 0)
    nseg_p = num_segments + ((-num_segments) % BLOCK_S)
    if combine == "min" and jnp.issubdtype(vals_p.dtype, jnp.floating):
        # inf -> sentinel so the kernel's int-sentinel fills and masks compare
        # consistently; restored to inf on the way out
        vals_p = jnp.minimum(vals_p, push_min.SENTINEL)

    def _prep_init(out_dtype):
        ip = _pad_to(init, BLOCK_S, identity).astype(out_dtype)
        if combine == "min" and jnp.issubdtype(out_dtype, jnp.floating):
            return jnp.minimum(ip, push_min.SENTINEL)  # +inf -> sentinel
        return ip

    if fused:
        if band is None:
            band = _bands_on_device(src_p, dst_p, valid_p,
                                    src_p.shape[0] // BLOCK_E)
        w_p = None if weight is None else _pad_to(
            weight, BLOCK_E, 1 if combine == "add" else 0)
        init_p = None
        if init is not None:
            if combine == "add":
                od = vals_p.dtype if jnp.issubdtype(vals_p.dtype,
                                                    jnp.integer) \
                    else jnp.promote_types(vals_p.dtype, jnp.float32)
            else:
                od = vals_p.dtype
            init_p = _prep_init(od)
        out = fused_mod.fused_push(band, src_p, dst_p, valid_p, w_p, vals_p,
                                   nseg_p, combine=combine,
                                   unit_weight=unit_weight, init=init_p,
                                   interpret=interpret)
    else:
        if unit_weight and weight is None and combine == "min":
            weight = jnp.ones_like(valid)  # staged path streams the ones
        if vals_p.ndim == 2:  # staged kernels are 1-D: static column loop
            out = jnp.stack(
                [_push_staged(vals_p[:, b], src_p, dst_p, valid_p, weight,
                              nseg_p, combine, interpret)
                 for b in range(vals_p.shape[1])], axis=-1)
        else:
            out = _push_staged(vals_p, src_p, dst_p, valid_p, weight, nseg_p,
                               combine, interpret)
        if init is not None:  # staged kernels have no seed operand: fold
            ip = _prep_init(out.dtype)
            out = out + ip if combine == "add" else jnp.minimum(out, ip)
    out = out[:num_segments]
    if combine == "add":
        return out.astype(vals.dtype)
    return _min_restore_identity(out)


@partial(jax.jit, static_argnames=("num_segments", "combine", "interpret"))
def segment_reduce(data, seg_ids, num_segments, combine="add",
                   interpret=None):
    """Scatter half only (data already gathered): engine's segment hook.

    Integer add data accumulates in its own integer dtype -- the seed cast
    everything to float32, which silently rounds int sums above 2^24.

    ``data`` may carry a trailing batch axis ([E, B] with shared [E] ids);
    the 1-D kernels run per column.
    """
    if data.ndim == 2:
        return jnp.stack(
            [segment_reduce(data[:, b], seg_ids, num_segments,
                            combine=combine, interpret=interpret)
             for b in range(data.shape[1])], axis=-1)
    interpret = _interpret(interpret)
    identity = 0 if combine == "add" else push_min.SENTINEL
    data_p = _pad_to(data, BLOCK_E, identity)
    seg_p = _pad_to(seg_ids, BLOCK_E, 0)
    nseg_p = num_segments + ((-num_segments) % BLOCK_S)
    if combine == "add":
        out = push_sum.scatter_sum(seg_p, data_p, nseg_p, interpret=interpret)
        return out[:num_segments].astype(data.dtype)
    if jnp.issubdtype(data_p.dtype, jnp.floating):
        data_p = jnp.minimum(data_p, push_min.SENTINEL)  # +inf -> sentinel
    out = push_min.scatter_min(seg_p, data_p, nseg_p, interpret=interpret)
    return _min_restore_identity(out[:num_segments])


def make_segment_fn(interpret=None, combine=None):
    """Adapter for ``Engine(segment_fn=...)``: routes the local combines of
    any strategy through the Pallas kernels (the paper's 'atomic'-style
    shared-buffer update, done TPU-natively).

    The strategies pass the active program's monoid via the ``combine``
    keyword (the segment_fn contract), so one hook serves PageRank (add),
    labelprop (int min), and SSSP (float min) alike.  The ``combine``
    constructor arg forces a fixed monoid; otherwise a call without the
    keyword falls back to dtype inference (float -> add, int -> min).
    """

    def fn(data, seg_ids, num_segments, combine=combine):
        if combine is None:
            combine = ("add" if jnp.issubdtype(data.dtype, jnp.floating)
                       else "min")
        return segment_reduce(data, seg_ids, num_segments, combine=combine,
                              interpret=interpret)

    return fn


def make_push_fn(interpret=None, fused=True):
    """Adapter for ``Engine(push_fn=...)``: the whole per-chare hot loop --
    gather, edge-value transform, segment combine -- as one fused Pallas
    launch, fed by the layout's partition-time band metadata.

    Contract (see ``strategies._dense_contrib``): strategies call

        push_fn(vals, src_local, dst, valid, weight, num_segments,
                combine=..., band=...)

    with ``weight=None`` when the program has no edge transform.  The hook
    implements the canonical semiring transforms (weight multiply for add,
    saturating add for min); which weights go in is decided by the
    program's declared ``edge_semiring`` ("weight" / "unit" / None), and a
    program whose ``edge_value`` is not declared kernel-expressible runs
    the staged path instead -- the hook never substitutes a different
    transform.
    """

    def fn(vals, src, dst, valid, weight, num_segments, combine, band=None,
           unit=False, init=None):
        return push(vals, src, dst, valid, num_segments, combine=combine,
                    weight=weight, band=band, interpret=interpret,
                    fused=fused, unit_weight=unit, init=init)

    return fn
