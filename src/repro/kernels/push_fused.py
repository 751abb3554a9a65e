"""Fused, band-pruned Pallas push kernels: one pass per superstep.

The staged path (``push_sum``/``push_min``) runs the paper's hot loop as two
dense-grid kernels with an ``[E]`` intermediate making a full HBM round trip
between them, and visits every (edge-block x vertex-block) and
(segment-block x edge-block) tile -- O(E*V/B^2) tile work for O(E) useful
work.  These kernels fuse the whole semiring push

    out[s] = combine_{e: dst[e]==s, valid[e]} edge_value(vals[src[e]], w[e])

into ONE ``pallas_call``: per edge block the one-hot gather, the edge-value
transform (weight multiply for add, saturating add for min), and the
segment combine all happen in VMEM; the per-edge contribution never touches
HBM.

Band pruning (DESIGN.md section 8): the grid is 1-D over groups of
``EDGE_ROWS`` edge blocks; the ``vals`` and ``out`` planes stay resident in
VMEM across the whole sweep (their BlockSpecs map every grid step to block
0).  Scalar-prefetched band metadata (``repro.kernels.blocks.edge_bands``)
gives each edge block the inclusive range of source vertex blocks and
destination segment blocks its valid edges touch, and two ``fori_loop``s
visit only those tiles.  Because the layouts are sorted by (destination
segment block, source vertex block) the bands are a few blocks wide, so tile
work drops from O((E/BE)*(V/BV) + (S/BS)*(E/BE)) to O(sum of band widths)
(``benchmarks.kernelbench.layout_cost_model``).

Layout for Mosaic: every operand is 2-D or 3-D with a 256-lane minor axis.
Edges arrive as ``[NB, BLOCK_E]`` rows, one edge block per row, moved
``EDGE_ROWS`` rows per grid step; the resident planes are ``[V/256, 256]``
(1-D) or ``[V/256, B, 256]`` (batched: one sublane row per query), so a
vertex block is one aligned row (or one ``[B, 256]`` tile) and no 1-D
dynamic slice is ever taken.

Two bodies share that layout:

  * float add -- the MXU one-hot matmul.  With edges on lanes, the gather is
    ``[B, BLOCK_V] @ hit[BLOCK_V, BLOCK_E]`` and the scatter, per query
    row, the transposed contraction against ``hit[BLOCK_S, BLOCK_E]``; one
    edge fetch serves the B query rows of the batched plane.
  * min, and integer add -- VPU mask-and-reduce.  The edge rows are
    transposed once per grid step so each edge block is a column; the
    gather reduces ``where(hit[BLOCK_E, BLOCK_V], vals_row, identity)``
    across lanes and the scatter reduces ``hit[BLOCK_E, BLOCK_S]`` down
    sublanes into an output row, one query row at a time.

The planes' VMEM and the band table's SMEM are what bounds the sizes this
kernel accepts; ``blocks.fused_footprint`` counts them from the shapes and
the engine's dispatch declines the kernel where they do not fit.  The same
code runs through the Pallas interpreter (``interpret=True``) off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blocks
from repro.kernels.blocks import BLOCK_E, BLOCK_S, BLOCK_V, EDGE_ROWS

SENTINEL = jnp.iinfo(jnp.int32).max


def _plane_row(ref, b, q):
    """Vertex block ``b`` of query row ``q`` of a resident plane: [1, 256]."""
    if ref.ndim == 3:
        return ref[b, pl.ds(q, 1), :]
    return ref[pl.ds(b, 1), :]


def _set_plane_row(ref, b, q, x):
    if ref.ndim == 3:
        ref[b, pl.ds(q, 1), :] = x
    else:
        ref[pl.ds(b, 1), :] = x


def _init_out(out_ref, init_ref, identity):
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = (jnp.full(out_ref.shape, identity, out_ref.dtype)
                        if init_ref is None else init_ref[...])


def _mxu_add_kernel(band_ref, src_ref, dst_ref, valid_ref, w_ref, vals_ref,
                    init_ref, out_ref):
    """Float add: one-hot gather and scatter on the MXU, edges on lanes.

    ``c[q, e]`` (one row per query) accumulates ``vals[q, v] * hit[v, e]``
    over the in-band source blocks; the scatter contracts it against
    ``hit[s, e]`` on the edge axis.  Invalid edges are masked out of the
    gather, so their (zero) contribution needs no second mask.
    ``init_ref`` (optional) seeds the accumulator instead of zeros -- the
    streamed window schedule chains sweeps through one recycled buffer.
    """
    _init_out(out_ref, init_ref, 0)
    g = pl.program_id(0)
    nq = out_ref.shape[1] if out_ref.ndim == 3 else 1
    acc = out_ref.dtype
    hi = jax.lax.Precision.HIGHEST
    sub_v = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_V, BLOCK_E), 0)
    sub_s = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_S, BLOCK_E), 0)

    def block(vb):
        return vals_ref[vb] if vals_ref.ndim == 3 else \
            vals_ref[pl.ds(vb, 1), :]

    for r in range(EDGE_ROWS):
        e = g * EDGE_ROWS + r
        src = src_ref[r:r + 1, :]  # [1, BLOCK_E]
        valid = valid_ref[r:r + 1, :] != 0

        def gather(b, c, src=src, valid=valid):
            hit = (sub_v + b * BLOCK_V == src) & valid  # [BLOCK_V, BLOCK_E]
            return c + jnp.dot(block(b).astype(acc), hit.astype(acc),
                               precision=hi, preferred_element_type=acc)

        c = jax.lax.fori_loop(band_ref[0, e], band_ref[1, e] + 1, gather,
                              jnp.zeros((nq, BLOCK_E), acc))
        if w_ref is not None:
            c = c * w_ref[r:r + 1, :].astype(acc)
        dst = dst_ref[r:r + 1, :]

        def scatter(b, carry, c=c, dst=dst):
            hit = (sub_s + b * BLOCK_S == dst).astype(acc)

            def part(row):  # [1, BLOCK_E] x hit[BLOCK_S, BLOCK_E]^T
                return jax.lax.dot_general(
                    row, hit, (((1,), (1,)), ((), ())), precision=hi,
                    preferred_element_type=acc)

            if out_ref.ndim == 2:
                out_ref[pl.ds(b, 1), :] += part(c)
            else:
                # one contraction per query row: each column sums its edges
                # in exactly the order of a 1-D sweep (the gather above is
                # exact at any width -- one-hot rows)
                for q in range(nq):
                    out_ref[b, q:q + 1, :] += part(c[q:q + 1, :])
            return carry

        jax.lax.fori_loop(band_ref[2, e], band_ref[3, e] + 1, scatter, 0)


def _vpu_kernel(band_ref, src_ref, dst_ref, valid_ref, w_ref, vals_ref,
                init_ref, out_ref, *, combine, weight_mode):
    """Min (and integer add): VPU mask-and-reduce, one edge block per column.

    ``weight_mode`` "array" applies the semiring transform with the
    streamed per-edge weights: a saturating ``c + w`` for min that never
    wraps past the int32 sentinel (float values ride on plain addition --
    anything at/above the sentinel is "unreached" and the caller maps it
    back to +inf), ``c * w`` for add.  "unit" is the min transform with a
    compile-time constant 1 (BFS hop counts): no per-edge weight operand is
    streamed from HBM at all.  ``init_ref`` (optional) seeds the
    accumulator instead of the identity (chained window sweeps).
    """
    is_min = combine == "min"
    dt = out_ref.dtype
    ident = jnp.asarray(SENTINEL if is_min else 0, dt)
    op = jnp.minimum if is_min else jnp.add
    reduce = jnp.min if is_min else jnp.sum
    _init_out(out_ref, init_ref, ident)
    g = pl.program_id(0)
    nq = out_ref.shape[1] if out_ref.ndim == 3 else 1
    lane_v = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK_V), 1)
    lane_s = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK_S), 1)
    lane_q = jax.lax.broadcasted_iota(jnp.int32, (1, nq), 1)
    # this step's EDGE_ROWS edge blocks as columns: [BLOCK_E, EDGE_ROWS]
    srcs, dsts, valids = src_ref[...].T, dst_ref[...].T, valid_ref[...].T
    ws = w_ref[...].T if weight_mode == "array" else None

    def query(fn, carry):
        """Run ``fn(q, carry)`` for every query row of the plane."""
        if nq == 1:
            return fn(0, carry)
        return jax.lax.fori_loop(0, nq, fn, carry)

    for r in range(EDGE_ROWS):
        e = g * EDGE_ROWS + r
        src = srcs[:, r:r + 1]  # [BLOCK_E, 1]
        valid = valids[:, r:r + 1] != 0

        def gather(b, c, src=src, valid=valid):
            hit = (src == lane_v + b * BLOCK_V) & valid  # [BLOCK_E, BLOCK_V]

            def one(q, c):
                got = reduce(jnp.where(hit, _plane_row(vals_ref, b, q)
                                       .astype(dt), ident),
                             axis=1, keepdims=True)  # [BLOCK_E, 1]
                return jnp.where(lane_q == q, op(c, got), c)

            return query(one, c)

        c = jax.lax.fori_loop(band_ref[0, e], band_ref[1, e] + 1, gather,
                              jnp.full((BLOCK_E, nq), ident, dt))
        if weight_mode != "none":
            w = (jnp.ones((BLOCK_E, 1), dt) if weight_mode == "unit"
                 else ws[:, r:r + 1].astype(dt))
            if not is_min:
                c = c * w
            elif jnp.issubdtype(dt, jnp.floating):
                c = c + w
            else:
                c = c + jnp.minimum(w, SENTINEL - c)  # saturate, never wrap
        dst = dsts[:, r:r + 1]

        def scatter(b, carry, c=c, dst=dst, valid=valid):
            hit = (dst == lane_s + b * BLOCK_S) & valid  # [BLOCK_E, BLOCK_S]

            def one(q, carry):
                cq = c if nq == 1 else reduce(
                    jnp.where(lane_q == q, c, ident), axis=1, keepdims=True)
                got = reduce(jnp.where(hit, cq, ident), axis=0,
                             keepdims=True)  # [1, BLOCK_S]
                _set_plane_row(out_ref, b, q,
                               op(_plane_row(out_ref, b, q), got))
                return carry

            return query(one, carry)

        jax.lax.fori_loop(band_ref[2, e], band_ref[3, e] + 1, scatter, 0)


def _to_plane(x, batched):
    """[n] -> [n/256, 256]; [n, B] -> [n/256, B, 256] (queries on sublanes)."""
    n = x.shape[0]
    if not batched:
        return x.reshape(n // BLOCK_V, BLOCK_V)
    return x.reshape(n // BLOCK_V, BLOCK_V, x.shape[1]).transpose(0, 2, 1)


def _from_plane(p, batched):
    if not batched:
        return p.reshape(-1)
    return p.transpose(0, 2, 1).reshape(-1, p.shape[1])


def fused_push(band, src, dst, valid, weight, vals, num_segments, *,
               combine="add", unit_weight=False, init=None, interpret=True):
    """One-launch fused push over pre-padded inputs.

    Shapes: edges padded to BLOCK_E (``band`` is [4, E/BLOCK_E] int32 from
    ``blocks.edge_bands``), ``vals`` padded to BLOCK_V, ``num_segments`` a
    BLOCK_S multiple.  ``vals`` may carry a trailing batch axis ([V, B]),
    in which case ``out`` is [num_segments, B] and the edge stream and band
    pruning are shared across the B query columns.  ``weight=None`` skips
    the edge-value transform; ``unit_weight`` applies it with a compile-time
    constant 1 instead of a streamed operand (the kernel is specialized, not
    masked).  The accumulator/output dtype is the ``vals`` dtype for min and
    float32 (or the input float dtype) for add.

    ``init`` (optional, same shape/dtype as the output) seeds the resident
    accumulator in place of the combiner identity -- the buffer-pool
    contract of the streamed window schedule (DESIGN.md section 13): each
    window's sweep folds into the previous windows' partial through ONE
    recycled VMEM-resident buffer, so N chained window calls allocate the
    same accumulator a single resident sweep does, not N.

    The launch asks Mosaic for exactly the VMEM ``blocks.fused_footprint``
    counts for these shapes; a shape over the chip's capacity is refused by
    the compiler, which is why the engine checks the footprint first.
    """
    E, V = src.shape[0], vals.shape[0]
    if unit_weight and weight is not None:
        raise ValueError("unit_weight replaces the weight operand")
    weight_mode = "array" if weight is not None else \
        ("unit" if unit_weight else "none")
    if combine != "add":
        combine = "min"
    elif weight_mode == "unit":
        weight_mode = "none"  # multiplying by 1 is the identity
    if blocks.push_body(combine, vals.dtype) == "mxu":
        out_dtype = jnp.promote_types(vals.dtype, jnp.float32)
        body = _mxu_add_kernel
    else:
        out_dtype = vals.dtype
        body = functools.partial(_vpu_kernel, combine=combine,
                                 weight_mode=weight_mode)
    have_w = weight_mode == "array"
    have_init = init is not None
    batched = vals.ndim == 2
    B = vals.shape[1] if batched else None
    out_shape = (num_segments, B) if batched else (num_segments,)
    if have_init and (tuple(init.shape) != out_shape
                      or init.dtype != out_dtype):
        raise ValueError(f"init {init.shape}/{init.dtype} must match the "
                         f"output {out_shape}/{out_dtype}")

    # edges: [E] -> [NB', BLOCK_E] rows, NB' a multiple of EDGE_ROWS; the
    # padding blocks are empty bands (0, -1), so no loop visits them
    nb = E // BLOCK_E
    nbp = -(-max(nb, 1) // EDGE_ROWS) * EDGE_ROWS
    pad = (nbp - nb) * BLOCK_E

    def rows(a):
        return jnp.pad(a, (0, pad)).reshape(nbp, BLOCK_E)

    empty = jnp.asarray([[0], [-1], [0], [-1]], jnp.int32)
    band = jnp.concatenate(
        [band.astype(jnp.int32),
         jnp.broadcast_to(empty, (4, nbp - nb))], axis=1)
    operands = [rows(src), rows(dst), rows(valid)]
    if have_w:
        operands.append(rows(weight))
    edge_spec = pl.BlockSpec((EDGE_ROWS, BLOCK_E), lambda g, band: (g, 0))
    in_specs = [edge_spec] * len(operands)

    def resident(shape):
        return pl.BlockSpec(shape, lambda g, band: (0,) * len(shape))

    vals_p = _to_plane(vals, batched)
    out_p_shape = (num_segments // BLOCK_S,) + \
        ((B, BLOCK_S) if batched else (BLOCK_S,))
    in_specs.append(resident(vals_p.shape))
    operands.append(vals_p)
    if have_init:
        in_specs.append(resident(out_p_shape))
        operands.append(_to_plane(init, batched))

    def kernel(band_ref, *refs):
        # unpack the optional operands (weight stream, seed accumulator);
        # pallas passes out_ref last
        s, d, v = refs[:3]
        i = 3
        w_ref = refs[i] if have_w else None
        i += int(have_w)
        vals_ref = refs[i]
        init_ref = refs[i + 1] if have_init else None
        body(band_ref, s, d, v, w_ref, vals_ref, init_ref, refs[-1])

    fp = blocks.fused_footprint(nb, V, num_segments, batch=B, weight=have_w,
                                init=have_init)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the band table rides in SMEM
            grid=(nbp // EDGE_ROWS,),
            in_specs=in_specs,
            out_specs=resident(out_p_shape),
        ),
        out_shape=jax.ShapeDtypeStruct(out_p_shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # out accumulates across steps
            vmem_limit_bytes=fp["vmem"]),
        interpret=interpret,
        name=f"push_fused_{combine}",  # the monoid, for the profiler
    )(band, *operands)
    return _from_plane(out, batched)
