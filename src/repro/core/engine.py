"""Actor-superstep engine: chare chunks -> mesh shards via ``shard_map``.

The paper's execution model is: per iteration, each chare (i) scans its local
edges and aggregates outgoing data, (ii) exchanges messages, (iii) applies
received payloads to local vertex state, with quiescence detection between
phases.  Under SPMD the quiescence barrier is the collective itself; the
engine jits ONE program containing the whole iteration loop, so XLA can
overlap the aggregation of iteration i+1 with the tail of the collective of
iteration i (the paper's "send early, let idle chares move on" -- see
`strategies.pairs`).

Algorithms are ``VertexProgram``s (see ``repro.core.programs``); the engine
owns the shard_map plumbing, fori/while-loop selection, frontier masking,
and the compile cache exactly once -- ``Engine.run(program)`` is the single
entry point, with ``pagerank``/``labelprop``/``sssp``/``bfs`` as thin
wrappers.

Two adaptive layers ride on top (DESIGN.md section 9):

  * ``push_fn="auto"`` (the default) prices the partition's measured band
    tables (``repro.kernels.blocks.choose_push``) and picks the fused
    band-pruned kernel vs the staged dense pipeline per layout, instead of
    being told -- the decision is recorded in ``Engine.dispatch`` and
    surfaced by the COST harness.
  * ``Engine.run(replan=...)`` re-partitions mid-run: the superstep loop is
    segmented, and at segment boundaries a load-skew trigger (per-chare
    frontier-edge counts) may rebuild the placement via
    ``PartitionedGraph.repartition`` and carry the state across through the
    composed relabel (``PartitionPlan.padded_map_from``) -- bit-exact for
    min-monoid programs.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import partitioners as part_mod
from repro.core import strategies as strat
from repro.core.graph import PartitionedGraph

AXIS = strat.AXIS


def make_pe_mesh(num_pes: int):
    """1-D mesh of chares ("processing elements" in the paper's plots)."""
    devs = jax.devices()
    if num_pes > len(devs):
        raise ValueError(
            f"requested {num_pes} PEs but only {len(devs)} devices; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count for CPU runs")
    return compat.make_mesh((num_pes,), (AXIS,),
                            axis_types=compat.auto_axes(1))


@jax.jit
def _relabel_gather(init_rows, old_rows, src, tgt):
    """The composed-relabel state move as one on-device gather/scatter:
    ``tgt`` slots (unique by construction -- a relabel is a bijection on
    live vertices) receive the ``src`` slots of the old plane; everything
    else keeps the caller-built init fill."""
    return init_rows.at[tgt].set(old_rows[src])


@dataclasses.dataclass
class ReplanPolicy:
    """When and how ``Engine.run`` re-partitions mid-run (DESIGN.md sec. 9).

    The superstep loop runs in jitted segments of ``every`` supersteps; at
    each segment boundary the engine checkpoints state on the host and, if
    triggered, switches the placement to ``partitioner``.  ``mode="skew"``
    (the default) triggers when the per-chare *frontier-edge* imbalance
    (``partition_stats(pg, frontier=...)``) exceeds ``threshold`` -- the
    load convergence programs actually shift across supersteps;
    ``mode="always"`` replans at every checkpoint (fixed-iteration programs
    and tests).  ``max_replans`` bounds the total prep spent re-placing.
    """

    partitioner: str
    every: int = 4
    threshold: float = 1.5
    mode: str = "skew"
    max_replans: int = 4

    def __post_init__(self):
        if self.mode not in ("skew", "always"):
            raise ValueError(f"unknown replan mode {self.mode!r}")
        if self.every < 1:
            raise ValueError("replan checkpoint interval must be >= 1")


@dataclasses.dataclass
class StreamConfig:
    """Sizing and placement knobs for ``residency="stream"`` (DESIGN.md
    section 13).

    Exactly one of ``windows`` / ``budget_bytes`` sizes the edge windows:
    ``windows`` asks for that many sweeps per superstep, ``budget_bytes``
    caps the DEVICE-resident edge working set (two staging windows -- the
    double buffer) and derives the widest window that fits.  ``cache_dir``
    points shard reads at the on-disk layout cache
    (``checkpoint.save_layout_cache``): the edge planes are memory-mapped
    and never fully materialized in host RAM either.  ``prefetch=False``
    serializes fetch and compute (the no-overlap baseline the measured
    overlap efficiency is defined against).
    """

    windows: int | None = None
    budget_bytes: int | None = None
    cache_dir: str | None = None
    prefetch: bool = True

    def __post_init__(self):
        if self.windows is not None and self.budget_bytes is not None:
            raise ValueError("pass windows OR budget_bytes, not both")
        if self.windows is not None and self.windows < 1:
            raise ValueError("windows must be >= 1")


class _StreamPrefetcher:
    """Double-buffered host->device pipeline for edge-window shards.

    Two recycled host staging slots and one worker thread.  While the jitted
    fold of window k runs, the worker slices window k+1 out of the (possibly
    memory-mapped) ``ShardSource`` into a staging slot -- pure numpy work
    whose memcpy releases the GIL, so it hides behind dispatched compute.
    The device transfer itself happens in ``take()`` on the consumer thread
    as an ASYNC ``jax.device_put`` enqueue: XLA materializes it on its own
    schedule, in queue order ahead of the window fold that consumes it.
    Blocking inside the worker would instead serialize on the whole device
    queue (on the CPU backend a transfer only reports ready once the queue
    drains past it), which is exactly the stall the pipeline exists to hide.

    Slot recycling: ``device_put`` may read the staging buffer LAZILY (the
    transfer can materialize as late as the consuming computation), so a
    slot is only safe to overwrite once the fold that consumed its previous
    window has EXECUTED.  The caller threads that dependency through
    ``submit(after=...)``: the win output of the slot's previous consumer.
    Waiting on it is classic depth-2 pipeline backpressure -- the worker
    never runs more than two windows ahead of device execution, which also
    bounds the in-flight transfer memory to the double buffer.

    Accounting: ``copy_s`` is data-movement work (staging read + transfer
    enqueue); ``stall_s`` is the share of it the COMPUTE pipeline was
    exposed to.  A consumer wait is a stall only if the device had nothing
    left to execute meanwhile -- attribution samples the last dispatched
    fold's non-blocking ``is_ready()`` at both ends of the wait (busy both
    ends: fully hidden; busy one end: half; idle: fully exposed).  Worker
    backpressure waits are excluded outright (the pipeline running AHEAD of
    execution is not a data stall).  Overlap efficiency = 1 - stall/copy;
    it measures the pipeline's structure -- on hardware where transfers and
    compute use disjoint resources (TPU DMA engines) it equals the
    wall-clock hiding.  ``pipelined=False`` performs the read inside
    ``take()`` and charges it in full (stall == copy): the serialized
    baseline with identical code.
    """

    def __init__(self, source, shardings, pipelined=True):
        self.source = source
        self.shardings = shardings
        self.pipelined = pipelined
        self._pool = [source.make_staging(), source.make_staging()]
        self._seq = 0
        self._pending = collections.deque()
        self._ex = (concurrent.futures.ThreadPoolExecutor(max_workers=1)
                    if pipelined else None)
        self.copy_s = 0.0
        self.stall_s = 0.0
        self.bytes_read = 0
        self.fetches = 0
        self.compute = None  # the most recently dispatched fold's output:
        #                      its readiness is the device-busy probe

    @property
    def next_slot(self):
        return self._seq % 2

    def _device_busy(self):
        if self.compute is None:
            return False
        try:
            return not self.compute.is_ready()
        except (AttributeError, RuntimeError):
            return False

    def _read(self, k, slot, active, after):
        bp = 0.0
        if after is not None:
            # backpressure, not copy work: wait until the fold that consumed
            # this slot's previous window has run (its transfer materialized)
            t0 = time.perf_counter()
            jax.block_until_ready(after)
            bp = time.perf_counter() - t0
        t0 = time.perf_counter()
        nbytes = self.source.read_window(k, self._pool[slot], active)
        return slot, nbytes, time.perf_counter() - t0, bp

    def submit(self, k, active, after=None):
        slot = self._seq % 2
        self._seq += 1
        if self._ex is not None:
            self._pending.append(self._ex.submit(self._read, k, slot,
                                                 active, after))
        else:
            self._pending.append((k, slot, active, after))

    def take(self):
        """-> (device window dict, slot): the next window, transfer enqueued."""
        item = self._pending.popleft()
        if self._ex is not None:
            busy0 = self._device_busy()
            t0 = time.perf_counter()
            slot, nbytes, dt_read, bp = item.result()
            wait = max(0.0, time.perf_counter() - t0 - bp)
            busy1 = self._device_busy()
            # device-busy attribution: the wait only stalls the pipeline to
            # the extent the device ran dry during it
            self.stall_s += wait * (0.0 if busy0 and busy1
                                    else 0.5 if busy0 or busy1 else 1.0)
        else:
            slot, nbytes, dt_read, _ = self._read(*item)
            self.stall_s += dt_read
        t1 = time.perf_counter()
        dev = {name: jax.device_put(buf, self.shardings[name])
               for name, buf in self._pool[slot].items()}
        put = time.perf_counter() - t1
        self.copy_s += dt_read + put
        if self._ex is None or not self._device_busy():
            self.stall_s += put
        self.bytes_read += nbytes
        self.fetches += 1
        return dev, slot

    def close(self):
        if self._ex is not None:
            self._ex.shutdown(wait=True)


@dataclasses.dataclass
class Engine:
    """Runs vertex programs on a partitioned graph with a chosen strategy.

    ``push_fn`` accepts ``"auto"`` (default: staged-vs-fused chosen per
    layout from the measured band occupancy, recorded in ``self.dispatch``),
    ``None`` (explicit staged jnp pipeline), or a callable hook
    (``ops.make_push_fn``, used as given).

    The strategy follows the partition's dimensionality: a ``grid(R,C)``
    partition always runs the ``grid2d`` two-phase reduce (the 1-D layouts
    do not exist on it), and the constructor's 1-D ``strategy`` is kept as
    the fallback a mid-run replan to a 1-D placement rebinds to.  Asking for
    ``grid2d`` on a 1-D partition is an error.
    """

    pg: PartitionedGraph
    strategy: str = "sortdest"
    mesh: object = None
    segment_fn: object = None  # optional kernel override for local combines
    push_fn: object = "auto"  # 'auto' | None | fused-kernel hook for the
    #                           whole gather/transform/combine loop
    collectives: str = "auto"  # grid2d phase-2 lowering: 'auto' (grouped),
    #                            'grouped' (axis_index_groups), 'full'
    residency: str = "resident"  # 'resident' (edge planes live on device) |
    #                              'stream' (windowed host->device pipeline)
    stream: StreamConfig | None = None

    def __post_init__(self):
        if self.collectives not in ("auto", "grouped", "full"):
            raise ValueError(f"unknown collectives mode {self.collectives!r}")
        if self.residency not in ("resident", "stream"):
            raise ValueError(f"unknown residency {self.residency!r}; "
                             "choose 'resident' or 'stream'")
        if self.residency == "stream" and not self.pg.is_grid:
            raise ValueError(
                "residency='stream' needs a grid(R,C) partition -- the "
                "window schedule walks edge rectangles (use grid(1,1) for "
                "a single PE)")
        if self.stream is not None and self.residency != "stream":
            raise ValueError("stream config given but residency is "
                             f"{self.residency!r}")
        if self.strategy not in strat.STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}; "
                             f"choose from {sorted(strat.STRATEGIES)}")
        if self.strategy == "grid2d" and not self.pg.is_grid:
            raise ValueError("strategy 'grid2d' needs a grid(R,C) partition "
                             f"(got partitioner {self.pg.partitioner!r})")
        if self.mesh is None:
            self.mesh = make_pe_mesh(self.pg.num_chunks)
        if self.pg.num_chunks != self.mesh.devices.size:
            raise ValueError("num_chunks must equal mesh size")
        if not (self.push_fn in ("auto", None) or callable(self.push_fn)):
            raise ValueError(
                f"push_fn must be 'auto', None, or a callable hook "
                f"(ops.make_push_fn), got {self.push_fn!r}")
        # the 1-D strategy a replan back to a 1-D placement falls back to
        self._strategy_request = (self.strategy if self.strategy != "grid2d"
                                  else "sortdest")
        self._push_request = self.push_fn
        self._bind(self.pg)

    def _bind(self, pg: PartitionedGraph):
        """Point the engine at a partition: alias its device-upload cache,
        re-resolve the active strategy and adaptive dispatch, start a fresh
        compile cache."""
        self.pg = pg
        # the strategy tracks the partition's dimensionality: rectangles run
        # the two-phase reduce, 1-D placements the requested variant
        self.strategy = "grid2d" if pg.is_grid else self._strategy_request
        self._source = None
        # layouts are uploaded once per PartitionedGraph and shared: engines
        # built on the same partition (a strategy sweep) alias the same
        # device buffers instead of re-transferring them per Engine; only
        # the strategy's own layout is materialized and shipped (a replan
        # never builds or uploads the edge order it will not run)
        if self.residency == "stream":
            # out-of-core: only the per-vertex planes and the row->col
            # gather map become device-resident; the [P, Emax] edge planes
            # stay in host memory (or on disk, memory-mapped) and reach the
            # device one double-buffered window at a time.  The source is
            # built FIRST so a layout cache hit feeds the band table below
            # from the memory-mapped entry instead of a fresh host build.
            cfg = self.stream or StreamConfig()
            self._source = pg.shard_source(windows=cfg.windows,
                                           budget_bytes=cfg.budget_bytes,
                                           cache_dir=cfg.cache_dir)
            self.arrays = {"gr_row_to_col": jnp.asarray(pg.gr_row_to_col)}
        elif self.strategy in strat.PAIRWISE:
            self.arrays = self.pg.device_pairwise()
        else:
            self.arrays = self.pg.device_arrays(
                strat.STRATEGY_LAYOUT[self.strategy])
        self.aux = self.pg.device_aux()
        if pg.is_grid:
            rows, cols = pg.grid_shape
            # the static grid meta rides in via partial: strategies share one
            # positional signature and only grid2d needs the column geometry
            self._grid_meta = (rows, cols, pg.col_chunk_size)
            self._collectives = ("grouped" if self.collectives == "auto"
                                 else self.collectives)
            self._fn = functools.partial(
                strat.grid2d, grid_meta=self._grid_meta,
                collectives=self._collectives)
        else:
            self._grid_meta = None
            self._collectives = "full"
            self._fn = strat.STRATEGIES[self.strategy]
        self._C, self._K = self.pg.num_chunks, self.pg.chunk_size
        # frontier-gating geometry: which BLOCK_V source blocks each shard's
        # edges can gather from at all (ones when the layout has no band
        # table -- the pairwise variant then gates on "any frontier at all")
        from repro.kernels import blocks as blk

        self._gate_nsb = max(-(-self._K // blk.BLOCK_V), 1)
        if "gate_blocks" not in self.arrays:
            # installed into the shared per-layout upload cache: the mask is
            # a pure function of (partition, layout), so every engine aliasing
            # this dict computes the identical table and the first one ships it
            layout = strat.STRATEGY_LAYOUT[self.strategy]
            band = {"basic": lambda: self.pg.band,
                    "sd": lambda: self.pg.sd_band,
                    "grid": lambda: self.pg.gr_band}.get(layout)
            gmask = (blk.band_source_mask(np.asarray(band()), self._gate_nsb)
                     if band is not None
                     else np.ones((self._C, self._gate_nsb), np.int32))
            self.arrays["gate_blocks"] = jnp.asarray(gmask)
        self.dispatch = self._resolve_dispatch()
        self.dispatch["collectives"] = self._collectives
        self.dispatch["residency"] = self.residency
        if self._source is not None:
            sb = self._source
            cfg = self.stream or StreamConfig()
            self.dispatch["stream"] = {
                "windows": sb.num_windows,
                "blocks_per_window": sb.blocks_per_window,
                "window_bytes": sb.window_bytes,
                # the device-resident edge working set: two staging windows
                "resident_edge_bytes": 2 * sb.window_bytes,
                "total_edge_bytes": sb.total_edge_bytes,
                "edge_fraction_resident":
                    2 * sb.window_bytes / sb.total_edge_bytes,
                "budget_bytes": cfg.budget_bytes,
                "origin": sb.origin,
            }
        self._compiled = {}  # program.key -> jitted fn; timing must not
        #                      rebuild the closure (COST times compute only)

    def _rebind(self, pg: PartitionedGraph):
        """Replan rebind: swap to a re-partitioned layout of the same graph.

        The new ``PartitionedGraph`` starts with an empty device cache, so
        every layout buffer (edge arrays, band tables, aux planes) is
        freshly uploaded -- nothing from the old placement can leak -- and
        the compile cache is dropped (``chunk_size`` and band tables differ,
        and the adaptive dispatch may flip with the new bands).
        """
        if pg.num_chunks != self._C:
            raise ValueError("replan must preserve the chare count "
                             f"({pg.num_chunks} != {self._C})")
        self._bind(pg)

    def _kernel_device_kind(self) -> str | None:
        """``device_kind`` the fused kernel would compile for; None off-TPU,
        where the kernels only run through the Pallas interpreter."""
        dev = self.mesh.devices.flat[0]
        return dev.device_kind if dev.platform == "tpu" else None

    def _resolve_dispatch(self) -> dict:
        """Resolve ``push_fn='auto'`` against the bound layout's bands.

        Returns the recorded decision; sets ``self.push_fn`` to the callable
        the strategies will actually receive for a 1-D state plane (a
        batched plane resolves its own width in ``_push_for``).  The choice
        is the band rule (``blocks.choose_push``: occupancy and in-band
        tiles per edge block, declining with ``reason`` ``"occupancy"`` or
        ``"tiles"``); on a TPU the fused kernel is then installed only where
        its fast-memory footprint (``blocks.fused_footprint``, for the
        shapes each launch is handed) fits the chip -- otherwise the record
        says ``staged`` with the ``reason`` ``"vmem"`` or ``"smem"``.
        Off-TPU the kernels would only
        run through the interpreter (an emulation, not an execution win), so
        the staged path runs and the choice is recorded as computed.
        """
        from repro.kernels import blocks

        self._push_by_batch = {}
        self._fused_geometry = None
        if self._push_request != "auto":
            self.push_fn = self._push_request
            choice = "explicit" if callable(self._push_request) else "staged"
            return {"choice": choice, "mode": "explicit"}
        layout = strat.STRATEGY_LAYOUT[self.strategy]
        if layout == "pairwise":
            self.push_fn = None
            return {"choice": "staged", "mode": "auto",
                    "reason": "basic strategy has no push loop to fuse"}
        if layout == "grid":
            # rectangle phase-1 push: gather side is the row-chunk state,
            # scatter side the column-padded destination space
            band = self.pg.gr_band
            scatter = self.pg.grid_shape[1] * self.pg.col_chunk_size
        else:
            band = self.pg.sd_band if layout == "sd" else self.pg.band
            scatter = self._C * self._K
        emax = self.pg.edge_valid.shape[1]
        choice, occ = blocks.choose_push(band, emax, self._K, scatter)
        rec = {"choice": choice, "mode": "auto", "layout": layout,
               "threshold": blocks.BAND_OCC_FUSED_MAX,
               "tiles_threshold": blocks.FUSED_TILES_PER_BLOCK_MAX, **occ}
        if choice == "fused":
            # a streamed launch sees one window's edge blocks and folds into
            # a seed accumulator (init=); a resident one sees the whole row
            stream = self._source is not None
            self._fused_geometry = {
                "num_edge_blocks": (self._source.blocks_per_window if stream
                                    else blocks.num_edge_blocks(emax)),
                "V": self._K, "S": scatter, "init": stream}
        self.push_fn = self._resolve_push(None, rec)
        return rec

    def _resolve_push(self, batch, rec):
        """Footprint check of the fused kernel for one plane width: returns
        the hook to install (None = staged) and records the bytes, and any
        ``reason`` for declining, into ``rec``."""
        from repro.kernels import blocks

        if self._fused_geometry is None:
            return None
        fp = blocks.fused_footprint(**self._fused_geometry, batch=batch)
        rec["footprint"] = {"vmem_bytes": fp["vmem"],
                            "smem_bytes": fp["smem"]}
        kind = self._kernel_device_kind()
        if kind is None:
            return None
        rec["footprint"]["device_kind"] = kind
        misfit = blocks.fused_misfit(fp, kind)
        if misfit is not None:
            rec["choice"], rec["reason"] = "staged", misfit
            return None
        from repro.kernels import ops

        return ops.make_push_fn(interpret=False)

    def _push_for(self, vals):
        """The push hook for a ``[K]`` or batched ``[K, B]`` vals plane: the
        fused kernel's footprint grows with B, so each batch width is
        checked on first use and recorded in ``dispatch['batch'][B]``."""
        if vals.ndim == 1 or self._fused_geometry is None:
            return self.push_fn
        B = int(vals.shape[-1])
        if B not in self._push_by_batch:
            rec = {"choice": "fused"}
            self._push_by_batch[B] = self._resolve_push(B, rec)
            self.dispatch.setdefault("batch", {})[B] = rec
        return self._push_by_batch[B]

    # -- shard_map plumbing -------------------------------------------------

    def _smap(self, body, n_state_in=1, n_out=2):
        arr_specs = {k: P(AXIS, *([None] * (v.ndim - 1)))
                     for k, v in self.arrays.items()}
        aux_specs = {k: P(AXIS, None) for k in self.aux}
        state_specs = tuple(P(AXIS, None) for _ in range(n_state_in))
        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(arr_specs, aux_specs, *state_specs),
            out_specs=tuple(P(AXIS, None) for _ in range(n_out)),
            check_vma=False)

    def _phase1(self, vals, arrs, combiner, edge_value=None,
                edge_semiring=None):
        p1, _ = strat.PHASES[self.strategy]
        return p1(vals, arrs, combiner, self._C, self._K,
                  segment_fn=self.segment_fn, edge_value=edge_value,
                  push_fn=self._push_for(vals), edge_semiring=edge_semiring,
                  grid_meta=self._grid_meta)

    def _phase2(self, partial, arrs, combiner):
        _, p2 = strat.PHASES[self.strategy]
        return p2(partial, arrs, combiner, self._C, self._K,
                  segment_fn=self.segment_fn, grid_meta=self._grid_meta,
                  collectives=self._collectives)

    def _propagate(self, vals, arrs, combiner, edge_value=None,
                   edge_semiring=None):
        return self._phase2(
            self._phase1(vals, arrs, combiner, edge_value, edge_semiring),
            arrs, combiner)

    def _push_closure(self, program, gate, arrs):
        """-> ``push(vals, frontier) -> (partial, active)``: phase 1,
        optionally gated on the frontier/band-block intersection.

        The gate is the rectangle-skipping test (DESIGN.md section 12): the
        live frontier reduced to BLOCK_V block granularity against the
        shard's precomputed band source-block mask.  A shard with no
        intersection skips its whole local push via ``lax.cond`` and feeds
        the identity partial to phase 2 -- which still runs unconditionally
        on every shard, collectives being SPMD.  Phase 1 contains no
        collectives by construction, so per-shard divergence is safe.
        """
        from repro.kernels import blocks as blk

        comb = program.combiner
        p1 = lambda v: self._phase1(v, arrs, comb, program.edge_value,
                                    program.edge_semiring)
        if not gate:
            return lambda vals, frontier: (p1(vals), jnp.asarray(True))
        gate_blocks = arrs["gate_blocks"] != 0  # per-shard [nsb]
        nsb = self._gate_nsb

        def push(vals, frontier):
            f = frontier.any(axis=-1) if frontier.ndim == 2 else frontier
            pad = nsb * blk.BLOCK_V - f.shape[0]
            if pad:
                f = jnp.pad(f, (0, pad))
            fb = f.reshape(nsb, blk.BLOCK_V).any(axis=1)
            active = (fb & gate_blocks).any()
            partial = jax.lax.cond(
                active, p1,
                lambda v: strat.phase1_identity(
                    self.strategy, v, arrs, comb, self._C, self._K,
                    self._grid_meta),
                vals)
            return partial, active

        return push

    # -- the one superstep loop ---------------------------------------------

    def _make_body(self, program, sync="barrier", gate=False):
        """Per-shard body: the whole iteration loop of one vertex program.

        Fixed-iteration programs (PageRank) compile to ``fori_loop``;
        convergence programs (label propagation, SSSP, BFS) compile to
        ``while_loop`` with frontier masking -- vertices whose state did not
        change last superstep send the combiner identity, preserving the
        paper's "only send labels that changed" work skipping under XLA's
        static shapes (see DESIGN.md "Dynamic message sizes").

        ``sync='overlap'`` relaxes the superstep barrier (DESIGN.md section
        12): the loop carries the phase-1 partial as a second buffer, so
        each iteration combines the PREVIOUS iteration's partial (phase 2)
        while computing the next push (phase 1) from state the combine has
        not yet touched -- the two halves share no data dependency and XLA
        is free to overlap the collective with the local compute.  Updates
        land with staleness 1; min-monoid label-correcting programs stay
        convergent to the same fixpoint.  Termination switches to
        double-check quiescence: two consecutive quiescent applies imply the
        in-flight partial is the identity (the last push was built from an
        empty frontier), so no improvement can still be pending.

        ``gate`` enables frontier gating: shards whose live frontier blocks
        miss their band source blocks skip phase 1 entirely (see
        ``_push_closure``); skipped launches are counted per shard.
        """
        comb = program.combiner

        def body(arrs, aux, s0):
            arrs = {k: v[0] for k, v in arrs.items()}
            aux = {k: v[0] for k, v in aux.items()}
            push = self._push_closure(program, gate, arrs)
            p2 = lambda partial: self._phase2(partial, arrs, comb)
            zero_sk = jnp.asarray(0, jnp.int32)

            def superstep(state, vals):
                incoming = self._propagate(vals, arrs, comb,
                                           program.edge_value,
                                           program.edge_semiring)
                return program.apply(state, incoming, aux)

            if program.fixed_iters is not None:
                final = jax.lax.fori_loop(
                    0, program.fixed_iters,
                    lambda _, s: superstep(s, program.update(s, aux)), s0[0])
                iters = jnp.asarray(program.fixed_iters, jnp.int32)
                skipped = zero_sk
            elif sync == "overlap":
                sent = jnp.asarray(comb.identity, s0.dtype)
                part0, active0 = push(program.update(s0[0], aux),
                                      jnp.ones((self._K,), bool))

                def cond(carry):
                    _, _, _, quiet, it, _ = carry
                    return jnp.logical_and(quiet < 2, it < program.max_iters)

                def step(carry):
                    state, frontier, pending, quiet, it, sk = carry
                    incoming = p2(pending)
                    vals = jnp.where(frontier, program.update(state, aux),
                                     sent)
                    new_pending, active = push(vals, frontier)
                    new = program.apply(state, incoming, aux)
                    delta = new != state
                    changed = jax.lax.psum(
                        delta.any().astype(jnp.int32), AXIS) > 0
                    quiet = jnp.where(changed, 0, quiet + 1)
                    return (new, delta, new_pending, quiet, it + 1,
                            sk + 1 - active.astype(jnp.int32))

                # the seed push happens before the loop; the first in-loop
                # frontier is empty so it is not pushed twice
                final, _, _, _, iters, skipped = jax.lax.while_loop(
                    cond, step,
                    (s0[0], jnp.zeros((self._K,), bool), part0,
                     jnp.asarray(0), jnp.asarray(0),
                     zero_sk + 1 - active0.astype(jnp.int32)))
            else:

                def cond(carry):
                    _, _, changed, it, _ = carry
                    return jnp.logical_and(changed, it < program.max_iters)

                def step(carry):
                    state, frontier, _, it, sk = carry
                    sent = jnp.asarray(comb.identity, s0.dtype)
                    # frontier masking: quiesced vertices send the identity
                    vals = jnp.where(frontier, program.update(state, aux),
                                     sent)
                    partial, active = push(vals, frontier)
                    new = program.apply(state, p2(partial), aux)
                    delta = new != state
                    changed = jax.lax.psum(
                        delta.any().astype(jnp.int32), AXIS) > 0
                    return (new, delta, changed, it + 1,
                            sk + 1 - active.astype(jnp.int32))

                final, _, _, iters, skipped = jax.lax.while_loop(
                    cond, step, (s0[0], jnp.ones((self._K,), bool),
                                 jnp.asarray(True), jnp.asarray(0), zero_sk))
            # per-shard gating stats: (skipped launches, launch slots) --
            # overlap runs one extra phase-1 slot (the pre-loop seed push)
            slots = iters + (1 if (sync == "overlap"
                                   and program.fixed_iters is None) else 0)
            stats = jnp.stack([skipped, slots.astype(jnp.int32)])[None]
            return (final[None], jnp.full((1, self._K), iters, jnp.int32),
                    stats)

        return body

    # -- segmented loop (the replan path) -----------------------------------

    def _make_segment_body(self, program, sync="barrier", gate=False):
        """Like ``_make_body`` but bounded: runs up to ``nsteps`` supersteps
        and returns (state, frontier, executed, skipped) so the host can
        checkpoint, replan, and resume.  One compiled segment serves every
        length (the bound is a traced operand), and chaining segments
        reproduces the whole-loop superstep sequence exactly -- same Jacobi
        order, same frontier masking, same quiescence accounting.  Under
        ``sync='overlap'`` every segment drains its in-flight partial before
        returning, so replans only ever fire at drained sync points.
        """
        comb = program.combiner
        convergence = program.fixed_iters is None

        def body(arrs, aux, s0, f0, nsteps):
            arrs = {k: v[0] for k, v in arrs.items()}
            aux = {k: v[0] for k, v in aux.items()}
            push = self._push_closure(program, gate, arrs)
            p2 = lambda partial: self._phase2(partial, arrs, comb)
            sent = jnp.asarray(comb.identity, s0.dtype)
            limit = nsteps[0, 0]
            zero_sk = jnp.asarray(0, jnp.int32)

            if convergence and sync == "overlap":
                # the incoming frontier seeds the pipeline's first push; the
                # loop then runs double-buffered and DRAINS before returning
                # -- a replan at the segment boundary never sees an in-flight
                # partial (the relabel would misroute it)
                f_in = f0[0] != 0
                part0, active0 = push(
                    jnp.where(f_in, program.update(s0[0], aux), sent), f_in)

                def cond(carry):
                    _, _, _, quiet, it, _ = carry
                    return jnp.logical_and(quiet < 2, it < limit)

                def step(carry):
                    state, frontier, pending, quiet, it, sk = carry
                    incoming = p2(pending)
                    vals = jnp.where(frontier, program.update(state, aux),
                                     sent)
                    new_pending, active = push(vals, frontier)
                    new = program.apply(state, incoming, aux)
                    delta = new != state
                    changed = jax.lax.psum(
                        delta.any().astype(jnp.int32), AXIS) > 0
                    quiet = jnp.where(changed, 0, quiet + 1)
                    return (new, delta, new_pending, quiet, it + 1,
                            sk + 1 - active.astype(jnp.int32))

                state, frontier, pending, _, it, sk = jax.lax.while_loop(
                    cond, step,
                    (s0[0], jnp.zeros((self._K,), bool), part0,
                     jnp.asarray(0), jnp.asarray(0),
                     zero_sk + 1 - active0.astype(jnp.int32)))
                # drain: fold the in-flight partial, keep its deltas in the
                # frontier so the next segment re-pushes them
                drained = program.apply(state, p2(pending), aux)
                frontier = frontier | (drained != state)
                state = drained
            else:

                def cond(carry):
                    _, _, changed, it, _ = carry
                    return jnp.logical_and(changed, it < limit)

                def step(carry):
                    state, frontier, _, it, sk = carry
                    if convergence:
                        vals = jnp.where(frontier, program.update(state, aux),
                                         sent)
                        partial, active = push(vals, frontier)
                    else:
                        vals = program.update(state, aux)
                        partial, active = push(vals, jnp.ones((self._K,),
                                                              bool))
                    new = program.apply(state, p2(partial), aux)
                    delta = new != state
                    if convergence:
                        changed = jax.lax.psum(
                            delta.any().astype(jnp.int32), AXIS) > 0
                    else:
                        changed = jnp.asarray(True)
                    return (new, delta, changed, it + 1,
                            sk + 1 - active.astype(jnp.int32))

                state, frontier, _, it, sk = jax.lax.while_loop(
                    cond, step,
                    (s0[0], f0[0] != 0, jnp.asarray(True), jnp.asarray(0),
                     zero_sk))
            slots = it + (1 if (convergence and sync == "overlap") else 0)
            stats = jnp.stack([sk, slots.astype(jnp.int32)])[None]
            return (state[None], frontier.astype(jnp.int32)[None],
                    jnp.full((1, 1), it, jnp.int32), stats)

        return body

    def _run_segment(self, program, state, frontier, nsteps, sync="barrier",
                     gate=False):
        key = (program.key, "segment", sync, gate)
        fn = self._compiled.get(key)
        if fn is None:
            fn = jax.jit(self._smap(self._make_segment_body(program, sync,
                                                            gate),
                                    n_state_in=3, n_out=4))
            self._compiled[key] = fn
        bound = jnp.full((self._C, 1), nsteps, jnp.int32)
        state, frontier, it, stats = fn(self.arrays, self.aux, state,
                                        frontier, bound)
        stats = np.asarray(jax.device_get(stats))
        self._gate_skipped += int(stats[:, 0].sum())
        self._gate_slots += int(stats[:, 1].sum())
        return state, frontier, int(jax.device_get(it)[0, 0])

    # -- streamed execution (residency='stream', DESIGN.md section 13) -------

    def _stream_fns(self, program, B=None):
        """Compile (once per program, and per B-bucket on the batched
        plane) the three jitted shard_map pieces of the streamed superstep:
        ``prep`` (frontier-masked update -> vals), ``win`` (fold ONE edge
        window's phase-1 contribution into the running partial), ``apply``
        (phase 2 + program apply + the convergence/frontier summaries the
        host loop steers by).

        ``B`` switches the bodies onto the batched [*, B] query plane
        (DESIGN.md section 15): state/frontier/vals/partial carry a
        trailing query axis, the window fold and phase 2 are
        rank-polymorphic over it, and ``apply`` returns PER-QUERY
        convergence counts ([B]) plus per-query frontier blocks
        ([nsb, B]) so the host gate can take the union over live query
        columns.  The compile key strips seed params exactly like the
        resident batched cache (``_batch_key``): any source list of the
        same bucket reuses the compilation.

        The win outputs double as the prefetcher's backpressure handles
        (``_StreamPrefetcher``), so the partial chain is NOT donated -- the
        accumulator recycling lives at the kernel level instead (the fused
        push's ``init=`` seed, ``kernels.ops.push``).
        """
        batched = B is not None
        key = ((self._batch_key(program, B), "stream") if batched
               else (program.key, "stream"))
        fns = self._compiled.get(key)
        if fns is not None:
            return fns
        from repro.kernels import blocks as blk

        comb = program.combiner
        aux_specs = {k: P(AXIS, None) for k in self.aux}
        arr_specs = {k: P(AXIS, *([None] * (v.ndim - 1)))
                     for k, v in self.arrays.items()}
        vec = P(AXIS, None)
        plane = P(AXIS, None, None) if batched else vec
        wd_specs = {"gr_src_local": vec, "gr_dst_col": vec,
                    "gr_edge_valid": vec, "gr_edge_weight": vec,
                    "gr_band": P(AXIS, None, None)}
        nsb = self._gate_nsb
        has_qp = batched and program.query_plane is not None
        qp_specs = (plane,) if has_qp else ()
        fixed = program.fixed_iters is not None

        def unpack_aux(aux, qp):
            if not batched:
                return {k: v[0] for k, v in aux.items()}
            # per-vertex [K] planes broadcast over the batch axis as
            # [K, 1]; the per-query operand is already [K, B] and merged
            # after the expansion so programs read it at full rank
            out = {k: v[0][:, None] for k, v in aux.items()}
            if has_qp:
                out["qplane"] = qp[0]
            return out

        def prep_body(aux, state, frontier, *qp):
            aux = unpack_aux(aux, qp[0] if has_qp else None)
            if fixed:
                vals = program.update(state[0], aux)
            else:
                sent = jnp.asarray(comb.identity, state.dtype)
                vals = jnp.where(frontier[0] != 0,
                                 program.update(state[0], aux), sent)
            return (vals[None],)

        def win_body(wd, vals, partial):
            wd = {k: v[0] for k, v in wd.items()}
            out = strat.grid2d_phase1_window(
                vals[0], wd, partial[0], comb, self._C, self._K,
                segment_fn=self.segment_fn, edge_value=program.edge_value,
                push_fn=self._push_for(vals[0]),
                edge_semiring=program.edge_semiring,
                grid_meta=self._grid_meta)
            return (out[None],)

        def apply_body(arrs, aux, partial, state, *qp):
            arrs = {k: v[0] for k, v in arrs.items()}
            aux = unpack_aux(aux, qp[0] if has_qp else None)
            incoming = self._phase2(partial[0], arrs, comb)
            new = program.apply(state[0], incoming, aux)
            delta = new != state[0]
            # frontier collapsed to BLOCK_V granularity: the host-side gate
            # intersects it with each window's band source-block mask
            pad = nsb * blk.BLOCK_V - delta.shape[0]
            if batched:
                # per-query convergence across chares, and per-query
                # frontier blocks for the union gate
                changed = jax.lax.psum(
                    delta.any(axis=0).astype(jnp.int32), AXIS)  # [B]
                f = (jnp.pad(delta, ((0, pad), (0, 0))) if pad else delta)
                fb = f.reshape(nsb, blk.BLOCK_V, f.shape[1]).any(axis=1)
                return (new[None], delta.astype(jnp.int32)[None],
                        changed[None], fb.astype(jnp.int32)[None])
            changed = jax.lax.psum(delta.any().astype(jnp.int32), AXIS) > 0
            f = jnp.pad(delta, (0, pad)) if pad else delta
            fb = f.reshape(nsb, blk.BLOCK_V).any(axis=1)
            return (new[None], delta.astype(jnp.int32)[None],
                    jnp.full((1, 1), changed.astype(jnp.int32)),
                    fb.astype(jnp.int32)[None])

        smap = functools.partial(jax.shard_map, mesh=self.mesh,
                                 check_vma=False)
        prep = jax.jit(smap(prep_body,
                            in_specs=(aux_specs, plane, plane) + qp_specs,
                            out_specs=(plane,)))
        win = jax.jit(smap(win_body,
                           in_specs=(wd_specs, plane, plane),
                           out_specs=(plane,)))
        apply_fn = jax.jit(smap(
            apply_body,
            in_specs=(arr_specs, aux_specs, plane, plane) + qp_specs,
            out_specs=(plane, plane, vec, plane if batched else vec)))
        fns = (prep, win, apply_fn)
        self._compiled[key] = fns
        return fns

    _STREAM_SHARD_NAMES = ("gr_src_local", "gr_dst_col", "gr_edge_valid",
                           "gr_edge_weight")

    def _stream_shardings(self):
        def shard(ndim):
            return NamedSharding(self.mesh, P(AXIS, *([None] * (ndim - 1))))

        out = {name: shard(2) for name in self._STREAM_SHARD_NAMES}
        out["gr_band"] = shard(3)
        return out

    def _stream_sweep(self, pf, win, sched, active, vals, partial, outs):
        """Walk one superstep's fetch schedule through the prefetcher,
        folding each window into the running partial.  ``outs`` maps each
        staging slot to the win output whose execution makes the slot safe
        to reuse (the depth-2 backpressure handle)."""
        if len(sched):
            k0 = int(sched[0])
            pf.submit(k0, active[:, k0], after=outs[pf.next_slot])
            for i, k in enumerate(sched):
                if i + 1 < len(sched):
                    nxt = int(sched[i + 1])
                    pf.submit(nxt, active[:, nxt],
                              after=outs[pf.next_slot])
                wd, slot = pf.take()
                (partial,) = win(wd, vals, partial)
                outs[slot] = pf.compute = partial
        return partial

    def _stream_record(self, pf, cfg, it, slots_total, slots_skipped,
                       **extra):
        """Publish one streamed run's prefetcher accounting into
        ``self.dispatch['stream']`` and fold the window-slot counts into
        the gate record."""
        overlap = (1.0 - pf.stall_s / pf.copy_s) if pf.copy_s > 0 else 1.0
        self.dispatch["stream"].update({
            "supersteps": it,
            "fetches": pf.fetches,
            "fetched_bytes": pf.bytes_read,
            "copy_s": pf.copy_s,
            "stall_s": pf.stall_s,
            "overlap_efficiency": max(0.0, min(1.0, overlap)),
            "edge_bandwidth_bytes_per_s":
                pf.bytes_read / pf.copy_s if pf.copy_s > 0 else 0.0,
            "fetch_slots": slots_total,
            "fetch_skipped": slots_skipped,
            "fetch_skip_fraction":
                slots_skipped / slots_total if slots_total else 0.0,
            "pipelined": bool(cfg.prefetch),
            **extra,
        })
        # window-granular slot accounting doubles as the gate record
        self._gate_skipped += slots_skipped
        self._gate_slots += slots_total

    def _run_streamed(self, program, gate) -> tuple[np.ndarray, int]:
        """The out-of-core superstep driver: per superstep, walk the edge
        windows through the double-buffered prefetcher and fold each into
        the running phase-1 partial; then one apply step.  The host loop
        replicates the resident barrier loop's semantics exactly -- same
        all-ones initial frontier, same frontier masking, same global
        ``changed`` termination -- so min-monoid programs are bit-exact
        against ``residency='resident'`` with identical iteration counts
        (add monoids differ only by float association across windows).

        Host-side frontier gating: with ``gate``, a (rectangle, window)
        slot whose band source blocks miss the live frontier is never even
        READ from the source -- gating saves host->device bandwidth, not
        just compute -- and windows with no active rectangle at all drop
        out of the fetch schedule entirely.
        """
        sb = self._source
        cfg = self.stream or StreamConfig()
        prep, win, apply_fn = self._stream_fns(program)
        comb = program.combiner
        _, cols, kc = self._grid_meta
        nw = sb.num_windows
        nsb = self._gate_nsb
        state = jnp.asarray(program.init(self.pg))
        frontier = jnp.ones((self._C, self._K), jnp.int32)
        fixed = program.fixed_iters is not None
        limit = program.fixed_iters if fixed else program.max_iters
        gate_masks = sb.gate_masks(nsb) if gate else None  # [P, nw, nsb]
        fb_host = np.ones((self._C, nsb), dtype=bool)
        pf = _StreamPrefetcher(sb, self._stream_shardings(),
                               pipelined=cfg.prefetch)
        it = 0
        changed = True
        slots_total = slots_skipped = 0
        outs = {0: None, 1: None}  # per staging slot: the win output whose
        #                            execution makes the slot safe to reuse
        try:
            while changed and it < limit:
                (vals,) = prep(self.aux, state, frontier)
                pf.compute = vals
                if gate_masks is not None:
                    active = sb.active_windows(gate_masks, fb_host)
                else:
                    active = np.ones((self._C, nw), dtype=bool)
                sched = np.flatnonzero(active.any(axis=0))
                slots_total += self._C * nw
                slots_skipped += self._C * nw - int(active.sum())
                partial = jnp.full((self._C, cols * kc), comb.identity,
                                   state.dtype)
                partial = self._stream_sweep(pf, win, sched, active, vals,
                                             partial, outs)
                state, delta, changed_dev, fb = apply_fn(
                    self.arrays, self.aux, partial, state)
                pf.compute = state
                it += 1
                if not fixed:
                    changed = bool(
                        np.asarray(jax.device_get(changed_dev))[0, 0])
                    frontier = delta
                    fb_host = np.asarray(jax.device_get(fb)).astype(bool)
        finally:
            pf.close()
        self._stream_record(pf, cfg, it, slots_total, slots_skipped)
        final = np.asarray(jax.device_get(state)).reshape(-1)
        return final[self.pg.global_to_local], it

    def _run_streamed_batch(self, program, B, state, frontier, qp, gate):
        """Streamed twin of ``_run_batch_segment`` (DESIGN.md section 15):
        the out-of-core window schedule over the batched [*, B] query
        plane.  One prefetched edge-window upload serves ALL B query
        columns of each window fold, so edge H2D bytes per query drop
        B-fold against B single-query streamed runs.

        Per-query convergence runs on the host exactly as the resident
        batched ``while_loop`` runs on device: ``q_it[b]`` counts the
        supersteps entered while query b's column was still active
        (monotone non-increasing for min monoids), and the global loop
        runs while ANY query is active -- so the per-query counts match
        the resident plane (and a sequential run of each query) exactly.

        Union-frontier gating: a (rectangle, window) slot is fetched iff
        its band source blocks intersect the frontier of AT LEAST ONE
        live query column (``ShardSource.active_windows`` over the
        [P, nsb, B] block plane) -- sound because a skipped window is
        provably dead for every query, and each fetch is shared by all.
        Returns ``(state_device, q_it)``.
        """
        sb = self._source
        cfg = self.stream or StreamConfig()
        prep, win, apply_fn = self._stream_fns(program, B)
        comb = program.combiner
        _, cols, kc = self._grid_meta
        nw = sb.num_windows
        nsb = self._gate_nsb
        fixed = program.fixed_iters is not None
        limit = program.fixed_iters if fixed else program.max_iters
        gate_masks = sb.gate_masks(nsb) if gate else None  # [P, nw, nsb]
        fb_host = np.ones((self._C, nsb, B), dtype=bool)
        active_q = np.ones(B, dtype=bool)
        q_it = np.zeros(B, np.int64)
        qp_args = () if qp is None else (qp,)
        pf = _StreamPrefetcher(sb, self._stream_shardings(),
                               pipelined=cfg.prefetch)
        it = 0
        slots_total = slots_skipped = 0
        outs = {0: None, 1: None}
        try:
            while active_q.any() and it < limit:
                (vals,) = prep(self.aux, state, frontier, *qp_args)
                pf.compute = vals
                if gate_masks is not None:
                    # quiesced columns are already all-zero in fb_host; the
                    # explicit mask documents the union-over-LIVE-queries
                    # contract and keeps padding columns inert
                    fb = fb_host & active_q[None, None, :]
                    active = sb.active_windows(gate_masks, fb)
                else:
                    active = np.ones((self._C, nw), dtype=bool)
                sched = np.flatnonzero(active.any(axis=0))
                slots_total += self._C * nw
                slots_skipped += self._C * nw - int(active.sum())
                partial = jnp.full((self._C, cols * kc, B), comb.identity,
                                   state.dtype)
                partial = self._stream_sweep(pf, win, sched, active, vals,
                                             partial, outs)
                state, delta, changed_q, fb_dev = apply_fn(
                    self.arrays, self.aux, partial, state, *qp_args)
                pf.compute = state
                q_it += active_q
                it += 1
                if fixed:
                    continue  # every column runs the full counted loop
                active_q = np.asarray(jax.device_get(changed_q))[0] > 0
                frontier = delta
                fb_host = np.asarray(jax.device_get(fb_dev)).astype(bool)
        finally:
            pf.close()
        self._stream_record(
            pf, cfg, it, slots_total, slots_skipped, batch=B,
            fetched_bytes_per_query=pf.bytes_read / B)
        return state, q_it

    # -- batched multi-query execution (DESIGN.md section 11) ----------------

    def _smap_batch(self, body, qplane=False):
        """shard_map wrapper for the batched plane: state/frontier are
        [C, K, B] (chare-sharded on the leading axis, batch trailing), the
        step bound [C, 1], outputs (state, frontier, per-query iters,
        per-shard skipped launches).  ``qplane`` adds the per-query
        read-only operand (personalized teleport vectors), sharded exactly
        like the state plane."""
        arr_specs = {k: P(AXIS, *([None] * (v.ndim - 1)))
                     for k, v in self.arrays.items()}
        aux_specs = {k: P(AXIS, None) for k in self.aux}
        qp_specs = (P(AXIS, None, None),) if qplane else ()
        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(arr_specs, aux_specs, P(AXIS, None, None),
                      P(AXIS, None, None)) + qp_specs + (P(AXIS, None),),
            out_specs=(P(AXIS, None, None), P(AXIS, None, None),
                       P(AXIS, None), P(AXIS, None)),
            check_vma=False)

    def _make_batch_body(self, program, sync="barrier", gate=False):
        """The superstep loop over a [K, B] query plane, with PER-QUERY
        convergence masking and iteration counting.

        One edge sweep serves all B columns (the strategies and kernels are
        rank-polymorphic over the trailing axis).  A query whose column
        stopped changing sends the combiner identity forever after (its
        frontier column is all-zero), so finished queries stop contributing
        work, and ``q_it`` counts -- per query -- exactly the supersteps a
        sequential run of that query would have executed: ``active[b]`` is
        monotone non-increasing (a quiesced min-monoid column can never
        reactivate), and the global loop runs while any query is active, so
        extra supersteps past a query's own convergence are no-ops for it.
        """
        comb = program.combiner
        convergence = program.fixed_iters is None
        has_qp = program.query_plane is not None

        def body(arrs, aux, s0, f0, *rest):
            qp, nsteps = (rest if has_qp else (None,) + rest)
            arrs = {k: v[0] for k, v in arrs.items()}
            # aux planes are per-vertex [K]; expose them as [K, 1] so the
            # program's update/apply lambdas broadcast over the batch axis
            aux = {k: v[0][:, None] for k, v in aux.items()}
            if has_qp:
                # the per-query operand is already [K, B]: merged after the
                # broadcast expansion so programs read it at full rank
                aux["qplane"] = qp[0]
            push = self._push_closure(program, gate, arrs)
            p2 = lambda partial: self._phase2(partial, arrs, comb)
            sent = jnp.asarray(comb.identity, s0.dtype)
            limit = nsteps[0, 0]
            B = s0.shape[-1]
            zero_sk = jnp.asarray(0, jnp.int32)

            def active_of(frontier):
                # per-query "did anything change last step", across chares
                return jax.lax.psum(
                    frontier.any(axis=0).astype(jnp.int32), AXIS) > 0

            if convergence and sync == "overlap":
                # per-query double-check quiescence: a query stays active
                # until TWO consecutive applies leave its column unchanged;
                # ``q_it`` counts each query's own overlap supersteps
                f_in = f0[0] != 0
                part0, active0 = push(
                    jnp.where(f_in, program.update(s0[0], aux), sent), f_in)

                def cond(carry):
                    _, _, _, q_quiet, it, _, _ = carry
                    return jnp.logical_and((q_quiet < 2).any(), it < limit)

                def step(carry):
                    state, frontier, pending, q_quiet, it, q_it, sk = carry
                    live = q_quiet < 2
                    incoming = p2(pending)
                    vals = jnp.where(frontier, program.update(state, aux),
                                     sent)
                    new_pending, active = push(vals, frontier)
                    new = program.apply(state, incoming, aux)
                    delta = new != state
                    changed = active_of(delta)
                    q_quiet = jnp.where(changed, 0, q_quiet + 1)
                    return (new, delta, new_pending, q_quiet, it + 1,
                            q_it + live.astype(jnp.int32),
                            sk + 1 - active.astype(jnp.int32))

                state, frontier, pending, _, it, q_it, sk = \
                    jax.lax.while_loop(
                        cond, step,
                        (s0[0], jnp.zeros_like(f_in), part0,
                         jnp.zeros((B,), jnp.int32), jnp.asarray(0),
                         jnp.zeros((B,), jnp.int32),
                         zero_sk + 1 - active0.astype(jnp.int32)))
                drained = program.apply(state, p2(pending), aux)
                frontier = frontier | (drained != state)
                state = drained
            elif convergence:

                def cond(carry):
                    _, _, active, it, _, _ = carry
                    return jnp.logical_and(active.any(), it < limit)

                def step(carry):
                    state, frontier, active, it, q_it, sk = carry
                    vals = jnp.where(frontier,
                                     program.update(state, aux), sent)
                    partial, pushed = push(vals, frontier)
                    new = program.apply(state, p2(partial), aux)
                    delta = new != state
                    changed = active_of(delta)
                    return (new, delta, changed, it + 1,
                            q_it + active.astype(jnp.int32),
                            sk + 1 - pushed.astype(jnp.int32))

                active0 = active_of(f0[0] != 0)
                state, frontier, _, it, q_it, sk = jax.lax.while_loop(
                    cond, step,
                    (s0[0], f0[0] != 0, active0, jnp.asarray(0),
                     jnp.zeros((B,), jnp.int32), zero_sk))
            else:
                # fixed-iteration programs (pagerank family): every query
                # column runs exactly ``limit`` supersteps, so the per-query
                # convergence mask degenerates to a constant -- skip it
                # entirely and run the plain counted loop (DESIGN.md
                # section 14).  The frontier is all-ones throughout (no
                # gating: _validate_async already rejects gate='frontier'
                # for fixed-iter programs).
                ones = jnp.ones_like(f0[0] != 0)

                def fstep(_, state):
                    partial, _ = push(program.update(state, aux), ones)
                    return program.apply(state, p2(partial), aux)

                state = jax.lax.fori_loop(0, limit, fstep, s0[0])
                frontier = ones
                it = limit
                q_it = jnp.full((B,), limit, jnp.int32)
                sk = zero_sk
            slots = it + (1 if (convergence and sync == "overlap") else 0)
            stats = jnp.stack([sk, slots.astype(jnp.int32)])[None]
            return (state[None], frontier.astype(jnp.int32)[None],
                    q_it[None], stats)

        return body

    @staticmethod
    def _bucket(n: int) -> int:
        """B-bucket: round the query count up to the next power of two so
        steady-state traffic hits a handful of compiled shapes."""
        return 1 << max(n - 1, 0).bit_length()

    @staticmethod
    def _batch_key(program, B: int) -> tuple:
        """Compile-cache key for the batched plane: the program key minus
        its seed params (seeds live in the STATE, not the traced program --
        any source list of the same bucket reuses the compilation), plus
        the B bucket."""
        key = tuple(kv for kv in program.key
                    if not (isinstance(kv, tuple)
                            and kv[0] in ("source", "sources", "pivots",
                                          "seeds")))
        return key + (("batch", B),)

    def _run_batch_segment(self, program, B, state, frontier, nsteps,
                           sync="barrier", gate=False, qp=None):
        key = (self._batch_key(program, B), "segment", sync, gate)
        fn = self._compiled.get(key)
        if fn is None:
            fn = jax.jit(self._smap_batch(
                self._make_batch_body(program, sync, gate),
                qplane=program.query_plane is not None))
            self._compiled[key] = fn
        bound = jnp.full((self._C, 1), nsteps, jnp.int32)
        operands = (state, frontier) + (() if qp is None else (qp,))
        state, frontier, q_it, stats = fn(self.arrays, self.aux, *operands,
                                          bound)
        stats = np.asarray(jax.device_get(stats))
        self._gate_skipped += int(stats[:, 0].sum())
        self._gate_slots += int(stats[:, 1].sum())
        return state, frontier, np.asarray(jax.device_get(q_it))[0]

    def _run_batch_replanned(self, program, B, padded_sets, state, frontier,
                             policy, sync="barrier", gate=False, qp=None):
        """Batched twin of ``_run_replanned``: the skew trigger sees the
        frontier collapsed over queries (a vertex is frontier-active if ANY
        query still touches it), and the state move carries the whole
        [*, K, B] plane through the on-device relabel gather."""
        policy = self._resolve_replan_policy(policy)
        limit = (program.fixed_iters if program.fixed_iters is not None
                 else program.max_iters)
        q_iters = np.zeros(B, np.int64)
        done, replans = 0, 0
        while done < limit:
            state, frontier, q_it = self._run_batch_segment(
                program, B, state, frontier, min(policy.every, limit - done),
                sync, gate, qp)
            q_iters += q_it
            # the longest-still-active query is active for every executed
            # superstep, so its count IS the segment's global step count
            done += int(q_it.max())
            f_host = np.asarray(jax.device_get(frontier))
            if program.fixed_iters is None and not f_host.any():
                break  # all queries quiesced
            if done >= limit or replans >= policy.max_replans:
                continue
            f_any = f_host.any(axis=-1).astype(np.int32)
            if not self._should_replan(policy, f_any):
                continue
            new_plan = part_mod.make_plan(self.pg.graph, self._C,
                                          policy.partitioner)
            if new_plan.same_as(self.pg.plan):
                continue  # no-op switch: keep the resident layout
            new_pg = self.pg.repartition(policy.partitioner, plan=new_plan)
            init = program.init_batch(new_pg, padded_sets)
            state, frontier = self._move_state(init, state, frontier, new_pg)
            self._rebind(new_pg)
            if program.query_plane is not None:
                # the read-only per-query operand is a pure function of the
                # placement: rebuild for the new layout instead of relabeling
                qp = jnp.asarray(program.query_plane(new_pg, padded_sets))
            replans += 1
        return state, q_iters

    def run_batch(self, program, sources=None, batch=None, replan=None,
                  sync="barrier", gate=None, **params
                  ) -> tuple[np.ndarray, np.ndarray]:
        """Run B queries of one program in a single batched sweep.

        ``sources`` is a sequence of queries -- each an original vertex id
        or an iterable of ids (a seed set); defaults to the program's own
        ``sources`` (betweenness pivots).  ``batch`` fixes the compiled
        plane width B (>= the query count); by default the count is rounded
        up to the next power of two (the B-bucket compile-cache policy).
        Padding columns re-run query 0 and are dropped on the way out.
        ``sync``/``gate`` relax the superstep barrier exactly as in ``run``.

        On an ``Engine(residency='stream')`` the plane runs the out-of-core
        window schedule (DESIGN.md section 15): each prefetched edge-window
        upload is swept against all B query columns, so edge H2D bytes per
        query drop B-fold (accounted in ``dispatch['stream']``); per-query
        iteration counts and min-monoid values match the resident plane
        bit-exactly.  ``replan`` and ``sync='overlap'`` stay resident-only.

        Returns ``(plane, iters)``: ``plane[i]`` is query i's converged
        per-vertex state in original vertex order ([n, V]), ``iters[i]``
        the supersteps query i needed (identical to its sequential count
        under ``sync='barrier'``; the query's own double-check overlap count
        under ``sync='overlap'``).
        """
        from repro.core import programs as prog_mod

        if isinstance(program, str):
            program = prog_mod.make_program(program, **params)
        elif params:
            raise TypeError("params only apply to registered program names")
        if program.init_batch is None:
            raise ValueError(
                f"program {program.name!r} has no batched init "
                f"(VertexProgram.init_batch); run it with Engine.run")
        if self.residency == "stream":
            if replan is not None:
                raise ValueError(
                    "replan is a resident-path feature: the streamed "
                    "schedule has no segment checkpoints to relabel at; "
                    "run replan=... on an Engine(residency='resident') of "
                    "the same graph, or drop it for the streamed schedule")
            if sync != "barrier":
                raise ValueError(
                    "residency='stream' already pipelines H2D copies "
                    "behind compute; run sync='overlap' on a resident "
                    "Engine, or keep the default sync='barrier' here")
        sync, gate = self._validate_async(program, sync, gate)
        if sources is None:
            sources = program.sources
        if sources is not None and not isinstance(sources, (int, np.integer)):
            sources = tuple(sources)
            if not sources:
                # caught here (not at the padding line below) so callers --
                # the serving loop above all -- get an actionable message
                raise ValueError("run_batch needs at least one query "
                                 "(sources is empty)")
        sets = prog_mod.seed_sets(sources)
        n = len(sets)
        B = self._bucket(n) if batch is None else int(batch)
        if B < n:
            raise ValueError(f"batch={B} is smaller than {n} queries")
        padded = sets + (sets[0],) * (B - n)
        state = jnp.asarray(program.init_batch(self.pg, padded))
        frontier = jnp.ones((self._C, self._K, B), jnp.int32)
        qp = (jnp.asarray(program.query_plane(self.pg, padded))
              if program.query_plane is not None else None)
        limit = (program.fixed_iters if program.fixed_iters is not None
                 else program.max_iters)
        self._gate_skipped = self._gate_slots = 0
        if self.residency == "stream":
            state, q_it = self._run_streamed_batch(program, B, state,
                                                   frontier, qp, gate)
        elif replan is not None:
            state, q_it = self._run_batch_replanned(program, B, padded,
                                                    state, frontier, replan,
                                                    sync, gate, qp)
        else:
            state, _, q_it = self._run_batch_segment(program, B, state,
                                                     frontier, limit, sync,
                                                     gate, qp)
        self._record_gate(sync, gate)
        # un-permute each query column to original vertex order (for grids,
        # g2l points at the column-0 replica slots)
        plane = np.asarray(jax.device_get(state)).reshape(
            self._C * self._K, B)[self.pg.global_to_local]
        out = plane.T[:n].copy()
        if program.finalize_batch is not None:
            out = program.finalize_batch(self.pg.graph, sets, out)
        return out, np.asarray(q_it[:n], np.int64)

    def _move_state(self, init_state, state, frontier, new_pg):
        """Carry state across a replan: plan B's ``g2l`` on top of plan A's
        ``l2g`` (the composed relabel, ``PartitionPlan.padded_map_from``)
        scatters live slots; padding gets the program's own init fill
        (``init_state``, built by the caller for the new partition), so
        min-monoid programs stay bit-exact.  The frontier rides along (new
        padding enters quiesced).

        The composed relabel is just a gather with unique targets, so the
        state never round-trips through the host: only the (host-resident)
        plan metadata decides ``src``/``tgt``; the state/frontier planes are
        moved by one jitted ``.at[tgt].set(old[src])`` on device.  Planes
        are shape-polymorphic: a trailing batch axis ([P, K, B]) rides
        through unchanged.

        1-D <-> 2-D switches compose through the same algebra on the ROW
        maps (``partitioners.row_plan_of``): a grid's state is its row plan
        replicated per column, so the move reads the old column-0 replica,
        scatters through the composed row relabel, and re-replicates into
        the new shape -- for 1-D <-> 1-D the replica count is 1 on both
        sides and this is exactly the original move.
        """
        move = part_mod.row_plan_of(new_pg.plan).padded_map_from(
            part_mod.row_plan_of(self.pg.plan))
        live = move >= 0
        src = jnp.asarray(np.nonzero(live)[0])
        tgt = jnp.asarray(move[live])
        old_cols = self.pg.grid_shape[1] if self.pg.is_grid else 1
        new_cols = new_pg.grid_shape[1] if new_pg.is_grid else 1
        old_rows = self.pg.num_chunks // old_cols
        new_rows = new_pg.num_chunks // new_cols
        k_old, k_new = self.pg.chunk_size, new_pg.chunk_size

        def rows_of(a, n_rows, n_cols, k):
            """Column-0 replica of a [P, K, ...] plane, flat in row space."""
            a = a.reshape((n_rows, n_cols, k) + a.shape[2:])[:, 0]
            return a.reshape((n_rows * k,) + a.shape[2:])

        def replicate(a):
            """Row-space plane -> the new partition's replicated [P, K, ...]."""
            tail = a.shape[1:]
            a = a.reshape((new_rows, 1, k_new) + tail)
            a = jnp.broadcast_to(a, (new_rows, new_cols, k_new) + tail)
            return a.reshape((new_pg.num_chunks, k_new) + tail)

        init_rows = rows_of(jnp.asarray(init_state), new_rows, new_cols,
                            k_new)
        old_rows_flat = rows_of(state, old_rows, old_cols, k_old)
        new_state = _relabel_gather(init_rows, old_rows_flat, src, tgt)
        f_rows = rows_of(frontier, old_rows, old_cols, k_old)
        f_init = jnp.zeros((new_rows * k_new,) + f_rows.shape[1:], jnp.int32)
        new_f = _relabel_gather(f_init, f_rows.astype(jnp.int32), src, tgt)
        return replicate(new_state), replicate(new_f)

    def _should_replan(self, policy, frontier_host) -> bool:
        if policy.mode == "always":
            return True
        stats = part_mod.partition_stats(self.pg, frontier=frontier_host)
        return stats["frontier_edge_imbalance"] > policy.threshold

    def _resolve_replan_policy(self, policy) -> ReplanPolicy:
        """Validate a replan request at run() entry, not hundreds of
        supersteps later when the skew trigger first fires: the target must
        name a known policy, and a grid must preserve the chare count (one
        mesh shard per rectangle)."""
        if isinstance(policy, str):
            policy = ReplanPolicy(partitioner=policy)
        part_mod.get_partitioner(policy.partitioner)
        shape = part_mod.grid_shape(policy.partitioner)
        if shape is not None and shape[0] * shape[1] != self._C:
            raise ValueError(
                f"replan target {policy.partitioner!r} needs "
                f"{shape[0] * shape[1]} chares, engine has {self._C}")
        return policy

    def _run_replanned(self, program, policy, sync="barrier", gate=False
                       ) -> tuple[np.ndarray, int]:
        """Segmented superstep driver with mid-run repartitioning.

        Replans only ever fire at segment boundaries, and under
        ``sync='overlap'`` each segment drains its in-flight double buffer
        before returning (``_make_segment_body``), so the relabel never has
        a partial to misroute: the overlap/replan interaction is safe by
        construction.
        """
        policy = self._resolve_replan_policy(policy)
        limit = (program.fixed_iters if program.fixed_iters is not None
                 else program.max_iters)
        state = jnp.asarray(program.init(self.pg))
        frontier = jnp.ones((self._C, self._K), jnp.int32)
        done, replans = 0, 0
        while done < limit:
            state, frontier, it = self._run_segment(
                program, state, frontier, min(policy.every, limit - done),
                sync, gate)
            done += it
            f_host = np.asarray(jax.device_get(frontier))
            if program.fixed_iters is None and not f_host.any():
                break  # quiesced: last superstep changed nothing
            if done >= limit or replans >= policy.max_replans:
                continue
            if not self._should_replan(policy, f_host):
                continue
            new_plan = part_mod.make_plan(self.pg.graph, self._C,
                                          policy.partitioner)
            if new_plan.same_as(self.pg.plan):
                continue  # no-op switch: keep the resident layout
            new_pg = self.pg.repartition(policy.partitioner, plan=new_plan)
            state, frontier = self._move_state(program.init(new_pg), state,
                                               frontier, new_pg)
            self._rebind(new_pg)
            replans += 1
        final = np.asarray(jax.device_get(state)).reshape(-1)
        return final[self.pg.global_to_local], done

    def _validate_async(self, program, sync, gate) -> tuple[str, bool]:
        """Normalize/validate the barrier-relaxation knobs against the
        program's algebra: overlap delivers stale reads, which only
        label-correcting min-monoid convergence programs absorb; gating
        needs a frontier, which only convergence programs maintain."""
        if sync not in ("barrier", "overlap"):
            raise ValueError(f"unknown sync mode {sync!r}; "
                             "choose 'barrier' or 'overlap'")
        if sync == "overlap" and (program.fixed_iters is not None
                                  or program.combiner.name != "min"):
            raise ValueError(
                f"sync='overlap' needs a min-monoid convergence program "
                f"(stale reads stay convergent only for label-correcting "
                f"updates); {program.name!r} is not one")
        if gate in (None, False, 0):
            gate = False
        elif gate in (True, "frontier"):
            if program.fixed_iters is not None:
                raise ValueError(
                    f"gate='frontier' needs a convergence program (the gate "
                    f"reads the frontier); {program.name!r} has fixed iters")
            gate = True
        else:
            raise ValueError(f"unknown gate mode {gate!r}; "
                             "choose None or 'frontier'")
        return sync, gate

    def _record_gate(self, sync, gate):
        """Publish the run's launch accounting into ``self.dispatch`` --
        per-shard phase-1 launch slots, how many the frontier gate skipped,
        and the fraction (0.0 when gating is off or nothing was skipped)."""
        slots, skipped = self._gate_slots, self._gate_skipped
        self.dispatch["gate"] = {
            "sync": sync, "enabled": gate, "launch_slots": slots,
            "skipped_launches": skipped, "launched": slots - skipped,
            "skipped_fraction": skipped / slots if slots else 0.0,
        }

    def run(self, program, replan=None, sync="barrier", gate=None,
            residency=None, **params) -> tuple[np.ndarray, int]:
        """Run a vertex program to completion; returns (state, iterations).

        ``program`` is a registered name (params forwarded to its factory)
        or a ``VertexProgram`` instance.  ``replan`` (a partitioner name or
        a ``ReplanPolicy``) enables mid-run repartitioning: the loop runs in
        jitted segments and the placement may switch at segment boundaries
        (DESIGN.md section 9); without it the whole loop is one jitted
        program, exactly as before.

        ``sync='overlap'`` relaxes the superstep barrier for min-monoid
        convergence programs: phase 2 of superstep t overlaps phase 1 of
        t+1 through a double-buffered partial, updates land with staleness
        1, and termination uses double-check quiescence -- same fixpoint,
        measured per-superstep time no longer pays the full barrier.
        ``gate='frontier'`` skips the phase-1 push of shards whose live
        frontier cannot reach their edges (band-block intersection test);
        the launch accounting lands in ``self.dispatch['gate']``.  Both
        compose with ``replan`` (segments drain before any relabel).

        ``residency='stream'`` (on an engine built with it) runs the
        out-of-core window schedule instead of the whole-loop jit: edge
        shards stream host->device through the double-buffered prefetcher
        (DESIGN.md section 13), composing with ``gate='frontier'`` (gated
        slots are never fetched) but not with ``replan`` or
        ``sync='overlap'``.  Metrics land in ``self.dispatch['stream']``.
        """
        from repro.core import programs as prog_mod

        if isinstance(program, str):
            program = prog_mod.make_program(program, **params)
        elif params:
            raise TypeError("params only apply to registered program names")

        residency = self.residency if residency is None else residency
        if residency not in ("resident", "stream"):
            raise ValueError(f"unknown residency {residency!r}; "
                             "choose 'resident' or 'stream'")
        if residency == "stream":
            if self.residency != "stream":
                raise ValueError(
                    "this engine is bound resident; build it with "
                    "Engine(..., residency='stream') so the edge planes "
                    "are never uploaded in the first place")
            if replan is not None:
                raise ValueError(
                    "replan is a resident-path feature: the streamed "
                    "schedule has no segment checkpoints to relabel at; "
                    "run replan=... on an Engine(residency='resident') of "
                    "the same graph, or drop it for the streamed schedule")
            if sync != "barrier":
                raise ValueError(
                    "residency='stream' already pipelines H2D copies "
                    "behind compute; run sync='overlap' on a resident "
                    "Engine, or keep the default sync='barrier' here")
            if (program.sources is not None
                    and program.init_batch is not None
                    and program.finalize is not None):
                # inherently multi-source programs ride the batched query
                # plane, which streams too (DESIGN.md section 15): one
                # edge-window upload serves every query column
                sets = prog_mod.seed_sets(program.sources)
                plane, q_it = self.run_batch(
                    program, sources=program.sources, sync=sync, gate=gate)
                return (program.finalize(self.pg.graph, sets, plane),
                        int(q_it.max()))
            _, gate = self._validate_async(program, sync, gate)
            self._gate_skipped = self._gate_slots = 0
            out = self._run_streamed(program, gate)
            self._record_gate(sync, gate)
            return out
        if self.residency == "stream":
            raise ValueError(
                "this engine is bound with residency='stream' and holds no "
                "resident edge planes; build a resident Engine for "
                "residency='resident' runs")

        if (program.sources is not None and program.init_batch is not None
                and program.finalize is not None):
            # inherently multi-source programs (betweenness pivots) run on
            # the batched plane and post-process the per-query rows; the
            # iteration count is the global superstep count (max over
            # queries), matching what one batched sweep executes
            sets = prog_mod.seed_sets(program.sources)
            plane, q_it = self.run_batch(program, sources=program.sources,
                                         replan=replan, sync=sync, gate=gate)
            return (program.finalize(self.pg.graph, sets, plane),
                    int(q_it.max()))

        sync, gate = self._validate_async(program, sync, gate)
        self._gate_skipped = self._gate_slots = 0
        if replan is not None:
            out = self._run_replanned(program, replan, sync, gate)
            self._record_gate(sync, gate)
            return out

        key = (program.key, sync, gate)
        s0 = jnp.asarray(program.init(self.pg))
        fn = self._compiled.get(key)
        if fn is None:
            # the state buffer is consumed by the superstep loop: donate it
            # so the loop carry reuses its allocation.  The CPU backend does
            # not implement donation (it would only warn), so ask the mesh.
            donate = self.mesh.devices.flat[0].platform in ("tpu", "gpu")
            fn = jax.jit(self._smap(self._make_body(program, sync, gate),
                                    n_out=3),
                         donate_argnums=(2,) if donate else ())
            self._compiled[key] = fn
        state, iters, stats = fn(self.arrays, self.aux, s0)
        stats = np.asarray(jax.device_get(stats))
        self._gate_skipped += int(stats[:, 0].sum())
        self._gate_slots += int(stats[:, 1].sum())
        self._record_gate(sync, gate)
        # un-permute: padded-id state -> original vertex order (the relabel
        # invariant -- callers always see original ids; DESIGN.md sec. 7)
        state = jax.device_get(state).reshape(-1)[self.pg.global_to_local]
        return state, int(jax.device_get(iters)[0, 0])

    def step_hlo(self, program, **params) -> str:
        """Optimized HLO text of ONE compiled propagate superstep (phase 1
        + phase 2 + apply, no iteration loop) -- the input to collective
        wire-byte analysis (``repro.launch.hloanalysis.analyze``), which is
        how the grouped-vs-full grid2d lowering comparison is *measured*
        rather than only modeled (``cost.grid_collective_bytes``)."""
        from repro.core import programs as prog_mod

        if self.residency == "stream":
            raise ValueError("step_hlo needs the resident edge planes; "
                             "build a resident Engine")

        if isinstance(program, str):
            program = prog_mod.make_program(program, **params)
        comb = program.combiner

        def body(arrs, aux, s0):
            arrs = {k: v[0] for k, v in arrs.items()}
            aux = {k: v[0] for k, v in aux.items()}
            incoming = self._propagate(program.update(s0[0], aux), arrs,
                                       comb, program.edge_value,
                                       program.edge_semiring)
            return (program.apply(s0[0], incoming, aux)[None],)

        s0 = jnp.asarray(program.init(self.pg))
        fn = jax.jit(self._smap(body, n_out=1))
        return fn.lower(self.arrays, self.aux, s0).compile().as_text()

    # -- thin per-algorithm wrappers ----------------------------------------

    def pagerank(self, alpha: float = 0.85, iters: int = 20) -> np.ndarray:
        """Push PageRank: a <- (1-alpha) + sum_in alpha * a_prev / d."""
        return self.run("pagerank", alpha=alpha, iters=iters)[0]

    def labelprop(self, max_iters: int = 10_000) -> tuple[np.ndarray, int]:
        """Min-label propagation to convergence. Returns (labels, iterations)."""
        return self.run("labelprop", max_iters=max_iters)

    def sssp(self, source: int = 0, max_iters: int = 10_000
             ) -> tuple[np.ndarray, int]:
        """Single-source shortest paths (min-plus over edge weights)."""
        return self.run("sssp", source=source, max_iters=max_iters)

    def bfs(self, source: int = 0, max_iters: int = 10_000
            ) -> tuple[np.ndarray, int]:
        """BFS reachability depth (min over hop counts)."""
        return self.run("bfs", source=source, max_iters=max_iters)

    def pagerank_weighted(self, alpha: float = 0.85, iters: int = 20
                          ) -> np.ndarray:
        """Weight-normalized push PageRank."""
        return self.run("pagerank_weighted", alpha=alpha, iters=iters)[0]

    def betweenness(self, pivots=(0, 1, 2, 3), max_iters: int = 10_000
                    ) -> tuple[np.ndarray, int]:
        """Approximate betweenness: batched multi-pivot BFS + Brandes."""
        return self.run("betweenness", pivots=tuple(pivots),
                        max_iters=max_iters)
