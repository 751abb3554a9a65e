"""Graph representation and chare-style partitioning.

The paper assigns contiguous chunks of vertices to actors (chares); each chare
stores its local vertices in index order plus the destinations of their
outgoing edges (CSR).  ``Graph`` is the global CSR; ``PartitionedGraph`` is the
chare decomposition with SPMD-friendly (padded, rectangular) per-chunk arrays.

Which vertices land on which chare is delegated to the pluggable partitioner
layer (``repro.core.partitioners``): ``partition(graph, C, partitioner=...)``
obtains a ``PartitionPlan`` (vertex permutation + chunk bounds), relabels every
edge into the permuted "padded id" space, and records the global<->local
relabel arrays the engine uses to keep original vertex ids at the API boundary
(see DESIGN.md "Partitioning").  A ``grid(R,C)`` partitioner yields a
``GridPlan`` instead and the decomposition becomes 2-D: one chare per *edge
rectangle* (src-row-chunk x dst-col-chunk), vertex state row-replicated, and
a single column-space edge layout for the two-phase reduce (DESIGN.md
section 10).  All layout builds are vectorized (argsort/bincount bucketing)
-- no per-chunk Python loops -- so graph prep scales to the larger RMAT
sizes.

Real datasets from the paper (soc-LiveJournal1, twitter_rv, uk-2007-05) are not
available offline; the registry provides *scaled synthetic stand-ins* with the
same edge/vertex ratios (14.2x, 23.8x, 35.3x) generated with an RMAT-style
power-law sampler, which preserves the skew that drives the paper's
load-imbalance observations.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

from repro.core import partitioners as part_mod
from repro.kernels import blocks

INT = np.int32
WEIGHT = np.float32


@dataclasses.dataclass(frozen=True)
class Graph:
    """Global CSR graph: ``dst[indptr[v]:indptr[v+1]]`` are v's out-neighbors.

    ``weight`` (optional) is aligned with ``dst``: ``weight[e]`` is the weight
    of edge e in CSR order.  ``None`` means unweighted; consumers that need
    weights use ``edge_weights`` which substitutes ones.
    """

    num_vertices: int
    indptr: np.ndarray  # [V+1] int64
    dst: np.ndarray  # [E] int32
    directed: bool = True
    weight: np.ndarray | None = None  # [E] float32 or None

    @property
    def num_edges(self) -> int:
        return int(self.dst.shape[0])

    @property
    def out_degrees(self) -> np.ndarray:
        return np.diff(self.indptr).astype(INT)

    @property
    def src(self) -> np.ndarray:
        """COO source array, derived from indptr."""
        return np.repeat(
            np.arange(self.num_vertices, dtype=INT), self.out_degrees
        )

    @property
    def edge_weights(self) -> np.ndarray:
        """Weights in CSR edge order; ones when unweighted."""
        if self.weight is None:
            return np.ones(self.num_edges, dtype=WEIGHT)
        return self.weight

    def with_weight(self, weight: np.ndarray) -> "Graph":
        """Attach a per-edge weight array (CSR edge order)."""
        weight = np.asarray(weight, dtype=WEIGHT)
        if weight.shape != (self.num_edges,):
            raise ValueError(f"weight shape {weight.shape} != ({self.num_edges},)")
        return dataclasses.replace(self, weight=weight)

    def to_undirected(self) -> "Graph":
        """Add reverse edges (dedup), as the paper does for label propagation.

        For weighted graphs the dedup keeps the *minimum* weight per
        (u, v) pair -- the convention that preserves shortest paths.
        """
        src, dst = self.src, self.dst
        fwd = src.astype(np.int64) * self.num_vertices + dst
        rev = dst.astype(np.int64) * self.num_vertices + src
        if self.weight is None:
            keys = np.unique(np.concatenate([fwd, rev]))
            w = None
        else:
            both = np.concatenate([fwd, rev])
            wboth = np.concatenate([self.weight, self.weight])
            order = np.argsort(both, kind="stable")
            both, wboth = both[order], wboth[order]
            first = np.ones(len(both), dtype=bool)
            first[1:] = both[1:] != both[:-1]
            keys = both[first]
            w = np.minimum.reduceat(wboth, np.flatnonzero(first))
        u = (keys // self.num_vertices).astype(INT)
        v = (keys % self.num_vertices).astype(INT)
        return from_edges(self.num_vertices, u, v, directed=False, weight=w)


def from_edges(n: int, src: np.ndarray, dst: np.ndarray, directed=True,
               weight: np.ndarray | None = None) -> Graph:
    """Build CSR from a COO edge list (sorts by src, keeps duplicates)."""
    src = np.asarray(src, dtype=INT)
    dst = np.asarray(dst, dtype=INT)
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    if weight is not None:
        weight = np.asarray(weight, dtype=WEIGHT)[order]
    counts = np.bincount(src, minlength=n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return Graph(num_vertices=n, indptr=indptr, dst=dst, directed=directed,
                 weight=weight)


def random_weights(graph: Graph, seed: int = 0, low: float = 1.0,
                   high: float = 10.0) -> Graph:
    """Attach uniform random weights in [low, high) -- the stand-in for the
    paper datasets' (absent) edge metadata in weighted scenarios."""
    rng = np.random.default_rng(seed)
    w = rng.uniform(low, high, size=graph.num_edges).astype(WEIGHT)
    return graph.with_weight(w)


@dataclasses.dataclass(frozen=True)
class PartitionedGraph:
    """Chare decomposition: ``num_chunks`` vertex chunks placed by a
    partitioner policy (contiguous id chunks by default).

    All per-chunk arrays live in *padded id* space -- the permutation chosen
    by the partitioner, laid out as ``chunk * chunk_size + slot`` -- and are
    padded to a common rectangle so they can be sharded on the leading axis
    with ``shard_map`` (one row <-> one chare).  ``global_to_local`` /
    ``local_to_global`` translate between original vertex ids (what callers
    and programs' serial references see) and padded ids (what the chare
    arrays index); for the default ``contiguous`` policy the relabel is the
    identity.

    Basic layout (edges in local-source order, as in the paper's basic
    variant):
      * ``src_local``  [C, Emax] local source index of each edge
      * ``dst_global`` [C, Emax] *padded id* of each edge's destination
      * ``edge_valid`` [C, Emax] 0/1 padding mask

    Sort-destination layout (the paper's best variant -- the same edges
    re-ordered by (destination segment block, source vertex block,
    destination vertex) so contributions to one external vertex are adjacent
    within their tile bucket and can be combined locally before sending):
      * ``sd_src_local``  [C, Emax]
      * ``sd_dst_global`` [C, Emax]
      * ``sd_edge_valid`` [C, Emax]

    Both layouts carry an aligned per-edge weight plane (``edge_weight`` /
    ``sd_edge_weight``, ones when the graph is unweighted) so strategies can
    apply a program's ``edge_value(v, w)`` transform before combining.
    ``out_weight`` is the per-vertex sum of outgoing weights (1 where the
    vertex has no out-edges, mirroring the ``out_degree`` div-0 clip).

    Band metadata (DESIGN.md section 8): both layouts group edges by
    (kernel-tile block of the scatter target, kernel-tile block of the gather
    source) -- the basic layout with the source block outermost, sortdest
    with the destination segment block outermost -- so each BLOCK_E edge
    block touches a narrow band of gather/scatter tiles.  ``band`` /
    ``sd_band`` ([C, 4, NB] int32, rows src_lo/src_hi/seg_lo/seg_hi from
    ``repro.kernels.blocks.edge_bands``) record those bands for the fused
    push kernels' sparsity-aware tile dispatch.

    The edge layouts are *demand-materialized* (DESIGN.md section 9): each
    layout's radix sort + rectangle pack + band table builds on first access
    from the shared relabeled-edge base and is then cached.  ``partition``
    forces both layouts up front (its callers expect a fully built
    decomposition); ``repartition`` leaves them lazy, so a mid-run replan
    pays only for the one layout its strategy actually reads.

    2-D grid partitions (``GridPlan`` placements, DESIGN.md section 10)
    reuse the same container with "chare" meaning "edge rectangle": one
    shard per rectangle ``(r, c)`` of an R x C grid, per-vertex planes
    row-replicated (shard ``r*C + c`` carries row chunk r's state), and a
    single demand-materialized ``grid`` edge layout in place of
    basic/sortdest:
      * ``gr_src_local``  [R*C, Emax] row-local source index of each edge
      * ``gr_dst_col``    [R*C, Emax] *column-padded* destination id
      * ``gr_edge_valid`` / ``gr_edge_weight`` aligned planes
      * ``gr_band``       [R*C, 4, NB] band table (same radix build)
      * ``gr_row_to_col`` [R*C, K] row slot -> column-padded id (-1 padding),
        the gather map that brings column-combined results back to row state
    """

    graph: Graph
    num_chunks: int
    chunk_size: int  # padded vertices per chunk
    vertex_valid: np.ndarray  # [C, chunk_size] 0/1
    out_degree: np.ndarray  # [C, chunk_size] int32 (>=1 to avoid div0; masked)
    out_weight: np.ndarray  # [C, chunk_size] float32 (>=1 where no out-edges)
    edge_valid: np.ndarray  # [C, Emax] 0/1 padding mask (shared by layouts)
    partitioner: str = "contiguous"
    global_to_local: np.ndarray | None = None  # [V] original id -> padded id
    local_to_global: np.ndarray | None = None  # [C*K] padded id -> original/-1
    plan: object = None  # the PartitionPlan this layout materializes
    # relabeled-edge base (_EdgeBase) both layout builds consume
    _base: object = dataclasses.field(default=None, repr=False, compare=False)
    # demand-materialized layout cache: "basic"/"sd" -> (src, dst, w, band)
    _lazy: dict = dataclasses.field(default_factory=dict, repr=False,
                                    compare=False)
    # device-upload cache (keyed "dense:basic"/"dense:sd"/"pairwise"/"aux"):
    # engines built on the same partition share one resident copy of every
    # layout buffer, so a PE/strategy sweep uploads each layout once
    _dev: dict = dataclasses.field(default_factory=dict, repr=False,
                                   compare=False)
    # plan-independent prep products (COO endpoints, per-vertex degree and
    # weight sums), shared with every ``repartition`` of this graph so a
    # replan re-runs only the relabel + radix re-sort + pack
    _prep: object = dataclasses.field(default=None, repr=False, compare=False)
    # grid-only metadata (_GridMeta: shape, column geometry, row->col map);
    # None for 1-D placements
    _grid: object = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def padded_vertices(self) -> int:
        return self.num_chunks * self.chunk_size

    # -- 2-D grid views ------------------------------------------------------

    @property
    def is_grid(self) -> bool:
        return self._grid is not None

    @property
    def grid_shape(self) -> tuple | None:
        """(rows, cols) for grid partitions, None for 1-D placements."""
        return (self._grid.rows, self._grid.cols) if self.is_grid else None

    @property
    def col_chunk_size(self) -> int:
        """Padded height of one destination (column) chunk."""
        if not self.is_grid:
            raise ValueError("col_chunk_size is a grid-partition property")
        return self._grid.col_chunk_size

    def chunk_of(self, v: np.ndarray) -> np.ndarray:
        """Owning chunk of a *padded* id (use ``global_to_local`` first for
        original ids)."""
        return v // self.chunk_size

    # -- demand-materialized edge layouts -----------------------------------

    def _layout(self, which: str) -> tuple:
        """Build-or-fetch one edge layout: the bounded radix sort into the
        (owner, tile-bucket) order, the rectangle pack, and the band table.
        All layouts share ``_base`` (relabeled endpoints, owner split, tile
        ids), so a replan that only reads one order skips the others' sorts
        and packs entirely.  1-D placements expose ``basic``/``sd``; grid
        placements expose the single ``grid`` layout (owners are edge
        rectangles, destinations column-padded ids)."""
        if which not in self._lazy:
            if self.is_grid != (which == "grid"):
                raise ValueError(
                    f"layout {which!r} unavailable: "
                    + ("grid partitions expose only the 'grid' layout"
                       if self.is_grid else
                       "the 'grid' layout needs a grid(R,C) partition"))
            b = self._base
            C = self.num_chunks
            key_bound = C * b.nsb * b.nseg
            key_dtype = INT if key_bound <= 1 << 31 else np.int64
            owner_k = b.owner.astype(key_dtype)
            if which == "basic":
                # source block outermost (permuted CSR order, block-granular)
                key = (owner_k * b.nsb + b.src_blk) * b.nseg + b.seg_blk
            else:
                # destination segment block outermost (the paper's
                # dest-sorted send order, block-granular; the grid layout
                # scatters into its narrow column block, so the same order
                # keeps its bands tight)
                key = (owner_k * b.nseg + b.seg_blk) * b.nsb + b.src_blk
            if key_dtype is INT and _device_build_enabled(len(key), C,
                                                          b.emax):
                s, d, w, band = _build_layout_device(b, key, C)
            else:
                order = _stable_argsort_bounded(key, key_bound)
                s, d, w = _pack_edges(order, b.src_local, b.dst, b.wgt,
                                      b.owner, b.per_chunk_e, C, b.emax)
                band = blocks.edge_bands_grouped(b.src_blk[order],
                                                 b.seg_blk[order],
                                                 b.per_chunk_e, b.emax)
            self._lazy[which] = (s, d, w, band)
        return self._lazy[which]

    @property
    def src_local(self) -> np.ndarray:
        return self._layout("basic")[0]

    @property
    def dst_global(self) -> np.ndarray:
        return self._layout("basic")[1]

    @property
    def edge_weight(self) -> np.ndarray:
        return self._layout("basic")[2]

    @property
    def band(self) -> np.ndarray:
        return self._layout("basic")[3]

    @property
    def sd_src_local(self) -> np.ndarray:
        return self._layout("sd")[0]

    @property
    def sd_dst_global(self) -> np.ndarray:
        return self._layout("sd")[1]

    @property
    def sd_edge_weight(self) -> np.ndarray:
        return self._layout("sd")[2]

    @property
    def sd_band(self) -> np.ndarray:
        return self._layout("sd")[3]

    @property
    def sd_edge_valid(self) -> np.ndarray:
        # one mask serves both layouts: row c has per_chunk_e[c] valid edges
        return self.edge_valid

    # -- grid (rectangle) layout accessors ----------------------------------

    @property
    def gr_src_local(self) -> np.ndarray:
        return self._layout("grid")[0]

    @property
    def gr_dst_col(self) -> np.ndarray:
        return self._layout("grid")[1]

    @property
    def gr_edge_weight(self) -> np.ndarray:
        return self._layout("grid")[2]

    @property
    def gr_band(self) -> np.ndarray:
        return self._layout("grid")[3]

    @property
    def gr_edge_valid(self) -> np.ndarray:
        # rectangle k has per_rect_e[k] valid edges in any order
        return self.edge_valid

    @property
    def gr_row_to_col(self) -> np.ndarray:
        """[R*C, K] row slot -> column-padded id of the same vertex (-1 at
        padding): the post-column-combine gather back into row state."""
        if not self.is_grid:
            raise ValueError("gr_row_to_col is a grid-partition property")
        return self._grid.row_to_col

    @property
    def rect_degree(self) -> np.ndarray:
        """[R*C, K] out-edges each row slot has IN each rectangle (a vertex's
        out-degree split across its row's rectangles by destination column);
        the per-rectangle frontier-load table ``partition_stats`` charges."""
        if not self.is_grid:
            raise ValueError("rect_degree is a grid-partition property")
        if "rect_degree" not in self._lazy:
            b = self._base
            P, K = self.num_chunks, self.chunk_size
            flat = b.owner.astype(np.int64) * K + b.src_local
            self._lazy["rect_degree"] = np.bincount(
                flat, minlength=P * K).astype(np.int64).reshape(P, K)
        return self._lazy["rect_degree"]

    def device_arrays(self, layout: str = "both") -> dict:
        """Device-resident dense layout arrays (edge order + band metadata),
        uploaded once per partition and shared by every Engine built on it.

        ``layout`` is ``"basic"``, ``"sd"``, ``"grid"``, or ``"both"``:
        engines ask for their strategy's layout only
        (``strategies.STRATEGY_LAYOUT``), so a replan uploads -- and
        materializes -- just what it will run.
        """
        if layout == "both":
            if self.is_grid:
                return self.device_arrays("grid")
            return {**self.device_arrays("basic"), **self.device_arrays("sd")}
        names = {
            "basic": ("src_local", "dst_global", "edge_valid", "edge_weight",
                      "band"),
            "sd": ("sd_src_local", "sd_dst_global", "sd_edge_valid",
                   "sd_edge_weight", "sd_band"),
            "grid": ("gr_src_local", "gr_dst_col", "gr_edge_valid",
                     "gr_edge_weight", "gr_band", "gr_row_to_col"),
        }[layout]
        key = f"dense:{layout}"
        if key not in self._dev:
            import jax.numpy as jnp

            self._dev[key] = {k: jnp.asarray(getattr(self, k))
                              for k in names}
        return self._dev[key]

    def device_pairwise(self) -> dict:
        """Device-resident pairwise (edge-bucketed) layout for the basic
        variant; built and uploaded on first use, then shared."""
        if "pairwise" not in self._dev:
            import jax.numpy as jnp

            pw = build_pairwise(self)
            self._dev["pairwise"] = {
                "pb_src_local": jnp.asarray(pw.pb_src_local),
                "pb_dst_local": jnp.asarray(pw.pb_dst_local),
                "pb_valid": jnp.asarray(pw.pb_valid),
                "pb_weight": jnp.asarray(pw.pb_weight),
            }
        return self._dev["pairwise"]

    def device_aux(self) -> dict:
        """Device-resident per-vertex auxiliaries (degree/weight/validity)."""
        if "aux" not in self._dev:
            import jax.numpy as jnp

            self._dev["aux"] = {
                "out_degree": jnp.asarray(self.out_degree),
                "out_weight": jnp.asarray(self.out_weight),
                "vertex_valid": jnp.asarray(self.vertex_valid),
            }
        return self._dev["aux"]

    def repartition(self, partitioner, plan=None) -> "PartitionedGraph":
        """Re-place the same graph under a new policy, cheaply.

        Reuses this partition's plan-independent prep products (COO source
        expansion, per-vertex degree and out-weight sums) so only the
        plan-dependent work re-runs: relabel gathers, the bounded radix
        re-sort into the tile-bucket edge orders, the rectangle packs, and
        the band tables -- and the layouts stay DEMAND-materialized, so a
        replan whose engine reads one edge order never pays for the other's
        sort and pack.  The returned ``PartitionedGraph`` starts with an
        EMPTY device-upload cache -- stale band tables or edge buffers from
        the old placement can never be reused (the engine re-uploads on
        rebind; see ``Engine._rebind``).

        ``partitioner`` names a registered policy; ``plan`` (optional)
        supplies an already-built ``PartitionPlan`` for it (the engine's
        replan path plans first to detect no-op switches).
        """
        if plan is None:
            plan = part_mod.make_plan(self.graph, self.num_chunks,
                                      partitioner)
        prep = self._prep if self._prep is not None else _edge_prep(self.graph)
        return _materialize(self.graph, plan, partitioner, prep, eager=False)

    # -- out-of-core streaming (DESIGN.md section 13) ------------------------

    def cached_layout(self, which: str, cache_dir: str) -> tuple:
        """Disk-backed ``_layout``: the cross-process analogue of ``_lazy``.

        A warm cache entry memory-maps the packed planes straight off disk
        (no sort, no pack, no materialized host copy); a cold one builds
        once through ``_layout`` and persists atomically.  The entry is
        keyed by ``checkpoint.layout_fingerprint`` (graph bytes +
        partitioner spec + chare count + layout name), so a changed graph
        or policy can never reload a stale build -- it simply misses and
        rebuilds.
        """
        from repro.checkpoint import store as ckpt_store

        fp = ckpt_store.layout_fingerprint(self.graph, self.partitioner,
                                           self.num_chunks, which)
        hit = ckpt_store.open_layout_cache(cache_dir, fp)
        if which in self._lazy:
            # already materialized in this process (an eager partition):
            # serve it, but still persist a missing entry so later
            # processes warm-start off disk
            if hit is None:
                s, d, w, band = self._lazy[which]
                ckpt_store.save_layout_cache(cache_dir, fp, {
                    "src": s, "dst": d, "weight": w, "band": band})
            return self._lazy[which]
        if hit is None:
            s, d, w, band = self._layout(which)
            ckpt_store.save_layout_cache(cache_dir, fp, {
                "src": s, "dst": d, "weight": w, "band": band})
            return self._lazy[which]
        self._lazy[which] = (hit["src"], hit["dst"], hit["weight"],
                             hit["band"])
        return self._lazy[which]

    def shard_source(self, windows: int | None = None,
                     budget_bytes: int | None = None,
                     cache_dir: str | None = None) -> "ShardSource":
        """Build the windowed edge-shard provider for ``residency="stream"``.

        Window width is chosen from ``windows`` (count) or sized so TWO
        staging windows -- the double-buffer working set -- fit under
        ``budget_bytes``; default is 8 windows.  With ``cache_dir`` the
        planes come from the disk layout cache (memory-mapped on a warm
        hit), so the host never holds a second copy of the edge layout.
        """
        if not self.is_grid:
            raise ValueError(
                "residency='stream' needs a grid(R,C) partition: rectangles "
                "are the independently bounded shard unit (use grid(1,1) "
                "for a single PE)")
        if cache_dir is not None:
            s, d, w, band = self.cached_layout("grid", cache_dir)
        else:
            s, d, w, band = self._layout("grid")
        nb = blocks.num_edge_blocks(s.shape[1])
        per_block = _window_block_bytes(self.num_chunks)
        if windows is not None:
            if windows < 1:
                raise ValueError(f"windows must be >= 1, got {windows}")
            nbw = max(-(-nb // int(windows)), 1)
        elif budget_bytes is not None:
            nbw = int(budget_bytes // (2 * per_block))
            if nbw < 1:
                raise ValueError(
                    f"budget_bytes={budget_bytes} cannot hold the "
                    f"double-buffered working set: two single-block staging "
                    f"windows need {2 * per_block} bytes")
            nbw = min(nbw, nb)
        else:
            nbw = max(-(-nb // 8), 1)
        return ShardSource(src=s, dst=d, valid=self.gr_edge_valid, weight=w,
                           band=band, blocks_per_window=nbw)


def _window_block_bytes(num_rects: int) -> int:
    """Staged bytes one BLOCK_E window column costs across all rectangles:
    src/dst/valid int32 + weight float32 planes plus the 4-row band slice."""
    return num_rects * blocks.BLOCK_E * 16 + num_rects * 4 * 4


@dataclasses.dataclass
class ShardSource:
    """Windowed edge-shard provider for ``residency="stream"`` (DESIGN.md
    section 13).

    Wraps one grid layout's packed planes -- host ndarrays or memory-mapped
    layout-cache files -- and serves BLOCK_E-aligned *edge windows*: window
    ``k`` of rectangle ``p`` is columns ``[k*W, (k+1)*W)`` of the
    ``[P, Emax]`` pack plus the matching band-table slice.  The streamed
    engine keeps only two staging windows device-resident (the
    double-buffer pool), so device edge footprint is ``2/num_windows`` of
    the resident layout regardless of graph size.
    """

    src: np.ndarray      # [P, Emax] int32 row-local sources
    dst: np.ndarray      # [P, Emax] int32 column-padded destinations
    valid: np.ndarray    # [P, Emax] int32 padding mask
    weight: np.ndarray   # [P, Emax] float32
    band: np.ndarray     # [P, 4, NB] int32
    blocks_per_window: int

    @property
    def num_rects(self) -> int:
        return self.src.shape[0]

    @property
    def emax(self) -> int:
        return self.src.shape[1]

    @property
    def num_blocks(self) -> int:
        return blocks.num_edge_blocks(self.emax)

    @property
    def num_windows(self) -> int:
        return -(-self.num_blocks // self.blocks_per_window)

    @property
    def window_edges(self) -> int:
        return self.blocks_per_window * blocks.BLOCK_E

    @property
    def origin(self) -> str:
        """"disk" when the planes are memory-mapped cache files."""
        return "disk" if isinstance(self.src, np.memmap) else "memory"

    @property
    def total_edge_bytes(self) -> int:
        """Bytes the RESIDENT path would upload for the edge layout."""
        return (self.src.nbytes + self.dst.nbytes + self.valid.nbytes
                + self.weight.nbytes + self.band.nbytes)

    @property
    def window_bytes(self) -> int:
        """Device bytes of ONE staging window (all rectangles)."""
        return _window_block_bytes(self.num_rects) * self.blocks_per_window

    def make_staging(self) -> dict:
        """One recycled host staging slot, keyed like the resident ``gr_*``
        arrays so the streamed phase-1 body is the resident body unchanged."""
        P, W = self.num_rects, self.window_edges
        return {
            "gr_src_local": np.zeros((P, W), dtype=INT),
            "gr_dst_col": np.zeros((P, W), dtype=INT),
            "gr_edge_valid": np.zeros((P, W), dtype=INT),
            "gr_edge_weight": np.ones((P, W), dtype=WEIGHT),
            "gr_band": np.zeros((P, 4, self.blocks_per_window), dtype=INT),
        }

    def gate_masks(self, num_src_blocks: int) -> np.ndarray:
        """``[P, num_windows, nsb]`` bool: which gather-side source blocks
        each (rectangle, window) shard can read -- ``band_source_mask`` at
        window granularity.  The streamed scheduler intersects these with
        the live frontier blocks; a slot that misses is neither fetched nor
        pushed, so gating saves H2D bandwidth, not just launches."""
        P, nw = self.num_rects, self.num_windows
        out = np.zeros((P, nw, num_src_blocks), dtype=bool)
        for k in range(nw):
            blo = k * self.blocks_per_window
            bhi = min(self.num_blocks, blo + self.blocks_per_window)
            sub = np.ascontiguousarray(self.band[:, :, blo:bhi])
            out[:, k, :] = blocks.band_source_mask(
                sub, num_src_blocks).astype(bool)
        return out

    @staticmethod
    def active_windows(gate_masks: np.ndarray,
                       frontier_blocks: np.ndarray) -> np.ndarray:
        """``[P, num_windows]`` bool fetch schedule: which (rectangle,
        window) slots the live frontier can reach at all.

        ``frontier_blocks`` is the engine's BLOCK_V-granular frontier
        summary -- ``[P, nsb]`` for a single query, or ``[P, nsb, B]`` for
        the batched plane, where a slot stays active iff ANY live query
        column's frontier intersects its band source blocks (the
        union-over-queries gate, DESIGN.md section 15).  The union is the
        soundness condition for sharing one fetch across B folds: a window
        may be skipped only when it is provably dead for EVERY query, and
        a fetched window contributes the combiner identity to the columns
        whose frontier misses it (frontier-masked vals), so no column ever
        sees a neighbor's extra work.
        """
        fb = np.asarray(frontier_blocks)
        if fb.ndim == 3:
            fb = fb.any(axis=2)  # union over query columns
        return (gate_masks & fb[:, None, :]).any(axis=2)

    def read_window(self, k: int, staging: dict,
                    active: np.ndarray | None = None) -> int:
        """Copy window ``k`` into the recycled ``staging`` slot; returns the
        bytes actually read from the backing store.

        Rectangles with ``active[p] == False`` are skipped entirely (their
        staged rows keep whatever a previous window left, with the validity
        mask and band table cleared so both push paths treat them as empty
        -- the combiner-identity contribution frontier gating relies on).
        The ragged tail window is zero-masked the same way.
        """
        lo = k * self.window_edges
        hi = min(self.emax, lo + self.window_edges)
        blo = k * self.blocks_per_window
        bhi = min(self.num_blocks, blo + self.blocks_per_window)
        n, nbk = hi - lo, bhi - blo
        idx = (np.arange(self.num_rects) if active is None
               else np.flatnonzero(np.asarray(active)))
        read = 0
        staging["gr_edge_valid"][...] = 0
        bband = staging["gr_band"]
        bband[:, 0::2, :] = 0  # empty-block convention: (0, -1, 0, -1)
        bband[:, 1::2, :] = -1
        if n <= 0 or len(idx) == 0:
            return read
        for name, plane in (("gr_src_local", self.src),
                            ("gr_dst_col", self.dst),
                            ("gr_edge_valid", self.valid),
                            ("gr_edge_weight", self.weight)):
            chunk = plane[idx, lo:hi]
            staging[name][idx, :n] = chunk
            read += chunk.nbytes
        bchunk = self.band[idx, :, blo:bhi]
        bband[idx, :, :nbk] = bchunk
        read += bchunk.nbytes
        return read


def _stable_argsort_bounded(keys: np.ndarray, bound: int) -> np.ndarray:
    """Stable argsort of non-negative int keys known to be < ``bound``.

    For bounds under 2^30 this runs one or two int16 radix passes (numpy's
    stable sort is radix for 16-bit ints but mergesort for 32/64-bit, which
    is ~4x slower on edge-scale arrays); larger bounds fall back to the
    generic stable sort.
    """
    if bound <= 1 << 15:
        return np.argsort(keys.astype(np.int16), kind="stable")
    if bound <= 1 << 30:
        lo = (keys & 0x7FFF).astype(np.int16)
        hi = (keys >> 15).astype(np.int16)
        o1 = np.argsort(lo, kind="stable")
        return o1[np.argsort(hi[o1], kind="stable")]
    return np.argsort(keys, kind="stable")


def _pack_edges(order_idx, src_local, dst, wgt, owner, per_chunk_e,
                num_chunks, emax):
    """Scatter owner-grouped edges into the padded [C, Emax] rectangle.

    ``order_idx`` must list edges with owners grouped (nondecreasing); the
    slot of an edge within its row is its rank among same-owner edges, so one
    global sort replaces the seed's per-chunk ``flatnonzero`` loop.
    ``src_local`` is already owner-local (a row-local index for grid
    rectangles, a chare-local one for 1-D layouts).  The validity mask is
    not built here -- it depends only on ``per_chunk_e`` (identical for
    every edge order), so the materializer builds it once.
    """
    so, do = src_local[order_idx], dst[order_idx]
    ow = owner[order_idx]
    starts = np.zeros(num_chunks, dtype=np.int64)
    np.cumsum(per_chunk_e[:-1], out=starts[1:])
    # ow is sorted, so flat = row offset + within-row slot is ascending:
    # flat[i] = ow[i]*emax + (i - starts[ow[i]])
    flat = (np.arange(len(order_idx), dtype=np.int64)
            + (np.arange(num_chunks, dtype=np.int64) * emax - starts)[ow])
    s = np.zeros((num_chunks, emax), dtype=INT)
    d = np.zeros((num_chunks, emax), dtype=INT)
    w = np.ones((num_chunks, emax), dtype=WEIGHT)
    s.ravel()[flat] = so
    d.ravel()[flat] = do
    w.ravel()[flat] = wgt[order_idx]
    return s, d, w


# Edge count above which the layout build (stable argsort + rectangle pack +
# band reduction) runs on device instead of the host radix path: scale-20
# stand-ins cross it, every CI-sized graph stays on the host build.  Override
# with REPRO_DEVICE_BUILD=device|host (auto = threshold).
_DEVICE_BUILD_MIN_EDGES = 1 << 21


def _device_build_enabled(num_edges: int, num_chunks: int, emax: int) -> bool:
    mode = os.environ.get("REPRO_DEVICE_BUILD", "auto")
    if mode in ("host", "0"):
        return False
    # the device pack scatters int32 flat indices into [C, NB*BLOCK_E]; fall
    # back to the host build when that padded plane leaves int32 range
    nb = blocks.num_edge_blocks(emax)
    if num_chunks * nb * blocks.BLOCK_E >= 1 << 31:
        return False
    if mode in ("device", "1"):
        return True
    return num_edges >= _DEVICE_BUILD_MIN_EDGES


def _build_layout_device(b: "_EdgeBase", key: np.ndarray, C: int) -> tuple:
    """On-device twin of the host layout build, bit-identical by design.

    The three O(E log E / E) passes that dominate prep at scale -- the
    stable argsort into (owner, tile-bucket) order, the rectangle pack
    scatter, and the band min/max -- run as one jitted XLA program; only the
    packed [C, Emax] planes round-trip back to host (they must live there
    anyway for the streamed shard source).  Stability of the sort is the
    whole contract: identical keys => identical permutation => the packed
    planes and band tables match the host radix path bit for bit (test:
    tests/test_stream.py::test_device_build_bit_identical).
    """
    import jax
    import jax.numpy as jnp

    E = len(key)
    nb = blocks.num_edge_blocks(b.emax)
    emax_p = nb * blocks.BLOCK_E
    starts = np.zeros(C, dtype=np.int64)
    np.cumsum(b.per_chunk_e[:-1], out=starts[1:])
    row_off = (np.arange(C, dtype=np.int64) * emax_p - starts).astype(INT)

    @jax.jit
    def build(key, src_local, dst, wgt, src_blk, seg_blk, owner, row_off):
        order = jnp.argsort(key, stable=True)
        ow = owner[order]
        # flat slot of each edge in the padded [C, emax_p] plane: the same
        # ascending row-offset + within-row-rank formula as _pack_edges
        flat = jnp.arange(E, dtype=INT) + row_off[ow]
        plane = lambda fill, dt: jnp.full(C * emax_p, fill, dtype=dt)
        s = plane(0, INT).at[flat].set(src_local[order])
        d = plane(0, INT).at[flat].set(dst[order])
        w = plane(1.0, WEIGHT).at[flat].set(wgt[order])
        # band min/max over BLOCK_E columns; sentinel fill so padding slots
        # never win, then the (0, -1, 0, -1) empty-block clamp
        big = jnp.int32(1) << 30
        shape = (C, nb, blocks.BLOCK_E)
        sb, gb = src_blk[order], seg_blk[order]
        lo = lambda blk: plane(big, INT).at[flat].set(blk).reshape(
            shape).min(axis=2)
        hi = lambda blk: plane(-1, INT).at[flat].set(blk).reshape(
            shape).max(axis=2)
        src_lo, src_hi = lo(sb), hi(sb)
        seg_lo, seg_hi = lo(gb), hi(gb)
        empty = src_hi < 0
        band = jnp.stack([jnp.where(empty, 0, src_lo), src_hi,
                          jnp.where(empty, 0, seg_lo), seg_hi], axis=1)
        return s.reshape(C, emax_p), d.reshape(C, emax_p), \
            w.reshape(C, emax_p), band

    s, d, w, band = build(
        jnp.asarray(key.astype(INT)), jnp.asarray(b.src_local),
        jnp.asarray(b.dst), jnp.asarray(b.wgt),
        jnp.asarray(b.src_blk.astype(INT)), jnp.asarray(b.seg_blk.astype(INT)),
        jnp.asarray(b.owner.astype(INT)), jnp.asarray(row_off))
    s, d, w, band = map(lambda a: np.asarray(jax.device_get(a)),
                        (s, d, w, band))
    return (np.ascontiguousarray(s[:, :b.emax]),
            np.ascontiguousarray(d[:, :b.emax]),
            np.ascontiguousarray(w[:, :b.emax]), band)


@dataclasses.dataclass(frozen=True)
class _EdgePrep:
    """Plan-independent prep products, computed once per graph and shared by
    every (re)partition of it: the COO source expansion (``np.repeat`` over
    out-degrees) and the per-vertex weight-sum bincount are both O(E) passes
    that do not depend on vertex placement."""

    src: np.ndarray  # [E] int32 COO sources, original ids
    dst: np.ndarray  # [E] int32 COO destinations, original ids
    wgt: np.ndarray  # [E] float32 weights (ones when unweighted)
    out_degrees: np.ndarray  # [V] int32
    wsum: np.ndarray  # [V] float32 per-vertex outgoing weight sums


def _edge_prep(graph: Graph) -> _EdgePrep:
    src = graph.src
    wgt = graph.edge_weights
    wsum = np.bincount(src, weights=wgt,
                       minlength=graph.num_vertices).astype(WEIGHT)
    return _EdgePrep(src, graph.dst, wgt, graph.out_degrees, wsum)


def partition(graph: Graph, num_chunks: int,
              partitioner: str = "contiguous",
              eager: bool = True) -> PartitionedGraph:
    """Split ``graph`` into ``num_chunks`` chares under a partitioner policy.

    ``partitioner`` names a registered policy (``repro.core.partitioners``);
    the default reproduces the paper's contiguous equal-vertex chunks.
    Re-placing an existing partition is cheaper via
    ``PartitionedGraph.repartition`` (shares the prep products).

    ``eager=False`` defers the edge-layout builds to first use -- the entry
    point for the disk layout cache (``cached_layout``/``shard_source``): a
    deferred partition whose layout comes off a warm cache entry never runs
    the sort/pack build at all.
    """
    plan = part_mod.make_plan(graph, num_chunks, partitioner)
    return _materialize(graph, plan, partitioner, _edge_prep(graph), eager)


@dataclasses.dataclass(frozen=True)
class _EdgeBase:
    """Relabeled-edge base shared by the layout builds of one partition:
    owner-local sources, scatter-space destinations, the owner split, and
    the kernel-tile ids the sort keys and band tables are made of (DESIGN.md
    section 8).  Every layout orders an owner's edges by coarse tile bucket
    so the fused kernels' gather/scatter bands stay narrow; the bucket count
    is small enough (C * K/BLOCK_V * S/BLOCK_S) that graphs up to scale ~18
    take a single int16 radix pass per layout.

    For 1-D placements the owner is the source's chare and ``dst`` a padded
    vertex id; for grid placements the owner is the edge's *rectangle* and
    ``dst`` a column-padded id (DESIGN.md section 10) -- the sort, pack, and
    band machinery is identical.
    """

    src_local: np.ndarray  # [E] int32 owner-local sources
    dst: np.ndarray  # [E] int32 scatter-space destinations
    wgt: np.ndarray  # [E] float32
    owner: np.ndarray  # [E] owning chunk/rectangle of each edge
    per_chunk_e: np.ndarray  # [C]
    emax: int
    src_blk: np.ndarray  # [E] gather-side tile id (local source / BLOCK_V)
    seg_blk: np.ndarray  # [E] scatter-side tile id (scatter dest / BLOCK_S)
    nsb: int  # gather-side tile count per owner
    nseg: int  # scatter-side tile count


@dataclasses.dataclass(frozen=True)
class _GridMeta:
    """Grid-only metadata riding on ``PartitionedGraph._grid``."""

    rows: int
    cols: int
    col_chunk_size: int
    row_to_col: np.ndarray  # [R*C, K] int32, -1 at padding


def _materialize(graph: Graph, plan, partitioner: str, prep: _EdgePrep,
                 eager: bool = True) -> PartitionedGraph:
    """Build the chare decomposition for one ``PartitionPlan``.

    ``eager`` forces both edge layouts (``partition``'s contract: a fully
    built decomposition); ``repartition`` passes ``eager=False`` so a replan
    materializes only the layout its engine strategy reads, on demand.
    ``GridPlan`` placements route to ``_materialize_grid``.
    """
    if isinstance(plan, part_mod.GridPlan):
        return _materialize_grid(graph, plan, partitioner, prep, eager)
    num_chunks = plan.num_chunks
    chunk_size = plan.chunk_size
    padded = num_chunks * chunk_size
    g2l, l2g = plan.relabel()

    # relabel every edge endpoint into padded-id space; int32 halves the
    # memory traffic of the gathers/scatters below
    g2l32 = g2l.astype(INT)
    src = g2l32[prep.src]
    dst = g2l32[prep.dst]
    owner = src // chunk_size

    live = l2g >= 0
    deg = np.ones(padded, dtype=INT)  # 1 for padding (avoids div-by-zero)
    deg[live] = np.maximum(prep.out_degrees[l2g[live]], 1)
    vertex_valid = live.astype(INT)
    out_weight = np.ones(padded, dtype=WEIGHT)
    out_weight[live] = np.where(prep.wsum[l2g[live]] > 0,
                                prep.wsum[l2g[live]], 1.0)

    per_chunk_e = np.bincount(owner, minlength=num_chunks)
    emax = max(int(per_chunk_e.max()) if len(src) else 1, 1)
    # one validity mask serves both layouts: row c has per_chunk_e[c] edges
    edge_valid = (np.arange(emax) < per_chunk_e[:, None]).astype(INT)
    src_local = src - owner * chunk_size
    base = _EdgeBase(src_local, dst, prep.wgt, owner, per_chunk_e, emax,
                     src_blk=src_local // blocks.BLOCK_V,
                     seg_blk=dst // blocks.BLOCK_S,
                     nsb=-(-chunk_size // blocks.BLOCK_V),
                     nseg=-(-padded // blocks.BLOCK_S))

    pg = PartitionedGraph(
        graph=graph,
        num_chunks=num_chunks,
        chunk_size=chunk_size,
        vertex_valid=vertex_valid.reshape(num_chunks, chunk_size),
        out_degree=deg.reshape(num_chunks, chunk_size),
        out_weight=out_weight.reshape(num_chunks, chunk_size),
        edge_valid=edge_valid,
        partitioner=partitioner,
        global_to_local=g2l,
        local_to_global=l2g,
        plan=plan,
        _base=base,
        _prep=prep,
    )
    if eager:
        pg._layout("basic")
        pg._layout("sd")
    return pg


def _materialize_grid(graph: Graph, plan, partitioner: str, prep: _EdgePrep,
                      eager: bool = True) -> PartitionedGraph:
    """Build the rectangle decomposition for one ``GridPlan``.

    One shard per rectangle ``(r, c)``; the per-vertex planes (state width,
    degrees, validity) are the ROW layout replicated across each row's C
    rectangles, destinations are relabeled into the COLUMN-padded space the
    two-phase reduce combines over, and the edge layout orders each
    rectangle's edges by (segment block, source block) through the same
    int16-radix pass as the 1-D layouts (DESIGN.md section 10).
    """
    R, C = plan.rows, plan.cols
    P = R * C
    Kr, Kc = plan.chunk_size, plan.col_chunk_size
    row_g2l, row_l2g = plan.row.relabel()  # [V], [R*Kr]
    col_g2l, col_l2g = plan.col.relabel()  # [V], [C*Kc]

    # state relabel: the row layout replicated across each row's rectangles;
    # g2l names the column-0 replica (engines read results from it), l2g
    # names every replica (so source seeding and id-valued inits hit all C)
    rrow = row_g2l // Kr
    g2l = rrow * C * Kr + (row_g2l - rrow * Kr)
    l2g = np.repeat(row_l2g.reshape(R, Kr), C, axis=0).reshape(-1)

    live = row_l2g >= 0
    deg = np.ones(R * Kr, dtype=INT)
    deg[live] = np.maximum(prep.out_degrees[row_l2g[live]], 1)
    out_weight = np.ones(R * Kr, dtype=WEIGHT)
    out_weight[live] = np.where(prep.wsum[row_l2g[live]] > 0,
                                prep.wsum[row_l2g[live]], 1.0)
    rep = lambda a: np.repeat(a.reshape(R, Kr), C, axis=0)

    # row slot -> column-padded id of the same vertex: the gather map that
    # brings the column-combined vector back into (replicated) row state
    row_to_col = np.full(R * Kr, -1, dtype=INT)
    row_to_col[live] = col_g2l[row_l2g[live]].astype(INT)

    # relabel edges: row-local gather index, column-padded scatter id,
    # owning rectangle
    src_row = row_g2l.astype(INT)[prep.src]
    src_local = src_row % Kr
    dst_col = col_g2l.astype(INT)[prep.dst]
    owner = blocks.edge_rectangles(src_row // Kr, dst_col // Kc, C)
    per_rect_e = np.bincount(owner, minlength=P)
    emax = max(int(per_rect_e.max()) if len(src_local) else 1, 1)
    edge_valid = (np.arange(emax) < per_rect_e[:, None]).astype(INT)
    base = _EdgeBase(src_local, dst_col, prep.wgt, owner, per_rect_e, emax,
                     src_blk=src_local // blocks.BLOCK_V,
                     seg_blk=dst_col // blocks.BLOCK_S,
                     nsb=-(-Kr // blocks.BLOCK_V),
                     nseg=-(-(C * Kc) // blocks.BLOCK_S))

    pg = PartitionedGraph(
        graph=graph,
        num_chunks=P,
        chunk_size=Kr,
        vertex_valid=rep(live.astype(INT)),
        out_degree=rep(deg),
        out_weight=rep(out_weight),
        edge_valid=edge_valid,
        partitioner=partitioner,
        global_to_local=g2l,
        local_to_global=l2g,
        plan=plan,
        _base=base,
        _prep=prep,
        _grid=_GridMeta(R, C, Kc, rep(row_to_col)),
    )
    if eager:
        pg._layout("grid")
    return pg


@dataclasses.dataclass(frozen=True)
class PairwiseLayout:
    """Edge layout for the *basic* variant: per (source chunk, dest chunk)
    buckets of (src_local, dst_local) pairs, padded to the max bucket size.

    ``pb_*`` arrays are [C, C, Pmax]; row ``[c, k]`` holds chunk c's messages
    destined to chunk k -- Listing 2's ``outgoing[CHUNKINDEX(dest)]`` buffers.
    """

    pair_max: int
    pb_src_local: np.ndarray
    pb_dst_local: np.ndarray
    pb_valid: np.ndarray
    pb_weight: np.ndarray


def build_pairwise(pg: PartitionedGraph) -> PairwiseLayout:
    """Bucket edges by (source chunk, dest chunk), vectorized: one stable
    argsort over flattened bucket ids replaces the seed's O(C^2) scan loop."""
    if pg.is_grid:
        raise ValueError("pairwise layout is 1-D only; grid partitions "
                         "already bucket edges by rectangle")
    prep = pg._prep if pg._prep is not None else _edge_prep(pg.graph)
    g2l32 = pg.global_to_local.astype(INT)
    src = g2l32[prep.src]
    dst = g2l32[prep.dst]
    wgt = prep.wgt
    K, C = pg.chunk_size, pg.num_chunks
    bucket = (src // K) * C + dst // K  # flattened (sc, dc)
    counts = np.bincount(bucket, minlength=C * C)
    pmax = max(int(counts.max()) if len(src) else 1, 1)
    order = _stable_argsort_bounded(bucket, C * C)
    bo = bucket[order]
    starts = np.zeros(C * C, dtype=np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    flat = (np.arange(len(order), dtype=np.int64)
            + (np.arange(C * C, dtype=np.int64) * pmax - starts)[bo])
    s = np.zeros((C, C, pmax), dtype=INT)
    d = np.zeros((C, C, pmax), dtype=INT)
    w = np.ones((C, C, pmax), dtype=WEIGHT)
    m = np.zeros((C, C, pmax), dtype=INT)
    s.ravel()[flat] = src[order] % K
    d.ravel()[flat] = dst[order] % K
    w.ravel()[flat] = wgt[order]
    m.ravel()[flat] = 1  # one E-sized scatter, not two passes over C*C*pmax
    return PairwiseLayout(pair_max=pmax, pb_src_local=s, pb_dst_local=d,
                          pb_valid=m, pb_weight=w)


# ---------------------------------------------------------------------------
# Generators
# ---------------------------------------------------------------------------


def ring(n: int, weighted: bool = False, weight_seed: int = 0) -> Graph:
    v = np.arange(n, dtype=INT)
    g = from_edges(n, v, (v + 1) % n)
    return random_weights(g, seed=weight_seed) if weighted else g


def two_cliques(n: int, weighted: bool = False, weight_seed: int = 0) -> Graph:
    """Two disjoint cliques of size n//2 -- a labelprop ground-truth fixture.

    Built by broadcasting (all ordered pairs minus the diagonal per clique),
    not the O(n^2) Python pair loop.
    """
    half = n // 2
    src_parts, dst_parts = [], []
    for base, size in ((0, half), (half, n - half)):
        i = np.arange(size, dtype=INT)
        s = np.repeat(i, size)
        d = np.tile(i, size)
        keep = s != d
        src_parts.append(base + s[keep])
        dst_parts.append(base + d[keep])
    g = from_edges(n, np.concatenate(src_parts).astype(INT),
                   np.concatenate(dst_parts).astype(INT))
    return random_weights(g, seed=weight_seed) if weighted else g


def erdos_renyi(n: int, num_edges: int, seed: int = 0,
                weighted: bool = False) -> Graph:
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=num_edges, dtype=INT)
    dst = rng.integers(0, n, size=num_edges, dtype=INT)
    keep = src != dst
    g = from_edges(n, src[keep], dst[keep])
    return random_weights(g, seed=seed) if weighted else g


def rmat(n_log2: int, num_edges: int, seed: int = 0,
         a=0.57, b=0.19, c=0.19, weighted: bool = False) -> Graph:
    """RMAT power-law generator (Graph500-style), vectorized."""
    rng = np.random.default_rng(seed)
    n = 1 << n_log2
    # bits accumulate in place: at scale 22 the 58.7M-edge draw is host
    # prep that every run pays
    acc = INT if n_log2 < 31 else np.int64
    src = np.zeros(num_edges, dtype=acc)
    dst = np.zeros(num_edges, dtype=acc)
    r = np.empty(num_edges)
    lower = np.empty(num_edges, dtype=bool)
    for _ in range(n_log2):
        rng.random(out=r)
        np.greater_equal(r, a + b, out=lower)
        src <<= 1
        src |= lower
        rng.random(out=r)
        # quadrant probabilities conditioned on the row bit
        dst <<= 1
        dst |= np.where(lower, r < c / (c + (1 - a - b - c)), r < b / (a + b))
    keep = src != dst
    g = from_edges(n, src[keep], dst[keep])
    return random_weights(g, seed=seed) if weighted else g


# Scaled stand-ins for the paper's datasets (same E/V ratio, power-law skew).
_DATASETS = {
    # name: (n_log2, edge_multiple-of-V)   paper: V, E, E/V
    "soc-lj1-mini": (15, 14),   # soc-LiveJournal1: 4.8M, 69M, 14.2x
    "twitter-mini": (15, 24),   # twitter_rv: 61.6M, 1.47B, 23.8x
    "uk-2007-mini": (15, 35),   # uk-2007-05: 105.9M, 3.74B, 35.3x
}


def load_dataset(name: str, scale_log2: int | None = None, seed: int = 1,
                 weighted: bool = False) -> Graph:
    n_log2, mult = _DATASETS[name]
    if scale_log2 is not None:
        n_log2 = scale_log2
    return rmat(n_log2, (1 << n_log2) * mult, seed=seed, weighted=weighted)


def dataset_names():
    return list(_DATASETS)
