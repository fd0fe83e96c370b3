"""Multi-device VSW: GraphMP's single-writer invariant on a device mesh.

GraphMP is single-machine; its no-atomics property — every in-edge of a
vertex lives in exactly one shard — extends directly to a device mesh:
partition destination intervals over the ``data`` axis (one writer device
per interval) and keep the source array device-resident, refreshed once per
iteration by an ``all_gather`` (the only collective; C|V| per iteration, the
same volume the paper writes to DRAM).  That is how GraphH (arxiv
1705.05595, same authors) scales the model to small clusters.

Two engines live here:

``ShardedVSWEngine`` — the production path (``EngineConfig.num_devices``,
env ``GRAPHMP_DEVICES``; ``GraphSession`` routes to it transparently).  It
subclasses ``VSWEngine`` and keeps the whole I/O story: shards stream from
the store through per-device ``CompressedShardCache`` partitions (one global
byte budget, split exactly — core/cache.py ``PartitionedShardCache``) and
per-device ``ShardPipeline`` prefetch lanes, with epoch pinning /
``ConcurrentMutationError`` intact.  Each iteration:

    x     = gather_transform(src)                  # replicated, no comm
    waves : device d folds its w-th scheduled shard (shard_map'ped
            gather -> SpMV -> post, single-writer per interval)
    merge : each device slices its own interval, a psum combines the
            changed-count, an all_gather exchanges the frontier blocks

Selective scheduling stays host-side: the per-shard Bloom filters are KBs
and REPLICATED, so every host computes the identical skip schedule with no
coordination (core/bloom.py).  Results are bitwise-identical to the
single-device engine at any device count — the same per-shard kernels run
with identity padding that cannot perturb f32 reductions (sentinel rows in
no slice, slices mapping no virtual row).

``DistributedVSW`` — the all-resident prototype kept for mesh-semantics
tests and as the minimal reference: the WHOLE edge set is partitioned onto
the mesh up front (``partition_for_mesh``), so there is no disk, cache or
prefetch path.  It honors ``EngineConfig.use_pallas`` and
``selective_threshold`` (replicated-Bloom device skipping) and documents the
I/O knobs as inapplicable rather than accepting-and-ignoring them.

The 2-D (src × dst) partition from DESIGN.md §2 maps a second mesh axis over
source ranges with a psum (min-fold for min-semirings) over partials;
implemented in ``spmv_2d`` and used by the graph-engine dry-run config.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.apps import VertexProgram, get_app
from repro.core.bloom import BloomFilter
from repro.core.cache import PartitionedShardCache
from repro.core.engine import EngineConfig, VSWEngine, make_shard_step
from repro.core.pipeline import ShardPipeline
from repro.core.shards import (GROUP_ROWS, LANE, ROW_ALIGN, ELLShard,
                               build_csr_shards, csr_to_ell,
                               dequantize_edge_vals)
from repro.core.spans import span
from repro.dist.context import make_data_mesh
from repro.kernels.spmv.ops import ell_spmv


# ---------------------------------------------------------------------------
def assign_shards(intervals: np.ndarray, shard_nnz, num_devices: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Contiguous, nnz-balanced shard -> device assignment.

    Returns ``(owner [P], bounds [D+1])``: device ``d`` owns the shards
    ``p`` with ``owner[p] == d``, whose destination intervals tile exactly
    ``[bounds[d], bounds[d+1])``.  Contiguity keeps every device's write
    region ONE interval — the single-writer invariant survives the mesh and
    the merge step needs only static slices; greedy nnz balancing keeps
    per-device SpMV work even.  A device may own zero shards (more devices
    than shards, or one giant shard): its bounds collapse and it runs dummy
    waves.
    """
    intervals = np.asarray(intervals, dtype=np.int64)
    P_ = len(intervals) - 1
    D = int(num_devices)
    if D < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    weights = np.asarray(shard_nnz, dtype=np.float64)
    if len(weights) != P_:
        raise ValueError(
            f"shard_nnz has {len(weights)} entries for {P_} shards")
    if weights.sum() <= 0:
        weights = np.ones(P_, dtype=np.float64)
    total = float(weights.sum())
    owner = np.zeros(P_, dtype=np.int64)
    cum, d = 0.0, 0
    for p in range(P_):
        owner[p] = d
        cum += weights[p]
        while d < D - 1 and cum >= total * (d + 1) / D:
            d += 1
    bounds = np.empty(D + 1, dtype=np.int64)
    bounds[D] = intervals[-1]
    for dd in range(D - 1, -1, -1):
        owned = np.nonzero(owner == dd)[0]
        bounds[dd] = intervals[owned[0]] if owned.size else bounds[dd + 1]
    bounds[0] = intervals[0]
    return owner, bounds


# ---------------------------------------------------------------------------
def stack_layouts(parts, vdt=np.float32):
    """Stack per-device ELL layouts ``(cols, vals, slices, row_map)`` (None:
    a device with no shard) into common [D, L, C] / [D, L / GROUP_ROWS] /
    [D, S*C] arrays.  The padding is reduce-identity: padded rows hold
    cols -1 and belong to no slice (group id S, which the fold drops),
    padded slices map no virtual row (row_map -1)."""
    real = [p for p in parts if p is not None]
    L = max((p[0].shape[0] for p in real), default=ROW_ALIGN)
    C = max((p[0].shape[1] for p in real), default=LANE)
    S = max((p[3].shape[0] // C for p in real), default=1)
    D = len(parts)
    cols = np.full((D, L, C), -1, dtype=np.int32)
    vals = np.zeros((D, L, C), dtype=vdt)
    slices = np.full((D, L // GROUP_ROWS), S, dtype=np.int32)
    row_map = np.full((D, S * C), -1, dtype=np.int32)
    for d, p in enumerate(parts):
        if p is None:
            continue
        c, v, g, rm = p
        cols[d, :c.shape[0]] = c
        vals[d, :v.shape[0]] = v
        slices[d, :g.shape[0]] = g
        row_map[d, :rm.shape[0]] = rm
    return cols, vals, slices, row_map


# ---------------------------------------------------------------------------
class ShardedVSWEngine(VSWEngine):
    """VSWEngine whose edge sweep drives ``config.num_devices`` devices.

    The base class owns everything host-side (convergence, checkpoints,
    selective scheduling, epoch pinning); this subclass swaps the per-
    iteration internals through the documented seams:

    * ``_fetch_shard`` routes each shard to its owning device's cache
      partition (``PartitionedShardCache`` — one global budget, split);
    * ``_make_pipeline`` builds one prefetch lane per device
      (``ShardPipeline`` each, staging host-side on the worker thread);
    * ``_sweep`` splits the Bloom-scheduled shard list by owner and runs it
      in WAVES: wave ``w`` stacks each device's ``w``-th shard into one
      ``[D, L, C]`` batch, a ``shard_map``'ped step folds all D shards
      concurrently (single-writer: device ``d`` only writes its interval),
      then a merge step psums the changed-count and ``all_gather``s the
      per-device frontier blocks back into the replicated value array;
    * ``_io_marks`` / ``_io_stats`` account disk/stall/fetch per device and
      as honest sums (``IterationStats.device_*`` tuples).

    Bitwise identity with the single-device engine holds by construction:
    the same ELL kernels run on the same shards; wave padding appends only
    reduce-identity material (sentinel rows in no slice, slices mapping no
    virtual row) and the merge takes each row from exactly its owner
    device.
    """

    def __init__(self, store, program, config=None, *, cache=None, **kw):
        cfg = config if isinstance(config, EngineConfig) else EngineConfig()
        D = cfg.num_devices
        self._num_devices = D
        self._axis = "data"
        self._mesh = make_data_mesh(D, self._axis)
        shard_meta = store.properties["shards"]
        nnz = [int(m.get("nnz", 0)) for m in shard_meta]
        self._owner, self._bounds = assign_shards(
            np.asarray(store.intervals), nnz, D)
        self._block_lens = [int(self._bounds[d + 1] - self._bounds[d])
                            for d in range(D)]
        self._per_max = max(self._block_lens, default=1) or 1
        if not (isinstance(cache, PartitionedShardCache)
                and cache.num_partitions == D
                and np.array_equal(cache.owner, self._owner)):
            # sessions configured with num_devices build the partitioned
            # cache up front and share it; a per-run config override (or
            # direct construction) gets a private partitioned cache instead
            cache = PartitionedShardCache(
                store, self._owner, D, mode=cfg.cache_mode,
                budget_bytes=cfg.cache_budget_bytes,
                hot_fraction=cfg.cache_hot_fraction,
                promote_after=cfg.cache_promote_after)
        super().__init__(store, program, config, cache=cache, **kw)
        # the merge step slices [bounds[d], bounds[d] + per_max) and dummy
        # waves write into [n, n + R); grow the vertex padding to cover both
        need = self.n + self._per_max
        if need > self.n_pad:
            self.n_pad = need
            self._out_deg_dev = jnp.asarray(
                np.pad(self.out_deg,
                       (0, self.n_pad - self.n)).astype(np.float32))

    # -- construction seams ---------------------------------------------
    def _fetch_shard(self, p: int) -> ELLShard:
        # self.cache is the PartitionedShardCache: owner-routed
        return self.cache.get(p)

    def _make_pipeline(self):
        # one prefetch lane per device; lane d streams only device d's
        # shards, each fetch landing in that device's cache partition
        self._lanes = [
            ShardPipeline(self._get_shard, depth=self.config.prefetch_depth,
                          stage=self._stage, nbytes=ELLShard.decoded_nbytes)
            for _ in range(self._num_devices)
        ]
        return None  # per-lane stats replace the single self._pipeline

    def _stage(self, shard: ELLShard):
        """Host-side staging only (mmap page-in + copy on the worker
        thread); the device transfer happens at wave assembly, where the
        wave's common [D, L, C] layout is known."""
        return (self._materialize(shard.cols), self._materialize(shard.vals),
                shard.group_slices(),
                self._materialize(shard.staged_row_map(self.slices)),
                np.array([shard.val_scale, shard.val_zero], dtype=np.float32))

    # -- compiled steps ---------------------------------------------------
    def _build_steps(self) -> None:
        super()._build_steps()
        program, n, D = self.program, self.n, self._num_devices
        use_pallas = self.use_pallas
        ax, mesh = self._axis, self._mesh
        rep, shd = P(), P(ax)
        B, lens, per_max = self._bounds, self._block_lens, self._per_max
        starts_c = jnp.asarray(B[:D].astype(np.int32))
        ends_c = jnp.asarray(B[1:].astype(np.int32))

        # replicated src broadcast into the per-device [D, n_pad(, K)] dst
        self._dst_init = jax.jit(
            lambda s: jnp.broadcast_to(s[None], (D,) + s.shape),
            out_shardings=NamedSharding(mesh, shd))

        # one shard per device: the single-device shard step on the
        # device's slice of every sharded argument
        step = make_shard_step(program, n, self.segments, use_pallas,
                               self.batched)
        n_rep = 4 if self.batched else 2  # x, src[, aux, it]: replicated

        def wave(dst, *args):
            rep_args, shd_args = args[:n_rep], args[n_rep:]
            return step(dst[0], *rep_args, *(a[0] for a in shd_args))[None]

        wave_in = (shd,) + (rep,) * n_rep + (shd,) * 7

        if self.batched:
            def merge(dst, src):
                dstl = dst[0]
                d = jax.lax.axis_index(ax)
                b = starts_c[d]
                K = src.shape[1]
                own = jax.lax.dynamic_slice(dstl, (b, 0), (per_max, K))
                old = jax.lax.dynamic_slice(src, (b, 0), (per_max, K))
                real = (b + jnp.arange(per_max) < ends_c[d])[:, None]
                chm = program.changed(own, old) & real
                cnt = jax.lax.psum(jnp.sum(chm.astype(jnp.int32)), ax)
                gathered = jax.lax.all_gather(own, ax)  # [D, per_max, K]
                new_full = src
                for dd in range(D):
                    if lens[dd]:
                        new_full = jax.lax.dynamic_update_slice(
                            new_full, gathered[dd, : lens[dd]],
                            (int(B[dd]), 0))
                return new_full, cnt
        else:
            def merge(dst, src):
                dstl = dst[0]
                d = jax.lax.axis_index(ax)
                b = starts_c[d]
                own = jax.lax.dynamic_slice(dstl, (b,), (per_max,))
                old = jax.lax.dynamic_slice(src, (b,), (per_max,))
                real = b + jnp.arange(per_max) < ends_c[d]
                chm = program.changed(own, old) & real
                cnt = jax.lax.psum(jnp.sum(chm.astype(jnp.int32)), ax)
                gathered = jax.lax.all_gather(own, ax)  # [D, per_max]
                new_full = src
                for dd in range(D):
                    if lens[dd]:
                        new_full = jax.lax.dynamic_update_slice(
                            new_full, gathered[dd, : lens[dd]], (int(B[dd]),))
                return new_full, cnt

        self._wave_step = jax.jit(
            jax.shard_map(wave, mesh=mesh, in_specs=wave_in, out_specs=shd,
                          check_vma=False),
            donate_argnums=(0,))
        self._merge_step = jax.jit(
            jax.shard_map(merge, mesh=mesh, in_specs=(shd, rep),
                          out_specs=(rep, rep), check_vma=False),
            donate_argnums=(0,))

    # -- per-iteration seams ----------------------------------------------
    def _assemble_wave(self, entries):
        """Stack one shard per device (or a dummy) into the wave's common
        [D, L, C] layout (``stack_layouts``) and place it sharded over the
        mesh.  The padding is reduce-identity, so results stay bitwise
        equal to running each shard at its own bucketed shape.  Dummies (a
        device with no shard this wave) write their restored old values at
        ``start = n``, i.e. into the padding region, so they cannot revert
        a real row updated by an earlier wave.
        """
        D = self._num_devices
        # one vals dtype per wave (the shard_map step compiles per dtype); a
        # mixed wave — possible mid-migration of a store — dequantizes to
        # float32 on the host and ships identity qparams instead
        vdts = {e[2][1].dtype for e in entries if e is not None}
        mixed = len(vdts) > 1
        vdt = np.float32 if (mixed or not vdts) else vdts.pop()
        parts = [None] * D
        qp = np.tile(np.array([1.0, 0.0], dtype=np.float32), (D, 1))
        start = np.full(D, self.n, dtype=np.int32)
        nrows = np.zeros(D, dtype=np.int32)
        for d, e in enumerate(entries):
            if e is None:
                continue
            _p, shard, staged = e
            c, v, g, rm, q = staged
            if mixed and v.dtype != np.float32:
                v = dequantize_edge_vals(v, float(q[0]), float(q[1]))
                q = np.array([1.0, 0.0], dtype=np.float32)
            parts[d] = (c, v, g, rm)
            qp[d] = q
            start[d] = shard.start_vertex
            nrows[d] = shard.end_vertex - shard.start_vertex
        arrays = stack_layouts(parts, vdt) + (qp, start, nrows)
        # each device receives one slice of every array: charge its bytes
        # to the device's lane
        per_device = sum(a.nbytes for a in arrays) // D
        for lane in self._lanes:
            lane.stats.bump(h2d_bytes=per_device)
        sharding = NamedSharding(self._mesh, P(self._axis))
        return tuple(jax.device_put(a, sharding) for a in arrays)

    def _sweep(self, x, src, aux_dev, it_dev, schedule, epoch_check,
               it: int = 0):
        D = self._num_devices
        scheds = [[p for p in schedule if self._owner[p] == d]
                  for d in range(D)]
        waves = max(len(s) for s in scheds)
        dst = self._dst_init(src)
        streams = [self._lanes[d].stream(scheds[d], check=epoch_check,
                                         sweep=it)
                   for d in range(D)]
        try:
            for w in range(waves):
                entries = [next(streams[d]) if w < len(scheds[d]) else None
                           for d in range(D)]
                with span("graphmp.step", sweep=it, wave=w):
                    tail = self._assemble_wave(entries)
                    if self.batched:
                        dst = self._wave_step(dst, x, src, aux_dev, it_dev,
                                              *tail)
                    else:
                        dst = self._wave_step(dst, x, src, *tail)
        finally:
            for s in streams:
                s.close()  # run pipeline cleanup (reap prefetch workers)
        with span("graphmp.changed", sweep=it):
            new_src, changed_count = self._merge_step(dst, src)
            if int(changed_count) == 0:
                # the psum'd count short-circuits the full mask pull
                shape = ((self.n, src.shape[1]) if self.batched
                         else (self.n,))
                changed = np.zeros(shape, dtype=bool)
            else:
                changed = np.asarray(self._changed_fn(new_src, src))
        return new_src, changed

    def _io_marks(self):
        return ([(c.stats.disk_bytes, c.stats.hits, c.stats.misses,
                  c.stats.decode_seconds_saved) for c in self.cache.parts],
                [(l.stats.stall_seconds, l.stats.fetch_seconds,
                  l.stats.stage_seconds, l.stats.h2d_bytes,
                  l.stats.ell_slots, l.stats.ell_arcs)
                 for l in self._lanes])

    def _io_stats(self, marks) -> dict:
        cache_marks, lane_marks = marks
        d_disk, d_saved, hits, total = [], [], 0, 0
        for part, (disk0, hits0, misses0, saved0) in zip(self.cache.parts,
                                                         cache_marks):
            s = part.stats
            d_disk.append(s.disk_bytes - disk0)
            d_saved.append(s.decode_seconds_saved - saved0)
            hits += s.hits - hits0
            total += (s.hits - hits0) + (s.misses - misses0)
        d_stall = [l.stats.stall_seconds - m[0]
                   for l, m in zip(self._lanes, lane_marks)]
        d_fetch = [l.stats.fetch_seconds - m[1]
                   for l, m in zip(self._lanes, lane_marks)]
        return dict(
            disk_bytes=sum(d_disk),
            cache_hit_ratio=hits / total if total else 0.0,
            # lanes are drained on the one consumer thread, so its total
            # blocked time is the SUM of per-lane stalls; fetch work happens
            # per worker and also sums
            stall_seconds=sum(d_stall),
            fetch_seconds=sum(d_fetch),
            stage_seconds=sum(l.stats.stage_seconds - m[2]
                              for l, m in zip(self._lanes, lane_marks)),
            h2d_bytes=sum(l.stats.h2d_bytes - m[3]
                          for l, m in zip(self._lanes, lane_marks)),
            ell_slots=sum(l.stats.ell_slots - m[4]
                          for l, m in zip(self._lanes, lane_marks)),
            ell_arcs=sum(l.stats.ell_arcs - m[5]
                         for l, m in zip(self._lanes, lane_marks)),
            decode_seconds_saved=sum(d_saved),
            device_disk_bytes=tuple(d_disk),
            device_stall_seconds=tuple(d_stall),
            device_fetch_seconds=tuple(d_fetch),
        )


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DeviceShardedGraph:
    """Edges repartitioned so device d owns destination interval d (1-D).

    ``num_vertices`` is the TRUE vertex count; the device intervals tile
    ``padded_num_vertices`` (the next multiple of the device count), and
    every consumer masks the padding rows out of init/post/changed.
    """

    num_vertices: int          # true |V|
    padded_num_vertices: int   # |V| rounded up to a multiple of num_devices
    num_edges: int
    cols: np.ndarray           # [D, L, C] int32 (per-device ELL, common shape)
    vals: np.ndarray           # [D, L, C] float32
    slices: np.ndarray         # [D, L / GROUP_ROWS] int32 slice of each row group
    row_map: np.ndarray        # [D, S*C] int32 (local row within the device interval)
    out_deg: np.ndarray        # [padded_num_vertices] int64 (0 on padding)
    rows_per_device: int       # interval length padded_num_vertices / D
    blooms: list               # per-device source-vertex BloomFilters (replicated)


def partition_for_mesh(
    src: np.ndarray, dst: np.ndarray, num_vertices: int, num_devices: int,
    val: np.ndarray | None = None, ell_max_width: int = 256,
) -> DeviceShardedGraph:
    n_pad = ((num_vertices + num_devices - 1) // num_devices) * num_devices
    per = n_pad // num_devices
    shards = build_csr_shards(src, dst, n_pad, threshold_edge_num=1 << 62, val=val)
    # build_csr_shards with huge threshold yields one shard; re-cut at device bounds
    csr = shards[0]
    ells: list[ELLShard] = []
    blooms: list[BloomFilter] = []
    for d in range(num_devices):
        lo, hi = d * per, (d + 1) * per
        sub = dataclasses.replace(
            csr,
            shard_id=d,
            start_vertex=lo,
            end_vertex=hi,
            row=csr.row[lo : hi + 1] - csr.row[lo],
            col=csr.col[csr.row[lo] : csr.row[hi]],
            val=None if csr.val is None else csr.val[csr.row[lo] : csr.row[hi]],
        )
        ells.append(csr_to_ell(sub, max_width=ell_max_width))
        sources = np.unique(sub.col)
        blooms.append(BloomFilter.build(
            sources, num_bits=BloomFilter.sized_for(sources.size)))
    cols, vals, slices, row_map = stack_layouts(
        [(e.cols, e.vals, e.group_slices(), e.row_map) for e in ells])
    out_deg = np.bincount(src, minlength=n_pad).astype(np.int64)
    return DeviceShardedGraph(
        num_vertices=int(num_vertices), padded_num_vertices=n_pad,
        num_edges=len(src), cols=cols, vals=vals, slices=slices,
        row_map=row_map, out_deg=out_deg, rows_per_device=per, blooms=blooms,
    )


class DistributedVSW:
    """1-D distributed VSW prototype: the WHOLE graph resident on the mesh.

    The minimal mesh-semantics reference (and oracle target for
    ``ShardedVSWEngine``): ``partition_for_mesh`` places every edge on its
    owner device up front, so an iteration is one ``shard_map``'ped
    gather -> SpMV -> post with an ``all_gather`` frontier exchange and a
    psum'd changed-count — no disk, no cache, no prefetch.

    ``config`` (an ``EngineConfig``) shares the session-level tuning
    surface.  Honored fields: ``use_pallas`` (SpMV backend) and
    ``selective_threshold`` — below it, the replicated per-device Bloom
    filters (``DeviceShardedGraph.blooms``) gate which devices compute at
    all (a skipped device keeps its interval unchanged); every host probes
    the same filters, so the schedule needs no coordination.  The I/O
    fields (``cache_*``, ``prefetch_depth``, ``preload``) do not apply —
    there is no storage path here to tune; use ``ShardedVSWEngine`` (via
    ``GraphSession`` with ``num_devices > 1``) for the streaming engine.

    Padding correctness: vertex ids in ``[num_vertices,
    padded_num_vertices)`` exist only to even the device intervals.  They
    are initialized to zero (never by ``program.init``, which sees the TRUE
    ``n``), masked out of the changed-count, and sliced off the returned
    values, so they can neither absorb PageRank mass nor join the CC label
    space.
    """

    def __init__(self, graph: DeviceShardedGraph,
                 program: VertexProgram | str,
                 mesh: Mesh, axis: str = "data",
                 use_pallas: bool | str = "auto",
                 config: EngineConfig | None = None):
        if isinstance(program, str):
            program = get_app(program)
        self.g = graph
        self.program = program
        self.mesh = mesh
        self.axis = axis
        self.num_devices = graph.cols.shape[0]
        self.selective_threshold = EngineConfig.selective_threshold
        if config is not None:
            use_pallas = config.use_pallas
            self.selective_threshold = config.selective_threshold
        self.use_pallas = use_pallas
        self.n = graph.num_vertices
        self.n_pad = graph.padded_num_vertices
        edge_spec = P(axis)
        self._cols = jax.device_put(graph.cols, NamedSharding(mesh, edge_spec))
        self._vals = jax.device_put(graph.vals, NamedSharding(mesh, edge_spec))
        self._slices = jax.device_put(graph.slices,
                                      NamedSharding(mesh, edge_spec))
        self._rmap = jax.device_put(graph.row_map, NamedSharding(mesh, edge_spec))
        self._out_deg = jnp.asarray(graph.out_deg.astype(np.float32))
        self._iter_fn = self._build_iter()

    def _build_iter(self):
        program, n, per = self.program, self.n, self.g.rows_per_device
        semiring, use_pallas, axis = program.semiring, self.use_pallas, self.axis

        def device_iter(src_full, out_deg, cols, vals, slices, row_map, flags):
            # shard_map gives per-device blocks with a leading length-1 axis
            cols, vals, flag = cols[0], vals[0], flags[0]
            slices, row_map = slices[0], row_map[0]
            x = program.gather_transform(src_full, out_deg)
            seg = ell_spmv(x, cols, vals, slices, row_map, per, semiring,
                           use_pallas=use_pallas)
            d = jax.lax.axis_index(axis)
            old_own = jax.lax.dynamic_slice(src_full, (d * per,), (per,))
            new_own = program.post(seg, old_own, n).astype(src_full.dtype)
            # Bloom-skipped device: keep the old interval verbatim
            new_own = jnp.where(flag != 0, new_own, old_own)
            # padding rows (ids >= n) never count as changed
            real = d * per + jnp.arange(per) < n
            changed_own = program.changed(new_own, old_own) & real
            changed = jnp.sum(changed_own.astype(jnp.int32))
            new_full = jax.lax.all_gather(new_own, axis, tiled=True)
            changed_full = jax.lax.all_gather(changed_own, axis, tiled=True)
            changed_total = jax.lax.psum(changed, axis)
            return new_full, changed_full, changed_total

        spec_rep = P()
        fn = jax.shard_map(
            device_iter,
            mesh=self.mesh,
            in_specs=(spec_rep, spec_rep) + (P(self.axis),) * 5,
            out_specs=(spec_rep, spec_rep, spec_rep),
            check_vma=False,
        )
        return jax.jit(fn)

    def _schedule_flags(self, active_ids: np.ndarray | None,
                        active_ratio: float) -> np.ndarray:
        """Replicated-Bloom device schedule (host-side, deterministic)."""
        if active_ids is None or active_ratio >= self.selective_threshold:
            return np.ones(self.num_devices, dtype=bool)
        return np.array([b.might_contain_any(active_ids)
                         for b in self.g.blooms], dtype=bool)

    def run(self, max_iters: int = 100) -> tuple[np.ndarray, int]:
        n = self.n
        values, active = self.program.init(n, None, self.g.out_deg[:n])
        src = jnp.asarray(
            np.pad(values.astype(np.float32), (0, self.n_pad - n)))
        active_ids = np.nonzero(np.asarray(active, dtype=bool))[0]
        active_ratio = active_ids.size / max(n, 1)
        flag_sharding = NamedSharding(self.mesh, P(self.axis))
        it_done = 0
        for it in range(1, max_iters + 1):
            flags = self._schedule_flags(active_ids, active_ratio)
            if not flags.any():
                break  # every device Bloom-skipped: nothing can change
            flags_dev = jax.device_put(flags.astype(np.int32), flag_sharding)
            src, changed_full, changed_total = self._iter_fn(
                src, self._out_deg, self._cols, self._vals, self._slices,
                self._rmap, flags_dev)
            it_done = it
            if int(changed_total) == 0:
                break
            mask = np.asarray(changed_full)[:n]
            active_ids = np.nonzero(mask)[0]
            active_ratio = active_ids.size / max(n, 1)
        return np.asarray(src)[:n], it_done


def spmv_2d(x: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
            slices: jnp.ndarray, row_map: jnp.ndarray, num_segments: int,
            semiring: str, mesh: Mesh, dst_axis: str = "data",
            src_axis: str = "model",
            use_pallas: bool | str = "auto") -> jnp.ndarray:
    """2-D partitioned SpMV: dst intervals over `dst_axis`, source ranges over
    `src_axis`.  Each device folds its (dst-block × src-range) ELL tile; a
    psum over `src_axis` combines partials (min-semirings use pmin via
    all_gather+fold).  x is sharded by source range; cols are *local* source
    indices.  Returns per-dst-interval partials [num_segments] sharded over
    `dst_axis`."""
    from repro.core.semiring import SEMIRINGS
    from repro.kernels.spmv.ops import ell_gather_fold
    from repro.kernels.spmv.ref import slice_combine

    sem = SEMIRINGS[semiring]

    def local(x_blk, cols_b, vals_b, slices_b, row_map_b):
        # x: [n] split over src_axis -> [n/S]; edge tensors: [D, S, ...] -> [1, 1, ...]
        cols_b, vals_b = cols_b[0, 0], vals_b[0, 0]
        slices_b, row_map_b = slices_b[0, 0], row_map_b[0, 0]
        groups = ell_gather_fold(x_blk, cols_b, vals_b, semiring,
                                 use_pallas=use_pallas)
        seg = slice_combine(groups[None], slices_b, row_map_b, num_segments,
                            semiring)[:, 0]
        if sem.is_plus:
            seg = jax.lax.psum(seg, src_axis)
        else:
            allseg = jax.lax.all_gather(seg, src_axis)  # [S, R]
            seg = (jnp.max if sem.is_max else jnp.min)(allseg, axis=0)
        return seg[None]

    edge = P(dst_axis, src_axis)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(src_axis), edge, edge, edge, edge),
        out_specs=P(dst_axis),
        check_vma=False,
    )
    return fn(x, cols, vals, slices, row_map)
