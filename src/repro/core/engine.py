"""VSW engine: the paper's Algorithm 2 on a JAX device.

Faithful structure:
  * ``SrcVertexArray`` / ``DstVertexArray`` live on-device for the whole run
    (vertices never touch disk until the final checkpoint) — VSW's core claim;
  * edges stream shard-by-shard through the compressed cache (host tier) to
    the device; each shard updates exactly its destination interval, so the
    update is single-writer and lock/atomic-free.  The stream runs through a
    ``ShardPipeline``: with ``config.prefetch_depth > 0`` the next shards'
    disk reads, decompression and host->device staging happen on a background
    thread while the current shard's SpMV runs (paper §2.3's overlap;
    depth 1 = double buffering, depth 0 = the synchronous path);
  * after each iteration the active-vertex set is extracted; when
    ``active_ratio < selective_threshold`` (paper: 0.001) the per-shard Bloom
    filters gate shard loading (Algorithm 2 line 5).

Construction: engines are normally built *by* a ``repro.session.GraphSession``
which owns the store, ONE ``CompressedShardCache``, and the device-resident
degree arrays shared by every application (paper §2.2's "preprocess once,
serve many").  Tuning lives in the frozen ``EngineConfig``; the old kwarg
signature (``cache_mode=...`` etc.) still works as a deprecated shim that
builds a private cache.

Fault tolerance: the VSW invariant makes engine state tiny (2C|V| + cursor);
``checkpoint_every`` snapshots (values, iteration) with atomic rename, and
``run(resume=True)`` restarts from the latest snapshot.

Multi-device: ``config.num_devices > 1`` routes sessions to
``repro.core.distributed.ShardedVSWEngine``, a subclass that overrides the
seams below (``_fetch_shard`` / ``_make_pipeline`` / ``_sweep`` /
``_io_marks`` / ``_io_stats``) to drive N devices per iteration while
``iter_run``'s convergence/checkpoint/epoch logic stays shared.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import warnings
from pathlib import Path
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.apps import BatchedVertexProgram, VertexProgram
from repro.core.cache import CompressedShardCache
from repro.core.pipeline import ShardPipeline
from repro.core.shards import ELLShard, segment_rows, store_slices
from repro.core.spans import span
from repro.graph.source import ConcurrentMutationError, ShardSource
from repro.kernels.spmv.ops import ell_spmv, ell_spmv_batch

_VALID_CACHE_MODES = (0, 1, 2, 3, 4)


def _device_nbytes(staged) -> int:
    """Bytes of the device arrays one ``_stage`` call returned."""
    return sum(a.nbytes for a in staged)


def _store_epoch(store) -> int:
    """Graph epoch of a store; frozen backends (no ``epoch``) sit at 0."""
    fn = getattr(store, "epoch", None)
    return int(fn()) if callable(fn) else 0


def _env(name: str, default, cast):
    raw = os.environ.get(name)
    if raw is None or raw == "":  # unset/empty (CI matrix legs) -> default
        return default
    try:
        return cast(raw)
    except (TypeError, ValueError):
        warnings.warn(f"ignoring unparseable {name}={raw!r}", RuntimeWarning)
        return default


def _cast_mode(raw: str):
    return raw if raw in ("auto", "adaptive") else int(raw)


def _cast_tristate(raw: str):
    low = raw.lower()
    if low == "auto":
        return "auto"
    return low in ("1", "true", "yes", "on")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Validated, immutable engine tuning (replaces the old kwarg soup).

    ``from_env()`` reads ``GRAPHMP_*`` environment overrides; ``replace()``
    derives per-run variants without mutating the shared default.  Fields
    (env var in parentheses; see docs/REPRODUCING.md for the full table):

    cache_mode (``GRAPHMP_CACHE_MODE``):
        ``"auto"``/``"adaptive"`` — the two-tier adaptive edge cache
        (default); an int 0-4 — the paper's static §2.4.2 modes (0 = no
        cache, 1 = raw arrays, 2-4 = zstd levels 1/3/9).
    cache_budget_bytes (``GRAPHMP_CACHE_BUDGET``, legacy alias
    ``GRAPHMP_CACHE_BUDGET_BYTES``):
        Strict host-byte budget for the edge cache, covering both tiers;
        0 means "no application cache" (degrades to mode 0).
    cache_hot_fraction (``GRAPHMP_CACHE_HOT_FRACTION``):
        Adaptive cache only: fraction of the budget the hot (decompressed)
        tier may occupy, in (0, 1].
    cache_promote_after (``GRAPHMP_CACHE_PROMOTE_AFTER``):
        Adaptive cache only: accesses (including the admitting miss) after
        which a cold shard becomes a promotion candidate (>= 1).
    selective_threshold (``GRAPHMP_SELECTIVE_THRESHOLD``):
        Active-vertex ratio below which Bloom-filter selective scheduling
        kicks in (paper: 0.001); negative disables it.
    use_pallas (``GRAPHMP_USE_PALLAS``):
        SpMV kernel backend: True/False, or ``"auto"`` to pick per platform.
    preload (``GRAPHMP_PRELOAD``):
        Pin every shard through the cache at engine construction.
    prefetch_depth (``GRAPHMP_PREFETCH``):
        Shards fetched ahead on a background thread (0 = synchronous,
        1 = double buffering).
    num_devices (``GRAPHMP_DEVICES``):
        Devices one VSW iteration drives concurrently.  1 (default) is the
        single-device engine; > 1 routes runs through the sharded engine
        (``repro.core.distributed.ShardedVSWEngine``): the shard schedule,
        edge-cache partitions and prefetch lanes split per device and the
        value matrix is partitioned over a 1-D ``jax.sharding.Mesh``.
        Requires that many local jax devices (on CPU:
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """

    cache_mode: int | str = "auto"
    cache_budget_bytes: int = 1 << 30
    cache_hot_fraction: float = 0.5
    cache_promote_after: int = 2
    selective_threshold: float = 1e-3
    use_pallas: bool | str = "auto"
    preload: bool = False
    prefetch_depth: int = 0
    num_devices: int = 1

    def __post_init__(self):
        mode = self.cache_mode
        if not (mode in ("auto", "adaptive")
                or (isinstance(mode, int)
                    and not isinstance(mode, bool)
                    and mode in _VALID_CACHE_MODES)):
            raise ValueError(
                f"cache_mode must be 'auto', 'adaptive' or one of "
                f"{_VALID_CACHE_MODES}, got {mode!r}")
        if not isinstance(self.cache_budget_bytes, int) \
                or isinstance(self.cache_budget_bytes, bool) \
                or self.cache_budget_bytes < 0:
            raise ValueError(
                f"cache_budget_bytes must be an int >= 0 (0 = no cache), "
                f"got {self.cache_budget_bytes!r}")
        if not isinstance(self.cache_hot_fraction, (int, float)) \
                or isinstance(self.cache_hot_fraction, bool) \
                or not 0.0 < self.cache_hot_fraction <= 1.0:
            raise ValueError(
                f"cache_hot_fraction must be in (0, 1], "
                f"got {self.cache_hot_fraction!r}")
        if not isinstance(self.cache_promote_after, int) \
                or isinstance(self.cache_promote_after, bool) \
                or self.cache_promote_after < 1:
            raise ValueError(
                f"cache_promote_after must be an int >= 1, "
                f"got {self.cache_promote_after!r}")
        if not np.isfinite(self.selective_threshold):
            raise ValueError(
                f"selective_threshold must be finite, "
                f"got {self.selective_threshold!r}")
        if self.use_pallas not in (True, False, "auto"):
            raise ValueError(
                f"use_pallas must be True, False or 'auto', "
                f"got {self.use_pallas!r}")
        if not isinstance(self.prefetch_depth, int) \
                or isinstance(self.prefetch_depth, bool) \
                or self.prefetch_depth < 0:
            raise ValueError(
                f"prefetch_depth must be a non-negative int, "
                f"got {self.prefetch_depth!r}")
        if not isinstance(self.num_devices, int) \
                or isinstance(self.num_devices, bool) \
                or self.num_devices < 1:
            raise ValueError(
                f"num_devices must be an int >= 1, got {self.num_devices!r}")

    @classmethod
    def from_env(cls, **overrides) -> "EngineConfig":
        """Defaults with GRAPHMP_* environment overrides applied underneath
        explicit keyword overrides."""
        budget_default = _env("GRAPHMP_CACHE_BUDGET_BYTES",  # legacy alias
                              cls.cache_budget_bytes, int)
        base = dict(
            cache_mode=_env("GRAPHMP_CACHE_MODE", cls.cache_mode, _cast_mode),
            cache_budget_bytes=_env("GRAPHMP_CACHE_BUDGET",
                                    budget_default, int),
            cache_hot_fraction=_env("GRAPHMP_CACHE_HOT_FRACTION",
                                    cls.cache_hot_fraction, float),
            cache_promote_after=_env("GRAPHMP_CACHE_PROMOTE_AFTER",
                                     cls.cache_promote_after, int),
            selective_threshold=_env("GRAPHMP_SELECTIVE_THRESHOLD",
                                     cls.selective_threshold, float),
            use_pallas=_env("GRAPHMP_USE_PALLAS", cls.use_pallas,
                            _cast_tristate),
            preload=_env("GRAPHMP_PRELOAD", cls.preload,
                         lambda r: _cast_tristate(r) is True),
            prefetch_depth=_env("GRAPHMP_PREFETCH", cls.prefetch_depth, int),
            num_devices=_env("GRAPHMP_DEVICES", cls.num_devices, int),
        )
        base.update(overrides)
        return cls(**base)

    def replace(self, **changes) -> "EngineConfig":
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass
class IterationStats:
    iteration: int
    seconds: float
    active_ratio: float
    shards_processed: int
    shards_skipped: int
    disk_bytes: int
    cache_hit_ratio: float
    selective_enabled: bool
    edges_processed: int = 0    # sum of nnz over the shards actually run
    stall_seconds: float = 0.0  # time the compute loop waited on shard I/O
    fetch_seconds: float = 0.0  # fetch+stage time (overlapped when prefetching)
    stage_seconds: float = 0.0  # of which host->device staging
    h2d_bytes: int = 0          # bytes staged to the device
    ell_slots: int = 0          # ELL slots staged, padding included
    ell_arcs: int = 0           # of which hold an edge (the shards' nnz)
    decode_seconds_saved: float = 0.0  # decompression cost hot-tier hits skipped
    # multi-device runs only (empty tuples otherwise): per-device splits of
    # the aggregates above — one entry per device, summing (disk/fetch) or
    # totalling along the consumer's critical path (stall) to the aggregate,
    # so Table-3 accounting stays honest across cache partitions
    device_disk_bytes: tuple = ()
    device_stall_seconds: tuple = ()
    device_fetch_seconds: tuple = ()


@dataclasses.dataclass
class RunResult:
    """What one application run produced.

    ``values`` holds one float per vertex (ranks for PageRank, distances
    for SSSP/BFS, component ids for CC); ``iterations`` is how many sweeps
    ran, ``converged`` whether the frontier emptied before ``max_iters``,
    and ``history`` one ``IterationStats`` per iteration (per-iteration
    seconds, active ratio, shards processed/skipped, disk bytes, cache hit
    ratio, stall/fetch/stage seconds, bytes staged to the device).
    ``total_seconds``/``edges_per_second`` aggregate it.
    """

    values: np.ndarray
    iterations: int
    history: list[IterationStats]
    converged: bool
    # graph epoch pinned at run start (0 = frozen store) and program tag —
    # what session.run_incremental validates a `prev` result against
    epoch: int = 0
    tag: str | None = None

    @property
    def total_seconds(self) -> float:
        return sum(h.seconds for h in self.history)

    @property
    def total_edges_processed(self) -> int:
        return sum(h.edges_processed for h in self.history)

    def edges_per_second(self, num_edges: int | None = None) -> float:
        """Throughput over edges actually processed.

        Shards hold unequal edge counts, so skipped shards are weighted by
        their per-shard nnz (recorded in each IterationStats), not by shard
        count — selective-scheduling runs report honest edges/sec.
        ``num_edges`` is only a fallback for histories recorded before
        per-iteration edge counts existed (assumes no shard skipping).
        """
        processed = self.total_edges_processed
        if processed == 0 and num_edges is not None \
                and not any(h.selective_enabled for h in self.history):
            processed = num_edges * len(self.history)
        return processed / max(self.total_seconds, 1e-9)


@dataclasses.dataclass
class BatchRunResult(RunResult):
    """Result of a batched (multi-frontier) run: ``values`` is [n, K].

    ``iterations``/``history``/``converged`` describe the shared sweep;
    ``column_iterations[k]`` counts only the iterations column k entered with
    a non-empty frontier (its honest cost — a landmark that converged in 4
    hops does not get billed for the 40-hop straggler's sweeps).  The counts
    are checkpointed, so they span resume boundaries even though ``history``
    only covers the current run.
    """

    column_iterations: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, dtype=np.int64))
    column_converged: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, dtype=bool))

    @property
    def num_columns(self) -> int:
        return self.values.shape[1]

    def column(self, k: int) -> RunResult:
        """Per-column view as a plain RunResult.

        ``iterations`` is the lifetime sweep count (spans resumes);
        ``history`` covers only this run, truncated to the iterations the
        column was live for here.  Frontiers only shrink, so a column live
        at a resume point was live for the entire pre-resume prefix —
        lifetime count minus the resume offset is its in-run live count.
        """
        iters = int(self.column_iterations[k])
        pre = self.history[0].iteration if self.history else 0
        return RunResult(values=self.values[:, k], iterations=iters,
                         history=self.history[: max(0, iters - pre)],
                         converged=bool(self.column_converged[k]),
                         epoch=self.epoch)

    def columns(self) -> list[RunResult]:
        return [self.column(k) for k in range(self.num_columns)]


def make_shard_step(program, n: int, segments: int, use_pallas="auto",
                    batched: bool = False):
    """The device work of one shard, to be jitted (``jit_shard_step``):
    gather, fold and slice combine into ``segments`` partials, then
    ``program.post`` on the destination rows ``[start, start + segments)``,
    keeping rows at or past ``num_rows`` (the next interval's) as they
    were.  Signature ``(dst, x, src, [aux, it,] cols, vals, slices,
    row_map, qp, start, num_rows) -> dst`` (aux and it for batched
    programs)."""
    semiring, R = program.semiring, segments
    if not batched:
        def shard_step(dst, x, src, cols, vals, slices, row_map, qp, start,
                       num_rows):
            seg = ell_spmv(x, cols, vals, slices, row_map, R, semiring,
                           use_pallas=use_pallas, qparams=qp)
            old_slice = jax.lax.dynamic_slice(src, (start,), (R,))
            new_slice = program.post(seg, old_slice, n).astype(dst.dtype)
            keep = jnp.arange(R) < num_rows
            new_slice = jnp.where(keep, new_slice, old_slice)
            return jax.lax.dynamic_update_slice(dst, new_slice, (start,))
        return shard_step

    # [n_pad, K] value matrix: one edge sweep advances K frontiers.
    # Per-column constants (PPR's reset vector) arrive through the runtime
    # ``aux`` argument so the compiled step — and therefore the engine — is
    # shared across source/seed sets (jit_signature).
    has_aux = getattr(program, "make_aux", None) is not None
    # phase-dependent programs (triangle counting's two-pass probe)
    # additionally receive the iteration number as a DEVICE scalar — a
    # runtime argument, so every iteration reuses one compiled step
    wants_it = getattr(program, "wants_iteration", False)

    def shard_step(dst, x, src, aux, it, cols, vals, slices, row_map, qp,
                   start, num_rows):
        K = src.shape[1]
        seg = ell_spmv_batch(x, cols, vals, slices, row_map, R, semiring,
                             use_pallas=use_pallas, qparams=qp)
        old_slice = jax.lax.dynamic_slice(src, (start, 0), (R, K))
        rows = start + jnp.arange(R)
        aux_slice = (jax.lax.dynamic_slice(aux, (start, 0), (R, K))
                     if has_aux else None)
        if wants_it:
            new_slice = program.post(seg, old_slice, rows, n, aux_slice, it)
        else:
            new_slice = program.post(seg, old_slice, rows, n, aux_slice)
        new_slice = new_slice.astype(dst.dtype)
        keep = (jnp.arange(R) < num_rows)[:, None]
        new_slice = jnp.where(keep, new_slice, old_slice)
        return jax.lax.dynamic_update_slice(dst, new_slice, (start, 0))
    return shard_step


_LEGACY_KWARGS = ("cache_mode", "cache_budget_bytes", "selective_threshold",
                  "use_pallas", "preload", "prefetch_depth")


class VSWEngine:
    """One vertex program bound to a graph store (Algorithm 2 executor).

    New API::

        session = GraphSession(store, config)
        result = session.run("pagerank", max_iters=30)

    or explicitly ``VSWEngine(store, program, config)``.  The old keyword
    signature (``VSWEngine(store, prog, cache_mode=2, ...)``) is kept as a
    deprecated shim and builds a private cache.
    """

    def __init__(
        self,
        store: ShardSource,
        program: VertexProgram,
        config: EngineConfig | int | str | None = None,
        *,
        cache: CompressedShardCache | None = None,
        vertex_info: tuple[np.ndarray, np.ndarray] | None = None,
        blooms: list | None = None,
        out_deg_dev: jnp.ndarray | None = None,
        n_pad: int | None = None,
        graph_epoch: int | None = None,
        observers: list | None = None,
        **legacy,
    ):
        if config is not None and not isinstance(config, EngineConfig):
            # old positional cache_mode slot
            legacy.setdefault("cache_mode", config)
            config = None
        unknown = set(legacy) - set(_LEGACY_KWARGS)
        if unknown:
            raise TypeError(f"unexpected VSWEngine arguments: {sorted(unknown)}")
        if legacy:
            warnings.warn(
                "VSWEngine(cache_mode=..., cache_budget_bytes=..., ...) is "
                "deprecated; pass an EngineConfig (or use GraphSession, which "
                "shares one compressed cache across applications)",
                DeprecationWarning, stacklevel=2)
            config = (config or EngineConfig()).replace(**legacy)
        self.config = config or EngineConfig()
        self.store = store
        self.program = program
        self.batched = isinstance(program, BatchedVertexProgram)
        self.cache = cache if cache is not None else CompressedShardCache(
            store, mode=self.config.cache_mode,
            budget_bytes=self.config.cache_budget_bytes,
            hot_fraction=self.config.cache_hot_fraction,
            promote_after=self.config.cache_promote_after)
        # telemetry taps: callables invoked with each IterationStats as it
        # is produced (GraphSession shares ONE list across all its engines,
        # so a MetricsHub attached mid-flight sees every later iteration).
        # Observer failures are swallowed — monitoring must never alter or
        # abort a computation.
        self.observers: list = observers if observers is not None else []
        self.selective_threshold = self.config.selective_threshold
        self.use_pallas = self.config.use_pallas
        self.preload = self.config.preload
        self.n = store.num_vertices
        # graph epoch the degree/bloom/meta arrays below were read at; a
        # mutable store moving past it triggers _sync_graph_state per run
        if graph_epoch is not None:
            self._graph_epoch = int(graph_epoch)
        else:
            self._graph_epoch = _store_epoch(store) if vertex_info is None else 0
        self._sync_lock = threading.Lock()
        self.in_deg, self.out_deg = (vertex_info if vertex_info is not None
                                     else store.read_vertex_info())
        self.blooms = blooms if blooms is not None else store.read_all_blooms()
        self.intervals = store.intervals
        self.P = store.num_shards
        shard_meta = store.properties["shards"]
        self._shard_nnz = [int(m.get("nnz", 0)) for m in shard_meta]
        self.slices = store_slices(shard_meta)
        # every shard step folds into, and updates, this many rows from its
        # interval's start (intervals never move, so neither does it)
        self.segments = segment_rows(self.intervals)
        # pad the vertex arrays so every such slice is in-bounds
        self.n_pad = n_pad if n_pad is not None else self.n + self.segments
        if out_deg_dev is not None:
            self._out_deg_dev = out_deg_dev
        else:
            self._out_deg_dev = jnp.asarray(
                np.pad(self.out_deg, (0, self.n_pad - self.n)).astype(np.float32))
        self._build_steps()
        self._preloaded: dict[int, ELLShard] = {}
        if self.preload:
            for p in range(self.P):
                self._preloaded[p] = self._fetch_shard(p)
        # ALL shard consumption goes through the pipeline — depth 0 is the
        # synchronous path, depth >= 1 prefetches + stages on a worker thread
        # (the sharded engine overrides _make_pipeline with one lane per
        # device and leaves self._pipeline as None)
        self._pipeline = self._make_pipeline()
        self.last_result: RunResult | None = None
        # serializes run() calls on this engine: concurrent clients (the
        # serving layer) sharing one jitted engine run back-to-back instead
        # of interleaving pipeline stats and per-iteration disk accounting
        self._run_lock = threading.Lock()

    @classmethod
    def from_session(cls, session, program: VertexProgram,
                     config: EngineConfig | None = None) -> "VSWEngine":
        """Build an engine that shares the session's cache + degree arrays."""
        return cls(
            session.store, program, config or session.config,
            cache=session.cache,
            vertex_info=(session.in_deg, session.out_deg),
            blooms=session.blooms,
            out_deg_dev=session.out_deg_dev,
            n_pad=session.n_pad,
            graph_epoch=getattr(session, "_graph_epoch", None),
            observers=getattr(session, "iteration_observers", None),
        )

    # ------------------------------------------------------------------
    def _build_steps(self) -> None:
        program, n, R = self.program, self.n, self.segments
        use_pallas = self.use_pallas

        # out-degrees arrive as a RUNTIME argument, never a closure constant:
        # a jit closure would bake the degree array at trace time and
        # silently keep serving stale degrees after a graph mutation
        @jax.jit
        def gather_fn(values, out_deg):
            return program.gather_transform(values, out_deg)

        # one compile per ELL shape (rows L, slices S): both are bucketed
        self._shard_step = jax.jit(
            make_shard_step(program, n, R, use_pallas, self.batched),
            donate_argnums=(0,))
        self._gather_fn = gather_fn

        @jax.jit
        def changed_fn(new, old):
            return program.changed(new[: self.n], old[: self.n])

        self._changed_fn = changed_fn

    # ------------------------------------------------------------------
    @property
    def _ckpt_tag(self) -> str:
        """Program identity recorded in checkpoints: name + frontier ids."""
        return self._tag_for(self.program)

    @staticmethod
    def _tag_for(program) -> str:
        return f"{program.name}:{tuple(program.sources)}"

    def _check_program(self, program):
        """A run-time program substitute must be jit-compatible: equal
        non-None ``jit_signature`` guarantees the jitted step closures built
        from ``self.program`` compute exactly its device functions (only
        host-side init / sources / checkpoint tags differ).

        The ``__code__`` comparison is a tripwire for a broken claim: fresh
        instances from the same factory (and rename-only
        ``dataclasses.replace`` derivatives like bfs) share code objects for
        their device callables, but a program that kept an inherited
        signature while overriding gather/post/changed does not — running it
        here would silently execute the OLD compiled functions."""
        if program is None or program is self.program:
            return self.program
        sig = getattr(program, "jit_signature", None)
        if sig is None or sig != self.program.jit_signature:
            raise ValueError(
                f"program {program.name!r} (jit_signature={sig!r}) is not "
                f"jit-compatible with this engine's {self.program.name!r} "
                f"(jit_signature={self.program.jit_signature!r})")
        for attr in ("gather_transform", "post", "changed"):
            mine = getattr(getattr(self.program, attr), "__code__", None)
            theirs = getattr(getattr(program, attr), "__code__", None)
            if mine is not theirs:
                raise ValueError(
                    f"program {program.name!r} claims jit_signature {sig!r} "
                    f"but its {attr} differs from this engine's compiled one "
                    f"— a dataclasses.replace() that overrides device "
                    f"callables must also replace jit_signature")
        return program

    def _fetch_shard(self, p: int) -> ELLShard:
        """Raw cache fetch (no preload shortcut) — the single overridable
        seam that decides WHICH cache a shard comes from (the sharded engine
        routes it to the owning device's cache partition)."""
        return self.cache.get(p)

    def _make_pipeline(self):
        """Build the shard stream consumed by ``_sweep``."""
        return ShardPipeline(
            self._get_shard, depth=self.config.prefetch_depth,
            stage=self._stage, nbytes=ELLShard.decoded_nbytes,
            h2d=_device_nbytes)

    def _get_shard(self, p: int) -> ELLShard:
        if p in self._preloaded:
            return self._preloaded[p]
        return self._fetch_shard(p)

    def _sync_graph_state(self) -> None:
        """Refresh graph-derived engine state after a store mutation.

        Cheap no-op while the store's epoch matches the one the current
        degree/bloom/shard-meta arrays were read at.  On an epoch change:
        re-read vertex info, rebuild the device out-degree array, recompute
        shard nnz/rows (``n_pad`` only ever grows, so jitted shapes stay
        stable when possible), and re-read Blooms — but ONLY for shards
        whose own epoch moved (the session shares one blooms list across
        engines; refreshing it in place keeps every engine consistent).
        """
        if _store_epoch(self.store) == self._graph_epoch:
            return
        with self._sync_lock:
            cur = _store_epoch(self.store)
            prev = self._graph_epoch
            if cur == prev:
                return
            self.in_deg, self.out_deg = self.store.read_vertex_info()
            shard_meta = self.store.properties["shards"]
            self._shard_nnz = [int(m.get("nnz", 0)) for m in shard_meta]
            self.slices = store_slices(shard_meta)
            self._out_deg_dev = jnp.asarray(
                np.pad(self.out_deg,
                       (0, self.n_pad - self.n)).astype(np.float32))
            shard_epoch = getattr(self.store, "shard_epoch", None)
            for p in range(self.P):
                if shard_epoch is None or shard_epoch(p) > prev:
                    self.blooms[p] = self.store.read_bloom(p)
                    if p in self._preloaded:
                        self._preloaded[p] = self._fetch_shard(p)
            self._graph_epoch = cur

    @staticmethod
    def _materialize(arr: np.ndarray) -> np.ndarray:
        """Read-only arrays are mmap-backed views (packed backend): copy them
        so the page-in happens HERE — on the prefetch thread, hideable —
        instead of via jax aliasing the mapping and faulting inside the SpMV
        (which would also pin the mmap open past session close)."""
        return arr if arr.flags.writeable else np.array(arr)

    def _stage(self, shard: ELLShard):
        """Host->device staging; runs on the prefetch thread when depth > 0,
        so the transfer overlaps the previous shard's SpMV."""
        return (jnp.asarray(self._materialize(shard.cols)),
                jnp.asarray(self._materialize(shard.vals)),
                jnp.asarray(shard.group_slices()),
                jnp.asarray(self._materialize(
                    shard.staged_row_map(self.slices))),
                jnp.asarray(np.array([shard.val_scale, shard.val_zero],
                                     dtype=np.float32)))

    def _schedule(self, active_ids: np.ndarray | None, active_ratio: float) -> tuple[list[int], bool]:
        """Algorithm 2 line 5: all shards, unless selective scheduling kicks in."""
        if (
            active_ids is None
            or active_ratio >= self.selective_threshold
        ):
            return list(range(self.P)), False
        keep = [p for p in range(self.P) if self.blooms[p].might_contain_any(active_ids)]
        return keep, True

    # ------------------------------------------------------------------
    # iteration internals — each one an override seam for the sharded engine
    def _io_marks(self):
        """Snapshot of the cache/pipeline counters an iteration deltas
        against (opaque to iter_run; paired with ``_io_stats``)."""
        cs, ps = self.cache.stats, self._pipeline.stats
        return (cs.disk_bytes, cs.hits, cs.misses, cs.decode_seconds_saved,
                ps.stall_seconds, ps.fetch_seconds, ps.stage_seconds,
                ps.h2d_bytes, ps.ell_slots, ps.ell_arcs)

    def _io_stats(self, marks) -> dict:
        """IterationStats I/O fields as deltas against ``marks``."""
        (disk0, hits0, misses0, saved0, stall0, fetch0, stage0, h2d0,
         slots0, arcs0) = marks
        cs, ps = self.cache.stats, self._pipeline.stats
        d_hits = cs.hits - hits0
        d_total = d_hits + cs.misses - misses0
        return dict(
            disk_bytes=cs.disk_bytes - disk0,
            cache_hit_ratio=d_hits / d_total if d_total else 0.0,
            stall_seconds=ps.stall_seconds - stall0,
            fetch_seconds=ps.fetch_seconds - fetch0,
            stage_seconds=ps.stage_seconds - stage0,
            h2d_bytes=ps.h2d_bytes - h2d0,
            ell_slots=ps.ell_slots - slots0,
            ell_arcs=ps.ell_arcs - arcs0,
            decode_seconds_saved=cs.decode_seconds_saved - saved0,
        )

    def _sweep(self, x, src, aux_dev, it_dev, schedule, epoch_check,
               it: int = 0):
        """One edge sweep (iteration ``it``): stream the scheduled shards,
        fold each into the destination array.  Returns ``(new values
        [n_pad(, K)], changed mask [n(, K)] as a numpy bool array)``."""
        dst = src + 0.0  # materialize a copy: the shard step donates its dst
        for p, shard, dev in self._pipeline.stream(schedule,
                                                   check=epoch_check,
                                                   sweep=it):
            tail = (*dev, shard.start_vertex,
                    shard.end_vertex - shard.start_vertex)
            with span("graphmp.step", sweep=it, shard=p):
                if self.batched:
                    dst = self._shard_step(dst, x, src, aux_dev, it_dev,
                                           *tail)
                else:
                    dst = self._shard_step(dst, x, src, *tail)
        with span("graphmp.changed", sweep=it):
            changed = np.asarray(self._changed_fn(dst, src))
        return dst, changed

    # ------------------------------------------------------------------
    def iter_run(
        self,
        max_iters: int = 200,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        program: VertexProgram | None = None,
        init_state: tuple[np.ndarray, np.ndarray] | None = None,
    ) -> Iterator[IterationStats]:
        """Generator form of ``run``: yields an IterationStats after every
        iteration (live monitoring), returns the RunResult on exhaustion
        (also stored in ``self.last_result``).  Batched programs return a
        ``BatchRunResult`` with [n, K] values and per-column accounting.

        ``program`` substitutes a jit-compatible program (equal
        ``jit_signature``) for this run only: the engine keeps its compiled
        shard steps while ``init``/``sources``/checkpoint tags come from the
        substitute.  This is how one engine answers e.g. SSSP from any
        source without recompiling — no engine state is mutated, so distinct
        runs with distinct programs can share the instance.

        ``init_state`` replaces ``program.init`` with explicit
        ``(values, active_mask)`` arrays — how incremental recompute seeds
        the frontier from a previous result's fixpoint.  Mutually exclusive
        with ``resume``.

        The run **pins the store's graph epoch at start**: every shard fetch
        asserts the shard has not moved past it, and a concurrent
        ``apply()`` therefore raises ``ConcurrentMutationError`` instead of
        mixing epochs into one result.

        The run is one ``graphmp.run`` span with its ``app`` and its
        ``sources`` (space-separated), so a trace tells short runs, such as
        SSSP searches, apart."""
        program = self._check_program(program)
        with span("graphmp.run", app=program.name,
                  sources=" ".join(map(str, program.sources))):
            return (yield from self._run_sweeps(
                program, max_iters, checkpoint_dir, checkpoint_every, resume,
                init_state))

    def _run_sweeps(self, program, max_iters, checkpoint_dir,
                    checkpoint_every, resume, init_state):
        """The body of ``iter_run`` for an already checked ``program``."""
        self._sync_graph_state()
        run_epoch = self._graph_epoch
        shard_epoch_fn = getattr(self.store, "shard_epoch", None)
        epoch_check = None
        if shard_epoch_fn is not None:
            def epoch_check(p, _fn=shard_epoch_fn, _pin=run_epoch):
                got = _fn(p)
                if got > _pin:
                    raise ConcurrentMutationError(
                        f"shard {p} is at epoch {got}, newer than the epoch "
                        f"{_pin} this run pinned at start — the graph was "
                        "mutated mid-run (drain runs before apply(), e.g. "
                        "via GraphService.apply_mutations)")
        if init_state is not None:
            if resume:
                raise ValueError("init_state and resume are mutually "
                                 "exclusive ways to seed a run")
            values, active_mask = init_state
            values = np.asarray(values)
            active_mask = np.asarray(active_mask, dtype=bool)
            if values.shape[0] != self.n or active_mask.shape != values.shape:
                raise ValueError(
                    f"init_state arrays must both be [{self.n}, ...] with "
                    f"matching shapes, got {values.shape} / "
                    f"{active_mask.shape}")
        else:
            values, active_mask = program.init(self.n, self.in_deg,
                                               self.out_deg)
        start_iter = 0
        ck_col_iters = None
        if resume and checkpoint_dir:
            ck = latest_checkpoint(checkpoint_dir)
            if ck is not None:
                if ck[0].shape != values.shape:
                    raise ValueError(
                        f"checkpoint in {checkpoint_dir!r} holds values of "
                        f"shape {ck[0].shape}, but this program expects "
                        f"{values.shape}; it belongs to a different run")
                if ck[4] is not None and ck[4] != self._tag_for(program):
                    # same shapes, different program or landmark/seed set —
                    # continuing would return the OLD frontiers labeled with
                    # the caller's sources
                    raise ValueError(
                        f"checkpoint in {checkpoint_dir!r} was written by "
                        f"{ck[4]!r}, not {self._tag_for(program)!r}; it "
                        f"belongs to a different run")
                values, active_mask, start_iter, ck_col_iters = ck[:4]
        pad = self.n_pad - self.n
        aux_dev = None
        if self.batched:
            vpad = np.pad(values.astype(np.float32), ((0, pad), (0, 0)))
            make_aux = getattr(program, "make_aux", None)
            if make_aux is not None:
                aux_np = np.asarray(make_aux(self.n), dtype=np.float32)
                aux_dev = jnp.asarray(np.pad(aux_np, ((0, pad), (0, 0))))
            else:
                # placeholder keeps the jitted call signature stable; the
                # trace-time has_aux branch never touches it
                aux_dev = jnp.zeros((1, 1), jnp.float32)
            # per-column frontiers: a shard is skipped only when NO column's
            # active set touches it, so schedule over the union of frontiers
            row_active = active_mask.any(axis=1)
            col_live = active_mask.any(axis=0)
            # batched checkpoints always carry per-column counts
            col_iters = (ck_col_iters.astype(np.int64)
                         if ck_col_iters is not None
                         else np.zeros(program.columns, dtype=np.int64))
        else:
            vpad = np.pad(values.astype(np.float32), (0, pad))
            row_active = active_mask
            col_live = col_iters = None
        src = jnp.asarray(vpad)
        active_ids = np.nonzero(row_active)[0]
        active_ratio = active_ids.size / self.n
        history: list[IterationStats] = []
        converged = False

        last_changed = active_mask
        for it in range(start_iter, max_iters):
            with span("graphmp.sweep", sweep=it) as sweep:
                marks = self._io_marks()
                with span("graphmp.schedule", sweep=it):
                    schedule, selective = self._schedule(active_ids,
                                                         active_ratio)
                if not schedule:
                    converged = True
                    break
                if self.batched:
                    # bill this sweep only to columns still holding a frontier
                    col_iters += col_live
                with span("graphmp.gather", sweep=it):
                    x = self._gather_fn(src, self._out_deg_dev)
                # iteration number as a device scalar: same shape/dtype every
                # sweep, so phase-dependent batched posts never retrace
                it_dev = jnp.int32(it) if self.batched else None
                dst, changed = self._sweep(x, src, aux_dev, it_dev, schedule,
                                           epoch_check, it)
                last_changed = changed
                with span("graphmp.schedule", sweep=it):
                    if self.batched:
                        col_live = changed.any(axis=0)
                        row_active = changed.any(axis=1)
                    else:
                        row_active = changed
                    active_ids = np.nonzero(row_active)[0]
                active_ratio = active_ids.size / self.n
                src = dst
            stats = IterationStats(
                iteration=it,
                seconds=sweep.seconds,
                active_ratio=active_ratio,
                shards_processed=len(schedule),
                shards_skipped=self.P - len(schedule),
                selective_enabled=selective,
                edges_processed=sum(self._shard_nnz[p] for p in schedule),
                **self._io_stats(marks),
            )
            history.append(stats)
            for observe in tuple(self.observers):
                try:
                    observe(stats)
                except Exception:
                    pass  # telemetry must never abort a sweep
            if checkpoint_dir and checkpoint_every and (it + 1) % checkpoint_every == 0:
                save_checkpoint(checkpoint_dir, np.asarray(src[: self.n]),
                                changed, it + 1, col_iters=col_iters,
                                tag=self._tag_for(program))
            yield stats
            if active_ids.size == 0:
                converged = True
                break

        final = np.asarray(src[: self.n])
        if checkpoint_dir:
            # persist the true active mask — a resumed run must see exactly
            # the frontier the interrupted run would have used next (for
            # batched runs this is the full per-column [n, K] frontier)
            save_checkpoint(checkpoint_dir, final, last_changed,
                            len(history) + start_iter, col_iters=col_iters,
                            tag=self._tag_for(program))
        if self.batched:
            # global convergence (empty union frontier / empty schedule)
            # implies no column can ever update again
            result: RunResult = BatchRunResult(
                values=final, iterations=len(history), history=history,
                converged=converged, epoch=run_epoch,
                tag=self._tag_for(program), column_iterations=col_iters,
                column_converged=np.asarray(~col_live | converged))
        else:
            result = RunResult(values=final, iterations=len(history),
                               history=history, converged=converged,
                               epoch=run_epoch, tag=self._tag_for(program))
        self.last_result = result
        return result

    def run(
        self,
        max_iters: int = 200,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        program: VertexProgram | None = None,
        init_state: tuple[np.ndarray, np.ndarray] | None = None,
    ) -> RunResult:
        # the lock serializes whole runs, so concurrent callers sharing one
        # engine (GraphService runner threads) see coherent per-iteration
        # disk/stall accounting; iter_run itself stays lock-free because a
        # generator holding a lock across yields could deadlock its consumer
        with self._run_lock:
            gen = self.iter_run(max_iters=max_iters,
                                checkpoint_dir=checkpoint_dir,
                                checkpoint_every=checkpoint_every,
                                resume=resume, program=program,
                                init_state=init_state)
            while True:
                try:
                    next(gen)
                except StopIteration as stop:
                    return stop.value


# ---------------------------------------------------------------------------
def save_checkpoint(ckpt_dir: str, values: np.ndarray, active: np.ndarray,
                    iteration: int, col_iters: np.ndarray | None = None,
                    tag: str | None = None) -> None:
    d = Path(ckpt_dir)
    d.mkdir(parents=True, exist_ok=True)
    tmp = d / f".tmp_ckpt_{iteration:06d}.npz"
    payload = dict(values=values, active=active, iteration=np.int64(iteration))
    if col_iters is not None:
        # batched runs: per-column sweep counts survive the interruption so
        # resumed accounting stays honest
        payload["col_iters"] = np.asarray(col_iters, dtype=np.int64)
    if tag is not None:
        # program identity (name + frontier ids): resume refuses state from
        # a different program or landmark/seed set
        payload["tag"] = np.asarray(tag)
    np.savez(tmp, **payload)
    os.replace(tmp, d / f"ckpt_{iteration:06d}.npz")  # atomic publish
    with open(d / "latest.json.tmp", "w") as f:
        json.dump({"iteration": iteration}, f)
    os.replace(d / "latest.json.tmp", d / "latest.json")
    # keep-N garbage collection
    cks = sorted(d.glob("ckpt_*.npz"))
    for old in cks[:-3]:
        old.unlink()


def latest_checkpoint(ckpt_dir: str):
    d = Path(ckpt_dir)
    meta = d / "latest.json"
    if not meta.exists():
        return None
    with open(meta) as f:
        it = json.load(f)["iteration"]
    p = d / f"ckpt_{it:06d}.npz"
    if not p.exists():
        return None
    with np.load(p) as z:
        col_iters = z["col_iters"] if "col_iters" in z.files else None
        tag = str(z["tag"]) if "tag" in z.files else None
        return z["values"], z["active"], int(z["iteration"]), col_iters, tag
