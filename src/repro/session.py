"""GraphSession: the unified entry point for graph analytics.

GraphMP's central economics are "preprocess once, serve many applications
from the same shards, with the compressed edge cache absorbing the disk
I/O" (paper §2.2, §2.4.2).  A ``GraphSession`` is the long-lived object
that realises that: it owns the ``GraphStore``, exactly ONE
``CompressedShardCache``, the device-resident padded degree arrays, the
per-shard Bloom filters, and a per-program cache of constructed engines
(so re-running an application reuses its jitted step functions).

    from repro import GraphSession

    with GraphSession(store_path, cache_budget_bytes=1 << 28) as s:
        pr = s.run("pagerank", max_iters=30)
        d  = s.run("sssp", source=0)          # warm cache: ~no disk reads
        cc = s.run("cc")
        print(s.stats.hit_ratio, s.stats.disk_bytes)
        print(s.cache_report())               # tier occupancy, promotions,
        #                                       decode seconds saved, ...

The shared cache is the two-tier adaptive edge cache of core/cache.py
(hot decompressed tier + cold compressed tier under one strict budget —
``cache_budget_bytes`` / env ``GRAPHMP_CACHE_BUDGET``); pass
``cache_mode=0..4`` for the paper's static modes.

Storage is pluggable through the ``ShardSource`` protocol —
``backend="npz" | "packed" | "memory"`` selects the layer (packed = one
mmap'd file, zero-copy shard views), and ``prefetch_depth=N`` (env
``GRAPHMP_PREFETCH``) streams shards through a double-buffered background
pipeline so disk reads, decompression and host->device staging overlap the
SpMV:

    with GraphSession(store_path, backend="packed", prefetch_depth=2) as s:
        pr = s.run("pagerank", max_iters=30)

Multi-device: ``num_devices=N`` (env ``GRAPHMP_DEVICES``) makes every run
drive N local jax devices per edge sweep — the session builds a
``PartitionedShardCache`` (per-device slices of the one budget) and routes
engines to ``repro.core.distributed.ShardedVSWEngine``; results are
bitwise-identical to ``num_devices=1`` and the whole API above is
unchanged (on CPU, set ``XLA_FLAGS=--xla_force_host_platform_device_count``
before jax initializes):

    with GraphSession(store_path, num_devices=8, prefetch_depth=2) as s:
        pr = s.run("pagerank", max_iters=30)   # 8 shards folded per wave

Applications dispatch through the ``@register_app`` registry
(core/apps.py) by name, or a ``VertexProgram`` can be passed directly.
``run_many`` batches several applications; ``iter_run`` yields an
``IterationStats`` per iteration for live monitoring; ``run_batch``
answers K single-source queries (SSSP/BFS landmarks, personalized-PageRank
seeds) through ONE sweep of the edge shards per iteration:

    dists = s.run_batch("sssp", sources=[0, 17, 4095])   # 3 frontiers,
    # ...one [n, 3] value matrix, one pass of disk + decompression

For many concurrent CLIENTS (a query-serving workload rather than one
analyst), ``session.service()`` wraps the session in a thread-safe
``GraphService`` that coalesces independent submissions into those
K-column batches dynamically — see repro/serve/graph_service.py.

Thread-safety: ``run``/``run_batch`` may be called from multiple threads.
The compressed cache takes its own lock, the engine cache is locked here,
engines are shared by ``jit_signature`` (identical compiled steps) with
the concrete program pinned per call, and each engine serializes its runs.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Iterable, Iterator

import jax.numpy as jnp
import numpy as np

from pathlib import Path

from repro.core.apps import (BatchedVertexProgram, DriverProgram,
                             VertexProgram, get_app, is_incremental)
from repro.core.cache import CompressedShardCache, PartitionedShardCache
from repro.core.engine import (BatchRunResult, EngineConfig, IterationStats,
                               RunResult, VSWEngine, _store_epoch)
from repro.core.shards import segment_rows
from repro.graph.source import ShardSource, path_mtime_ns
from repro.graph.storage import GraphStore

BACKENDS = ("npz", "packed", "memory")


def _resolve_source(store, backend: str | None):
    """Turn (path, backend) into a ShardSource; pass storage objects through."""
    from repro.graph.memory import MemoryGraphStore
    from repro.graph.packed import (DEFAULT_PACKED_NAME, PackedGraphStore,
                                    is_packed_file, pack_graph)

    if not isinstance(store, (str, os.PathLike)):
        if backend is not None:
            raise TypeError(
                "backend= only applies when a graph path is given; got a "
                f"storage object ({type(store).__name__}) — pass its path, "
                "or drop backend=")
        return store
    path = Path(store)
    if backend is None:
        backend = "packed" if is_packed_file(path) else "npz"
    if backend == "npz":
        store = GraphStore(path)
        store.properties  # validate up front: clear MissingGraphError, not a
        #                   raw ENOENT from vertex_info.npz deeper in __init__
        return store
    if backend == "packed":
        if path.is_dir():
            # auto-pack (and re-pack after a fresh preprocess): property.json
            # is written last by preprocess_graph, so its mtime dates the store
            packed = path / DEFAULT_PACKED_NAME
            prop = path / "property.json"
            packed_ns = path_mtime_ns(packed)  # -1 when missing
            if packed_ns < 0 or packed_ns <= path_mtime_ns(prop):
                pack_graph(GraphStore(path), packed)
            path = packed
        return PackedGraphStore(path)
    if backend == "memory":
        inner = (PackedGraphStore(path) if is_packed_file(path)
                 else GraphStore(path))
        return MemoryGraphStore.from_source(inner)
    raise ValueError(
        f"unknown backend {backend!r}; expected one of {BACKENDS}")

# run_batch accepts the single-source names and maps them onto the batched
# program factories (which are also directly addressable by name).
_BATCH_ALIASES = {
    "sssp": "sssp_multi",
    "bfs": "bfs_multi",
    "pagerank": "personalized_pagerank",
    "ppr": "personalized_pagerank",
    "lp": "lp_multi",
    "kcore": "kcore_multi",
    "triangle_count": "triangles_multi",
    "random_walk": "random_walks",
}
# factories whose per-column parameter is not called "sources" (PPR seeds,
# k-core thresholds, triangle-count probe vertices); sources= still works
# for all of them and is rewritten onto the factory's own vocabulary
_BATCH_PARAMS = {
    "personalized_pagerank": "seeds",
    "kcore_multi": "ks",
    "triangles_multi": "vertices",
}


class GraphSession:
    """Long-lived analytics session over one preprocessed graph.

    Parameters
    ----------
    store:
        A path to a preprocessed graph (npz directory or packed ``.gmpk``
        file), or any constructed ``ShardSource``.  Passing a constructed
        ``GraphStore`` (the pre-backend ``GraphSession(store=...)`` style)
        still works, but ``backend=`` then does not apply — prefer handing
        the session a path and letting ``backend`` pick the storage layer.
    backend:
        Storage backend for a path: ``"npz"`` (directory of per-shard npz
        files), ``"packed"`` (single mmap'd file with zero-copy shard views;
        a directory path is auto-packed to ``packed.gmpk`` on first use), or
        ``"memory"`` (whole graph RAM-resident — tests/benchmarks).  Default:
        sniffed — ``"packed"`` for a packed file, else ``"npz"``.
    config:
        ``EngineConfig`` shared by every engine the session builds.  When
        omitted it comes from ``EngineConfig.from_env()``; extra keyword
        arguments (``cache_budget_bytes=...``, ``prefetch_depth=...``, ...)
        override single fields.
    max_engines:
        LRU bound on cached engines.  Engines are keyed by (program,
        config) — for ``run_batch`` that includes the sources tuple — so a
        long-lived session answering many distinct landmark sets would
        otherwise retain one jitted engine per set forever.
    mutable:
        Wrap the resolved store in a ``repro.graph.delta.DeltaGraphStore``
        so ``apply_mutations`` can commit edge inserts/deletes/upserts.
        Each commit bumps the graph epoch; the shared cache drops only the
        dirty shards, and ``run_incremental`` can continue a previous
        result instead of rerunning cold.  ``repro.graph.compact.compact``
        folds accumulated deltas back into the base storage.
    """

    def __init__(self, store: ShardSource | str | os.PathLike,
                 config: EngineConfig | None = None, max_engines: int = 16,
                 *, backend: str | None = None, mutable: bool = False,
                 **overrides):
        self._owns_store = isinstance(store, (str, os.PathLike))
        store = _resolve_source(store, backend)
        if mutable:
            from repro.graph.delta import DeltaGraphStore
            if not isinstance(store, DeltaGraphStore):
                store = DeltaGraphStore(store)
        if config is None:
            config = EngineConfig.from_env(**overrides)
        elif overrides:
            config = config.replace(**overrides)
        self.store = store
        self.config = config
        if config.num_devices > 1:
            # multi-device sessions partition the ONE edge cache by shard
            # owner: each device's shards hash into its own
            # CompressedShardCache slice, all under the same global budget
            from repro.core.distributed import assign_shards
            owner, _ = assign_shards(
                np.asarray(store.intervals),
                [int(m.get("nnz", 0)) for m in store.properties["shards"]],
                config.num_devices)
            self.cache = PartitionedShardCache(
                store, owner, config.num_devices, mode=config.cache_mode,
                budget_bytes=config.cache_budget_bytes,
                hot_fraction=config.cache_hot_fraction,
                promote_after=config.cache_promote_after)
        else:
            self.cache = CompressedShardCache(
                store, mode=config.cache_mode,
                budget_bytes=config.cache_budget_bytes,
                hot_fraction=config.cache_hot_fraction,
                promote_after=config.cache_promote_after)
        # graph epoch the shared arrays below were read at; engines inherit
        # it and re-sync per run when a mutable store moves past it
        self._graph_epoch = _store_epoch(store)
        # shared vertex metadata: read from disk exactly once per session
        self.in_deg, self.out_deg = store.read_vertex_info()
        self.blooms = store.read_all_blooms()
        self.n = store.num_vertices
        # room for the last interval's shard step (engine.segments)
        self.n_pad = self.n + segment_rows(store.intervals)
        # device-resident padded out-degrees, shared by every engine
        self.out_deg_dev = jnp.asarray(
            np.pad(self.out_deg, (0, self.n_pad - self.n)).astype(np.float32))
        if max_engines < 1:
            raise ValueError(f"max_engines must be >= 1, got {max_engines}")
        self.max_engines = max_engines
        self._engines: "OrderedDict" = OrderedDict()
        # engine-cache lock: GraphService runner threads resolve engines
        # concurrently; the cache itself (CompressedShardCache) has its own
        # lock, and each engine serializes its runs — together these make
        # run()/run_batch() safe to call from many threads
        self._engines_lock = threading.RLock()
        # combined [n, K] result of the most recent run_batch (survives
        # engine-cache eviction, unlike engine(...).last_result)
        self.last_batch_result: BatchRunResult | None = None
        # telemetry taps shared (by reference) with every engine this
        # session builds: each entry is called with every IterationStats as
        # sweeps produce them.  Appending here — e.g. via attach_hub — is
        # seen by engines built BEFORE the append too (same list object).
        self.iteration_observers: list = []

    # -- engine construction / reuse ------------------------------------
    def _resolve(self, app, app_kwargs) -> tuple[VertexProgram, object]:
        if isinstance(app, (VertexProgram, BatchedVertexProgram,
                            DriverProgram)):
            if app_kwargs:
                raise TypeError(
                    "application kwargs only apply when dispatching by name; "
                    f"got a VertexProgram plus {sorted(app_kwargs)}")
            program = app
        else:
            program = get_app(app, **app_kwargs)
        if isinstance(program, DriverProgram):
            # host-driven: no engine, no jit cache — the key is unused
            return program, ("driver", program.name)
        # programs declaring a jit_signature share engines across every
        # parameterization with identical device callables (e.g. ALL sssp
        # sources, ALL K-landmark sets of the same K): the signature is the
        # cache key and the concrete program is handed to run() per call,
        # so a serving workload never recompiles per source set
        sig = getattr(program, "jit_signature", None)
        if sig is not None:
            return program, ("sig", sig)
        if isinstance(app, str):
            return program, ("name", app, tuple(sorted(app_kwargs.items())))
        return program, ("prog", id(program))

    def engine(self, app: str | VertexProgram, config: EngineConfig | None = None,
               **app_kwargs) -> VSWEngine:
        """The session-shared engine for an application (built once per
        (jit_signature or program, config); reuse keeps the jitted step
        caches warm).  The returned engine's default program is rebound to
        the one just requested, so single-threaded ``engine(...).run()``
        works; concurrent callers should go through ``session.run`` /
        ``run_batch`` (which pin the program per call) instead."""
        program, prog_key = self._resolve(app, app_kwargs)
        if isinstance(program, DriverProgram):
            raise TypeError(
                f"{program.name!r} is a host-driven application and has no "
                "engine; dispatch it through session.run / run_batch")
        return self._engine_for(program, prog_key, config)

    def _run_target(self, app, app_kwargs, config):
        """(engine, program-to-pin) for one run.

        Signature-keyed engines get the resolved program pinned per call
        (thread-safe sharing across parameterizations).  Name-keyed engines
        (no jit_signature) run their OWN program: the cache key already
        proves name+kwargs equality, and a fresh factory instance would
        fail _check_program's identity test.  Host-driven programs have no
        engine at all — (None, driver)."""
        program, prog_key = self._resolve(app, app_kwargs)
        if isinstance(program, DriverProgram):
            return None, program
        eng = self._engine_for(program, prog_key, config)
        return eng, (program if prog_key[0] == "sig" else None)

    def _engine_for(self, program, prog_key, config) -> VSWEngine:
        key = (prog_key, config or self.config)
        with self._engines_lock:
            eng = self._engines.get(key)
            if eng is None:
                cls = VSWEngine
                if (config or self.config).num_devices > 1:
                    # transparent multi-device routing: same run/run_batch/
                    # iter_run surface, N devices per edge sweep
                    from repro.core.distributed import ShardedVSWEngine
                    cls = ShardedVSWEngine
                eng = cls.from_session(self, program, config)
                if prog_key[0] == "prog":
                    # a raw-id key must keep the program alive to stay unique
                    eng._keyed_program = program
                self._engines[key] = eng
                while len(self._engines) > self.max_engines:
                    self._engines.popitem(last=False)  # drop the LRU engine
            else:
                self._engines.move_to_end(key)
                if eng.program is not program and prog_key[0] == "sig":
                    # same compiled steps, new default host-side identity;
                    # _check_program trips on a false jit_signature claim
                    # (device callables differing from the compiled ones)
                    eng._check_program(program)
                    eng.program = program
            return eng

    # -- running --------------------------------------------------------
    def run(self, app: str | VertexProgram, *, max_iters: int = 200,
            checkpoint_dir: str | None = None, checkpoint_every: int = 0,
            resume: bool = False, config: EngineConfig | None = None,
            **app_kwargs) -> RunResult:
        """Run one application to ``max_iters`` or convergence.

        Parameters
        ----------
        app:
            A registered application name (see
            ``repro.core.apps.available_apps()``; extra keyword arguments go
            to its factory, e.g. ``run("sssp", source=3)`` or
            ``run("pagerank", damping=0.9)``) or a constructed
            ``VertexProgram``.
        max_iters:
            Iteration cap; the run also stops early when no vertex value
            changes (``RunResult.converged``).
        checkpoint_dir / checkpoint_every / resume:
            Fault tolerance: snapshot (values, frontier, iteration) into
            ``checkpoint_dir`` every ``checkpoint_every`` iterations;
            ``resume=True`` restarts from the latest snapshot (and refuses a
            checkpoint written by a different program or source set).
        config:
            ``EngineConfig`` overriding the session config for this
            application's engine (the compressed edge cache stays shared
            either way).

        Returns
        -------
        RunResult with ``values`` (one float per vertex), ``iterations``,
        ``converged``, and ``history`` (one ``IterationStats`` per
        iteration — disk bytes, cache hit ratio, stall/fetch seconds).
        """
        # the program rides along explicitly: engines shared by jit_signature
        # stay stateless across concurrent runs (thread-safety contract)
        eng, run_program = self._run_target(app, app_kwargs, config)
        if eng is None:  # host-driven application
            return self._run_driver(
                run_program, max_iters=max_iters,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume=resume,
                config=config)
        return eng.run(max_iters=max_iters, checkpoint_dir=checkpoint_dir,
                       checkpoint_every=checkpoint_every, resume=resume,
                       program=run_program)

    def _run_driver(self, program: DriverProgram, *, max_iters,
                    checkpoint_dir, checkpoint_every, resume, config):
        if checkpoint_dir or checkpoint_every or resume:
            raise TypeError(
                f"{program.name!r} is a host-driven application; engine "
                "checkpoint/resume do not apply to it")
        result = program.run(self, max_iters=max_iters, config=config)
        if isinstance(result, BatchRunResult):
            self.last_batch_result = result
        return result

    def iter_run(self, app: str | VertexProgram, *, max_iters: int = 200,
                 checkpoint_dir: str | None = None, checkpoint_every: int = 0,
                 resume: bool = False, config: EngineConfig | None = None,
                 **app_kwargs) -> Iterator[IterationStats]:
        """Streaming form of ``run``: yields an ``IterationStats`` after
        every iteration, for live monitoring of long runs.

        Takes exactly the arguments of ``run``.  The finished ``RunResult``
        is the generator's return value (``StopIteration.value``) and is
        also available afterwards as ``session.engine(app, ...).last_result``:

            gen = session.iter_run("pagerank", max_iters=100)
            while True:
                try:
                    print(next(gen).active_ratio)
                except StopIteration as stop:
                    result = stop.value
                    break
        """
        eng, run_program = self._run_target(app, app_kwargs, config)
        if eng is None:
            raise TypeError(
                f"{run_program.name!r} is a host-driven application; "
                "iter_run streams engine iterations — use run() for it")
        return eng.iter_run(max_iters=max_iters, checkpoint_dir=checkpoint_dir,
                            checkpoint_every=checkpoint_every, resume=resume,
                            program=run_program)

    def run_batch(self, app: str | BatchedVertexProgram = "sssp", *,
                  sources: Iterable[int] | None = None, max_iters: int = 200,
                  checkpoint_dir: str | None = None, checkpoint_every: int = 0,
                  resume: bool = False, config: EngineConfig | None = None,
                  **app_kwargs) -> list[RunResult]:
        """K single-source queries through ONE sweep of the edge shards.

        Each iteration pays disk + decompression for a shard once and
        advances every column against it, so K landmark queries cost close
        to one query's I/O instead of K (paper §2.2's amortization, applied
        across *queries*).

        Parameters
        ----------
        app:
            A single-source name (``"sssp"``/``"bfs"``/``"pagerank"`` — the
            latter becomes personalized PageRank over the given seeds), a
            batched factory name (``"sssp_multi"``/``"bfs_multi"``/
            ``"personalized_pagerank"``), or a ``BatchedVertexProgram``.
        sources:
            One frontier vertex per column (for PPR these are the ``seeds``;
            either spelling works).  Required when dispatching by name.
        max_iters / checkpoint_dir / checkpoint_every / resume / config:
            As in ``run``; checkpoints hold the full [n, K] state, so a
            resumed batch continues every column.

        Returns
        -------
        One ``RunResult`` per source, in order, with honest per-column
        iteration counts (a column is only billed for sweeps it entered
        with a live frontier).  The combined ``BatchRunResult`` ([n, K]
        values, shared history) stays available as
        ``session.last_batch_result`` until the next ``run_batch`` call.
        """
        if isinstance(app, (BatchedVertexProgram, DriverProgram)):
            if sources is not None:
                raise TypeError(
                    "sources= only applies when dispatching by name; the "
                    "BatchedVertexProgram already fixes its frontiers")
            # forward app_kwargs so misuse raises like run() does
            program, prog_key = self._resolve(app, app_kwargs)
        else:
            name = _BATCH_ALIASES.get(app, app)
            param = _BATCH_PARAMS.get(name, "sources")
            if sources is not None:
                if param in app_kwargs:
                    raise TypeError(
                        f"pass sources= or {param}=, not both")
                app_kwargs[param] = tuple(int(s) for s in sources)
            elif param in app_kwargs:
                # the factory's own vocabulary (e.g. seeds= for PPR) works too
                app_kwargs[param] = tuple(int(s) for s in app_kwargs[param])
            else:
                raise TypeError("run_batch needs sources=[...] when "
                                "dispatching by name")
            # signature-keyed dispatch so repeat calls reuse the engine (and
            # its jitted [n, K] shard steps) — across DIFFERENT landmark
            # sets of the same K, not just repeats of one set
            try:
                program, prog_key = self._resolve(name, app_kwargs)
            except TypeError as exc:
                if f"unexpected keyword argument {param!r}" in str(exc):
                    # the factory has no frontier parameter at all
                    raise TypeError(
                        f"{name!r} is not a batched application") from None
                raise  # genuine bad kwarg — keep the factory's own message
        if isinstance(program, DriverProgram):
            if not program.batched:
                raise TypeError(f"{app!r} is not a batched application")
            result = self._run_driver(
                program, max_iters=max_iters, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume=resume,
                config=config)
            assert isinstance(result, BatchRunResult)
            return result.columns()
        if not isinstance(program, BatchedVertexProgram):
            raise TypeError(f"{app!r} is not a batched application")
        eng = self._engine_for(program, prog_key, config)
        result = eng.run(max_iters=max_iters, checkpoint_dir=checkpoint_dir,
                         checkpoint_every=checkpoint_every, resume=resume,
                         program=program if prog_key[0] == "sig" else None)
        assert isinstance(result, BatchRunResult)
        self.last_batch_result = result
        return result.columns()

    def run_many(self, apps: Iterable, **run_kwargs) -> list[RunResult]:
        """Run several applications back-to-back over the shared cache.

        Each item is a registered name, a ``(name, factory_kwargs)`` pair,
        or a ``VertexProgram``; ``run_kwargs`` (``max_iters=...``) apply to
        every run.  Returns results in input order.
        """
        results = []
        for item in apps:
            if isinstance(item, tuple):
                name, kw = item
                results.append(self.run(name, **run_kwargs, **kw))
            else:
                results.append(self.run(item, **run_kwargs))
        return results

    # -- mutation / incremental recompute -------------------------------
    def apply_mutations(self, inserts=None, deletes=None,
                        updates=None) -> int:
        """Commit one batch of edge edits to a ``mutable=True`` session.

        ``inserts``/``updates`` (synonyms — both upsert) take ``(src, dst)``
        or ``(src, dst, weight)`` arrays or triple iterables; ``deletes``
        takes ``(src, dst)`` pairs.  Returns the new graph epoch.  The
        session's shared degree arrays and Bloom filters are refreshed for
        exactly the shards that changed; the shared cache drops stale
        entries lazily on next access.  Runs already in flight pinned the
        previous epoch and will raise ``ConcurrentMutationError`` rather
        than mix epochs — drain them first (``GraphService.apply_mutations``
        does this for serving workloads).
        """
        apply = getattr(self.store, "apply", None)
        if apply is None:
            raise TypeError(
                "this session's store is frozen; open it with "
                "GraphSession(path, mutable=True) (or wrap the store in a "
                "DeltaGraphStore) before applying edge mutations")
        epoch = apply(inserts=inserts, deletes=deletes, updates=updates)
        self._refresh_graph_state()
        return epoch

    def _refresh_graph_state(self) -> None:
        """Re-read graph-derived session state after the store's epoch moved.

        Mirrors ``VSWEngine._sync_graph_state`` for the session-owned shared
        arrays, so engines built *after* a mutation start consistent.  The
        blooms list is shared by reference with every live engine — updating
        entries in place keeps them all coherent.
        """
        prev = self._graph_epoch
        cur = _store_epoch(self.store)
        if cur == prev:
            return
        self.in_deg, self.out_deg = self.store.read_vertex_info()
        self.out_deg_dev = jnp.asarray(
            np.pad(self.out_deg, (0, self.n_pad - self.n)).astype(np.float32))
        shard_epoch = getattr(self.store, "shard_epoch", None)
        for p in range(self.store.num_shards):
            if shard_epoch is None or shard_epoch(p) > prev:
                self.blooms[p] = self.store.read_bloom(p)
        self._graph_epoch = cur

    def run_incremental(self, app: str | VertexProgram, *,
                        prev: RunResult, max_iters: int = 200,
                        config: EngineConfig | None = None,
                        **app_kwargs) -> RunResult:
        """Continue a previous run's fixpoint across graph mutations.

        ``prev`` must be the ``RunResult`` of the same application and
        source over this session's store.  When every commit since
        ``prev.epoch`` was *monotone* (insert-only / weight-non-increasing)
        and the application is registered ``incremental=True`` (SSSP, BFS,
        CC — min-propagations whose old fixpoint stays a valid upper
        bound), the run seeds its values from ``prev`` and its frontier
        from just the source vertices the deltas touched: convergence takes
        the few iterations the change actually propagates, and selective
        scheduling reads only the shards those frontiers reach.

        Falls back to a cold full run whenever the shortcut would be
        unsound: a non-incremental app, a delete or weight increase since
        ``prev.epoch``, an unconverged ``prev``, or an epoch log truncated
        past it.  If the store has not moved since ``prev``, returns the
        previous values directly (0 iterations).
        """
        program, prog_key = self._resolve(app, app_kwargs)
        if isinstance(program, (BatchedVertexProgram, DriverProgram)):
            raise TypeError(
                "run_incremental takes single-frontier applications; "
                "run_batch results cannot seed it")
        tag = VSWEngine._tag_for(program)
        if prev.tag is not None and prev.tag != tag:
            raise ValueError(
                f"prev result was produced by {prev.tag!r}, not {tag!r}; "
                "incremental recompute must continue the same program and "
                "source")
        cur = _store_epoch(self.store)
        if cur == prev.epoch and prev.converged:
            # nothing changed since prev: its fixpoint is still the answer
            return RunResult(values=np.array(prev.values), iterations=0,
                             history=[], converged=True, epoch=cur, tag=tag)
        name = app if isinstance(app, str) else program.name
        monotone_since = getattr(self.store, "monotone_since", None)
        seeds = None
        if (prev.converged and is_incremental(name)
                and monotone_since is not None
                and monotone_since(prev.epoch)):
            # None when the epoch log no longer reaches back to prev.epoch
            seeds = self.store.affected_sources_since(prev.epoch)
        eng = self._engine_for(program, prog_key, config)
        run_program = program if prog_key[0] == "sig" else None
        if seeds is None:
            return eng.run(max_iters=max_iters, program=run_program)
        values = np.array(prev.values)
        active = np.zeros(self.n, dtype=bool)
        active[seeds] = True
        return eng.run(max_iters=max_iters, program=run_program,
                       init_state=(values, active))

    def service(self, config=None, **overrides):
        """A concurrent query service over this session.

        Returns a started ``repro.serve.GraphService`` wrapping this
        session: many client threads ``submit()`` single queries, the
        service coalesces compatible ones into K-column micro-batches served
        by ``run_batch`` through the shared compressed cache, and each
        caller gets its own future/``RunResult``.  ``config`` is a
        ``repro.serve.ServiceConfig``; keyword overrides
        (``max_batch=...``, ``max_wait_ms=...``) adjust single fields::

            with GraphSession(path) as s, s.service(max_batch=16) as svc:
                fut = svc.submit("sssp", source=42)
                print(fut.result().values[:10])

        The session must outlive the service (close the service first —
        the ``with`` form above nests them correctly).
        """
        from repro.serve.graph_service import GraphService
        return GraphService(self, config, **overrides)

    # -- observability / lifecycle --------------------------------------
    @property
    def stats(self):
        """Shared CompressedShardCache stats (hits, disk_bytes, ...)."""
        return self.cache.stats

    def cache_report(self) -> dict:
        """Snapshot of the shared edge cache: policy ("adaptive"/"static"),
        mode, budget, per-tier occupancy (``hot_bytes``/``hot_shards``,
        ``cold_bytes``/``cold_shards``), hit/miss/promotion/demotion/eviction
        counters, ``decode_seconds_saved`` (decompression cost hot-tier hits
        skipped) and the achieved compression ratio.  All values are
        self-consistent (taken under the cache lock)."""
        return self.cache.report()

    def attach_hub(self, hub, prefix: str = "session"):
        """Wire this session's telemetry into a ``repro.obs.MetricsHub``:

        * ``{prefix}.cache.*`` — a poller over ``cache_report()`` (numeric
          leaves flattened into gauges at each hub sample: tier occupancy,
          hit/miss/eviction counters, achieved compression ratio; the
          partitioned cache's per-partition sub-reports flatten too);
        * ``{prefix}.engine.*`` — an ``iteration_observers`` tap converting
          every ``IterationStats`` into counters (iterations,
          disk_bytes, edges_processed, stall/fetch/stage/decode-saved
          seconds, h2d_bytes staged to the device,
          per-device ``engine.devN.*`` splits for sharded runs), gauges
          (last active_ratio / cache_hit_ratio), and an
          ``{prefix}.engine.iteration_s`` histogram of sweep durations.

        Engines already built share the observer list by reference, so
        attaching mid-flight captures every subsequent iteration.  Returns
        ``hub`` for chaining.
        """
        hub.register_poller(f"{prefix}.cache", self.cache_report)
        iter_hist = hub.histogram(f"{prefix}.engine.iteration_s")
        eng = f"{prefix}.engine"

        def observe(stats) -> None:
            hub.counter(f"{eng}.iterations").inc()
            hub.counter(f"{eng}.disk_bytes").inc(stats.disk_bytes)
            hub.counter(f"{eng}.edges_processed").inc(stats.edges_processed)
            hub.counter(f"{eng}.shards_processed").inc(stats.shards_processed)
            hub.counter(f"{eng}.shards_skipped").inc(stats.shards_skipped)
            hub.counter(f"{eng}.stall_seconds").inc(stats.stall_seconds)
            hub.counter(f"{eng}.fetch_seconds").inc(stats.fetch_seconds)
            hub.counter(f"{eng}.stage_seconds").inc(stats.stage_seconds)
            hub.counter(f"{eng}.h2d_bytes").inc(stats.h2d_bytes)
            hub.counter(f"{eng}.decode_seconds_saved").inc(
                stats.decode_seconds_saved)
            hub.gauge(f"{eng}.active_ratio").set(stats.active_ratio)
            hub.gauge(f"{eng}.cache_hit_ratio").set(stats.cache_hit_ratio)
            iter_hist.observe(stats.seconds)
            for d, (db, ds, df) in enumerate(zip(stats.device_disk_bytes,
                                                 stats.device_stall_seconds,
                                                 stats.device_fetch_seconds)):
                hub.counter(f"{eng}.dev{d}.disk_bytes").inc(db)
                hub.counter(f"{eng}.dev{d}.stall_seconds").inc(ds)
                hub.counter(f"{eng}.dev{d}.fetch_seconds").inc(df)

        self.iteration_observers.append(observe)
        return hub

    def warm(self) -> int:
        """Pull every shard through the cache once (prefetch); returns the
        bytes now resident."""
        for p in range(self.store.num_shards):
            self.cache.get(p)
        return self.cache.cached_bytes

    def close(self) -> None:
        """Drop engine and cache references (jit caches, cached blobs)."""
        self._engines.clear()
        self.cache.clear()
        if self._owns_store:
            try:
                getattr(self.store, "close", lambda: None)()
            except BufferError:
                # jax aliases mmap'd shard segments zero-copy on CPU and
                # releases them asynchronously; the mapping closes when the
                # last consumer drops its buffer
                pass

    def __enter__(self) -> "GraphSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:
        return (f"GraphSession({str(self.store.path)!r}, |V|={self.n}, "
                f"|E|={self.store.num_edges}, shards={self.store.num_shards}, "
                f"cache_mode={self.cache.mode})")
