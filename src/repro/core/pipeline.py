"""ShardPipeline: overlap shard fetch/decompress/staging with compute.

GraphMP's thesis is hiding disk behind compute (paper §2.3; NXgraph and
GraphH stream shards the same way).  The engine used to fetch every shard
synchronously inside the iteration loop, serializing disk reads, npz
parsing, cache decompression, and host->device staging with the Pallas
SpMV.  The pipeline moves all of that onto ONE background thread feeding a
bounded queue:

    worker:  fetch(p) -> stage(shard) -> queue.put        (depth items ahead)
    main  :  queue.get -> SpMV on the previous result

``prefetch_depth`` is the queue bound — 1 is classic double buffering, 0 is
the old synchronous path (same code path, no thread).  A SINGLE worker
fetching in schedule order is deliberate: cache accesses happen in exactly
the order the synchronous path would issue them, so hit/miss/eviction
sequences — and therefore the Table-3 disk-byte accounting — are bit-for-bit
identical at every depth.  (Multi-device engines keep that property per
device: ``ShardedVSWEngine`` runs one pipeline instance — a prefetch LANE —
per device over that device's slice of the schedule, each feeding its own
cache partition, with per-lane ``stats`` summing to the engine aggregates.)

``stats`` separates the two sides of the overlap: ``stall_seconds`` is time
the consumer spent blocked waiting on the queue (what prefetch is supposed
to drive to zero) and ``fetch_seconds`` is background time spent producing
shards (what it hides), of which ``stage_seconds`` went to staging.  Each
is the duration of a span (``repro.core.spans``): ``graphmp.wait`` on the
consumer, ``graphmp.fetch`` and ``graphmp.stage`` on the worker, carrying
the ``sweep`` and ``shard`` they served.  ``h2d_bytes`` counts the bytes
staging sent to the device, ``ell_slots`` the ELL slots of the shards it
staged and ``ell_arcs`` the edges those slots hold.

Memory interplay with the two-tier cache (core/cache.py): the worker's
``fetch`` is ``cache.get``, which may promote/demote/evict — every such
transition and its byte accounting happens inside the cache's lock, so
staging never races a promotion and the cache budget holds at every depth.
The pipeline itself holds up to ``depth`` staged shards in flight on top of
the cache; that host memory is charged to ``stats.staged_bytes`` (current)
and ``stats.staged_peak_bytes`` (high-water), bounded by
``depth × max shard bytes``.  It is deliberately NOT charged against the
cache budget: doing so would make eviction sequences — and therefore the
Table-3 disk-byte accounting — depend on prefetch depth, breaking the
bit-for-bit invariance contract above.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable, Iterator, Sequence

from repro.core.shards import ELLShard
from repro.core.spans import Counters, span

_DONE = object()


@dataclasses.dataclass
class PipelineStats(Counters):
    """Producer/consumer accounting.

    ``shards``/``stall_seconds``/``fetch_seconds``/``stage_seconds``/
    ``h2d_bytes`` are lifetime accumulators; ``staged_bytes`` is the host
    bytes of shards currently staged but not yet consumed (bounded by
    depth × max shard bytes) and ``staged_peak_bytes`` its lifetime
    high-water mark.
    """

    shards: int = 0           # shards delivered to the consumer
    stall_seconds: float = 0.0  # consumer time blocked on the queue
    fetch_seconds: float = 0.0  # producer time fetching + staging
    stage_seconds: float = 0.0  # producer time staging (within fetch)
    h2d_bytes: int = 0          # bytes staging sent to the device
    ell_slots: int = 0          # ELL slots of the staged shards
    ell_arcs: int = 0           # edges those slots hold
    staged_bytes: int = 0       # staged-but-unconsumed host bytes (in flight)
    staged_peak_bytes: int = 0  # lifetime high-water mark of staged_bytes


@dataclasses.dataclass
class _Failure:
    exc: BaseException


class ShardPipeline:
    """Streams ``(shard_id, shard, staged)`` for a schedule, ``depth`` ahead.

    ``fetch``: shard_id -> ELLShard (typically ``cache.get``; must be safe to
    call from one background thread — the CompressedShardCache does every
    tier transition, including promotions, under its own lock).
    ``stage``: optional ELLShard -> anything; runs on the worker too, so
    host->device transfers land off the critical path.  With ``depth == 0``
    both run inline on the consumer thread (the synchronous path).
    ``nbytes``: optional ELLShard -> int used to charge staged-but-unconsumed
    shards to ``stats.staged_bytes`` (the pipeline's own memory footprint on
    top of the cache budget).
    ``h2d``: optional staged -> int, the bytes ``stage`` sent to the device,
    added to ``stats.h2d_bytes``.
    """

    def __init__(self, fetch: Callable[[int], ELLShard], depth: int = 0,
                 stage: Callable[[ELLShard], Any] | None = None,
                 nbytes: Callable[[ELLShard], int] | None = None,
                 h2d: Callable[[Any], int] | None = None):
        if depth < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {depth}")
        self.fetch = fetch
        self.stage = stage
        self.nbytes = nbytes
        self.h2d = h2d
        self.depth = int(depth)
        self.stats = PipelineStats()

    def _charge(self, n: int) -> None:
        st = self.stats
        with st.lock:  # producer + consumer both charge
            st.staged_bytes += n
            st.staged_peak_bytes = max(st.staged_peak_bytes, st.staged_bytes)

    def _produce(self, p: int, check: Callable[[int], None] | None,
                 sweep: int) -> tuple[int, ELLShard, Any, int]:
        with span("graphmp.fetch", self.stats, "fetch_seconds", sweep=sweep,
                  shard=p):
            if check is not None:
                check(p)  # epoch pin: refuse to stage a shard from a newer epoch
            shard = self.fetch(p)
            staged = None
            if self.stage is not None:
                with span("graphmp.stage", self.stats, "stage_seconds",
                          shard=p):
                    staged = self.stage(shard)
                if self.h2d is not None:
                    self.stats.bump(h2d_bytes=self.h2d(staged))
                self.stats.bump(ell_slots=int(shard.cols.size),
                                ell_arcs=int(shard.nnz))
            held = self.nbytes(shard) if self.nbytes is not None else 0
            self._charge(held)
        return p, shard, staged, held

    def stream(self, schedule: Sequence[int],
               check: Callable[[int], None] | None = None, sweep: int = 0,
               ) -> Iterator[tuple[int, ELLShard, Any]]:
        """Yield every shard of ``schedule`` in order, prefetching ahead.

        ``check`` (optional) runs on the producer immediately before each
        fetch; the engine passes its epoch-pin assertion so a mid-run graph
        mutation raises ``ConcurrentMutationError`` instead of silently
        staging a shard from a newer epoch into an older run.  ``sweep``
        names the iteration the stream serves in the spans it writes.
        """
        # a single-shard schedule has nothing to overlap with — skip the
        # worker thread (same order, same accounting, no spawn cost)
        if self.depth == 0 or len(schedule) < 2:
            for p in schedule:
                # synchronous path: the consumer IS stalled for the whole fetch
                with span("graphmp.wait", self.stats, "stall_seconds",
                          sweep=sweep, shard=p):
                    pid, shard, staged, held = self._produce(p, check, sweep)
                self.stats.shards += 1
                self._charge(-held)  # delivered: no longer in flight
                yield pid, shard, staged
            return

        q: queue.Queue = queue.Queue(maxsize=self.depth)
        cancel = threading.Event()

        def worker() -> None:
            try:
                for p in schedule:
                    if cancel.is_set():
                        return
                    q.put(self._produce(p, check, sweep))
                q.put(_DONE)
            except BaseException as exc:  # noqa: BLE001 — forwarded, re-raised
                q.put(_Failure(exc))

        t = threading.Thread(target=worker, name="shard-prefetch", daemon=True)
        t.start()
        try:
            for p in schedule:
                with span("graphmp.wait", self.stats, "stall_seconds",
                          sweep=sweep, shard=p):
                    item = q.get()
                if isinstance(item, _Failure):
                    raise item.exc
                pid, shard, staged, held = item
                self.stats.shards += 1
                self._charge(-held)  # delivered: no longer in flight
                yield pid, shard, staged
            # the worker's end marker (or its failure) follows the last shard
            with span("graphmp.wait", self.stats, "stall_seconds",
                      sweep=sweep):
                item = q.get()
            if isinstance(item, _Failure):
                raise item.exc
        finally:
            cancel.set()
            # unblock a worker parked on q.put, then reap it; de-charge
            # drained items so staged_bytes never counts abandoned shards
            while t.is_alive():
                try:
                    item = q.get_nowait()
                    if isinstance(item, tuple):
                        self._charge(-item[3])
                except queue.Empty:
                    pass
                t.join(timeout=0.05)
            # the worker may have completed one last q.put between the drain
            # and its cancel check — sweep whatever is still queued
            while True:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
                if isinstance(item, tuple):
                    self._charge(-item[3])
