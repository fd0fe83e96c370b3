"""ShardPipeline mechanics + thread-safety of the cache and byte counters.

The pipeline's deterministic contract: shards are delivered in schedule
order at every depth, a failing fetch surfaces in the consumer, an early
consumer exit reaps the worker, and concurrent ``cache.get`` hammering
leaves every counter exactly right (the satellite regression: stats drifted
when BytesCounter/CacheStats updates raced).
"""
import threading

import numpy as np
import pytest

from repro.core.cache import CompressedShardCache
from repro.core.engine import EngineConfig
from repro.core.pipeline import ShardPipeline
from repro.core.shards import ELLShard
from repro.graph.source import BytesCounter

from _hypo import given, settings, st


def _fake_shard(p: int) -> ELLShard:
    cols = np.full((8, 4), -1, dtype=np.int32)
    return ELLShard(shard_id=p, start_vertex=0, end_vertex=8, nnz=0,
                    cols=cols, vals=np.zeros((8, 4), np.float32),
                    row_map=np.full(4, -1, np.int32),
                    slice_ptr=np.zeros(2, np.int32))


# ---------------------------------------------------------------------------
# ordering + staging
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [0, 1, 2, 4, 16])
def test_stream_preserves_schedule_order(depth):
    schedule = [3, 0, 2, 2, 5, 1]
    fetched = []

    def fetch(p):
        fetched.append(p)
        return _fake_shard(p)

    pipe = ShardPipeline(fetch, depth=depth, stage=lambda s: s.shard_id * 10)
    out = list(pipe.stream(schedule))
    assert [p for p, _, _ in out] == schedule
    assert fetched == schedule  # fetch order == schedule order (determinism)
    assert [staged for _, _, staged in out] == [p * 10 for p in schedule]
    assert pipe.stats.shards == len(schedule)
    assert pipe.stats.fetch_seconds >= 0.0


@pytest.mark.parametrize("depth", [0, 2])
def test_stream_empty_schedule(depth):
    pipe = ShardPipeline(_fake_shard, depth=depth)
    assert list(pipe.stream([])) == []


@given(st.lists(st.integers(0, 9), max_size=30), st.integers(0, 6))
@settings(deadline=None, max_examples=25)
def test_stream_order_property(schedule, depth):
    pipe = ShardPipeline(_fake_shard, depth=depth)
    got = [(p, s.shard_id) for p, s, _ in pipe.stream(schedule)]
    assert got == [(p, p) for p in schedule]


def test_fetch_error_reaches_consumer():
    def fetch(p):
        if p == 2:
            raise OSError("shard 2 unreadable")
        return _fake_shard(p)

    for depth in (0, 1, 3):
        pipe = ShardPipeline(fetch, depth=depth)
        seen = []
        with pytest.raises(OSError, match="shard 2"):
            for p, _, _ in pipe.stream([0, 1, 2, 3]):
                seen.append(p)
        assert seen == [0, 1]  # everything before the failure was delivered


def test_consumer_early_exit_reaps_worker():
    fetched = []

    def fetch(p):
        fetched.append(p)
        return _fake_shard(p)

    pipe = ShardPipeline(fetch, depth=1,
                         nbytes=lambda s: s.decoded_nbytes())
    for p, _, _ in pipe.stream(list(range(100))):
        if p == 3:
            break
    # worker stopped promptly: it ran at most a couple past the break point
    assert len(fetched) <= 8
    assert threading.active_count() < 20  # no leaked prefetch threads
    # abandoned queued shards were de-charged: nothing is in flight anymore
    assert pipe.stats.staged_bytes == 0
    assert pipe.stats.staged_peak_bytes > 0


def test_negative_depth_rejected():
    with pytest.raises(ValueError, match="depth"):
        ShardPipeline(_fake_shard, depth=-1)
    with pytest.raises(ValueError):
        EngineConfig(prefetch_depth=-2)
    with pytest.raises(ValueError):
        EngineConfig(prefetch_depth=True)


def test_prefetch_env_override(monkeypatch):
    monkeypatch.setenv("GRAPHMP_PREFETCH", "3")
    assert EngineConfig.from_env().prefetch_depth == 3
    assert EngineConfig.from_env(prefetch_depth=1).prefetch_depth == 1


# ---------------------------------------------------------------------------
# stall accounting flows into IterationStats
# ---------------------------------------------------------------------------
def test_engine_reports_stall_and_fetch_seconds(graph_store):
    from repro.session import GraphSession
    sess = GraphSession(graph_store, cache_mode=1, prefetch_depth=1)
    res = sess.run("pagerank", max_iters=3)
    for h in res.history:
        assert h.stall_seconds >= 0.0
        assert h.fetch_seconds > 0.0  # fetch+stage always does real work


# ---------------------------------------------------------------------------
# thread-safety regression: 8 threads hammer cache.get
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", [0, 1, 2, "adaptive"])
def test_cache_get_is_thread_safe(graph_store, mode):
    from repro.graph.storage import GraphStore
    store = GraphStore(graph_store.path)  # private io counters
    cache = CompressedShardCache(store, mode=mode, budget_bytes=1 << 28)
    P = store.num_shards
    per_thread = 40
    threads_n = 8
    errors = []

    def hammer(seed):
        rng = np.random.default_rng(seed)
        try:
            for sid in rng.integers(0, P, size=per_thread):
                shard = cache.get(int(sid))
                assert shard.shard_id == int(sid)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(threads_n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    total = threads_n * per_thread
    assert cache.stats.hits + cache.stats.misses == total
    if mode == 0:
        # uncached: every access is a miss charged at canonical nbytes
        assert cache.stats.misses == total
        assert cache.stats.disk_bytes == store.io.read
    else:
        # big budget, no evictions: exactly one miss per distinct shard
        # (adaptive promotions/demotions must not re-read or re-charge)
        assert cache.stats.evictions == 0
        assert cache.stats.misses == P
        assert cache.stats.disk_bytes == sum(
            store.shard_nbytes(p) for p in range(P))
        assert store.io.read == cache.stats.disk_bytes
    assert cache.cached_bytes <= cache.budget


def test_cache_eviction_under_concurrency_keeps_budget(graph_store):
    from repro.graph.storage import GraphStore
    store = GraphStore(graph_store.path)
    budget = max(store.shard_nbytes(0) * 2, 1 << 16)
    cache = CompressedShardCache(store, mode=1, budget_bytes=budget)
    barrier = threading.Barrier(8)

    def hammer(seed):
        rng = np.random.default_rng(seed)
        barrier.wait()
        for sid in rng.integers(0, store.num_shards, size=30):
            cache.get(int(sid))

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cache.cached_bytes <= cache.budget
    assert cache.stats.hits + cache.stats.misses == 8 * 30


def test_bytes_counter_concurrent_adds_are_exact():
    c = BytesCounter()

    def add():
        for _ in range(10_000):
            c.add_read(3)
            c.add_written(2)

    threads = [threading.Thread(target=add) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.read == 8 * 10_000 * 3
    assert c.written == 8 * 10_000 * 2
    c.reset()
    assert (c.read, c.written) == (0, 0)
    # legacy augmented-assignment call sites keep working single-threaded
    c.read += 7
    assert c.read == 7
