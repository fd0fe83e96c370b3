"""Two-tier adaptive edge cache: placement, budget edge cases, churn.

The contract under test (core/cache.py):
  * one strict byte budget over BOTH tiers, never exceeded — not after any
    single get, not under 8 threads of promotion/demotion churn;
  * hot tier is earned by reuse (frequency), and the eviction path cascades
    hot→cold→out;
  * degenerate budgets still make progress: smaller than the largest shard,
    and budget=0 degrades to mode 0 (no application cache);
  * every placement decision is a deterministic function of the get
    sequence, so results stay bitwise identical to the static cache
    (cross-backend/depth property in tests/test_backends.py).
"""
import threading

import numpy as np
import pytest

from repro.core.cache import CompressedShardCache
from repro.core.engine import EngineConfig
from repro.session import GraphSession


@pytest.fixture(scope="module")
def tier_store(tmp_path_factory, small_graph):
    """A store with enough shards for eviction/promotion churn to happen."""
    from repro.graph.preprocess import preprocess_graph
    from repro.graph.storage import write_edge_list
    src, dst, n = small_graph
    base = tmp_path_factory.mktemp("tier_graph")
    write_edge_list(base / "el", [(src, dst)])
    return preprocess_graph(str(base / "el"), str(base / "store"),
                            threshold_edge_num=256, ell_max_width=64)


def _raw_nbytes(cache, store):
    return [cache._entry_nbytes(store.read_shard(p))
            for p in range(store.num_shards)]


# ---------------------------------------------------------------------------
# placement lifecycle: miss -> cold -> (frequency) -> hot
# ---------------------------------------------------------------------------
def test_promotion_lifecycle_and_decode_seconds_saved(tier_store):
    cache = CompressedShardCache(tier_store, mode="adaptive",
                                 budget_bytes=1 << 28)
    assert cache.adaptive and cache.mode >= 2  # admission default: compressed
    cache.get(0)                               # miss: admitted cold
    assert cache.shard_tier(0) == "cold"
    cache.get(0)                               # cold hit: 2nd touch promotes
    assert cache.shard_tier(0) == "hot"
    saved0 = cache.stats.decode_seconds_saved
    cache.get(0)                               # hot hit: zero decode
    assert (cache.stats.misses, cache.stats.cold_hits,
            cache.stats.hot_hits, cache.stats.promotions) == (1, 1, 1, 1)
    assert cache.stats.decode_seconds_saved > saved0
    assert cache.stats.hits == 2
    cache.audit()


def test_rarely_touched_shards_stay_cold(tier_store):
    cache = CompressedShardCache(tier_store, mode="adaptive",
                                 budget_bytes=1 << 28)
    for _ in range(4):
        cache.get(0)            # hub shard: touched every iteration
    cache.get(1)                # rarely-scheduled shard: one touch
    assert cache.shard_tier(0) == "hot"
    assert cache.shard_tier(1) == "cold"
    rep = cache.report()
    assert rep["hot_shards"] == 1 and rep["cold_shards"] == 1
    assert rep["measured_ratio"] > 1.0  # the cold blob really is compressed


def test_demotion_cascade_hot_to_cold_and_no_equal_heat_churn(tier_store):
    """A hotter shard displaces the hot LRU (which is demoted, compressed,
    back to cold) — but EQUAL heat must not displace (no promote/demote
    ping-pong between uniformly-swept shards)."""
    raw = _raw_nbytes(
        CompressedShardCache(tier_store, mode=1, budget_bytes=1), tier_store)
    # hot_fraction=0.5 -> the hot tier fits ONE of shards {0, 1}, not both
    budget = 2 * max(raw[0], raw[1])
    cache = CompressedShardCache(tier_store, mode="adaptive",
                                 budget_bytes=budget, hot_fraction=0.5)
    cache.get(0)
    cache.get(0)            # freq 2: promoted, hot tier now full
    assert cache.shard_tier(0) == "hot"
    cache.get(1)
    cache.get(1)            # freq 2 == freq of hot LRU: stays cold (no churn)
    assert cache.shard_tier(1) == "cold"
    assert cache.stats.demotions == 0
    cache.get(1)            # freq 3 > 2: displaces shard 0
    assert cache.shard_tier(1) == "hot"
    assert cache.shard_tier(0) in ("cold", "out")  # demoted (may then evict)
    assert cache.stats.demotions == 1
    assert cache.stats.promotions == 2
    cache.audit()


# ---------------------------------------------------------------------------
# budget edge cases
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["adaptive", 1, 2])
def test_budget_smaller_than_any_shard_still_makes_progress(tier_store, mode):
    """A budget no entry can fit under must behave like a cache that caches
    nothing: every get returns the right shard, bytes stay at <= budget."""
    cache = CompressedShardCache(tier_store, mode=mode, budget_bytes=64)
    for p in list(range(tier_store.num_shards)) * 2:
        shard = cache.get(p)
        assert shard.shard_id == p
        assert cache.cached_bytes <= cache.budget
    assert cache.stats.misses == 2 * tier_store.num_shards
    if cache.adaptive:
        cache.audit()


def test_budget_smaller_than_largest_shard_caches_what_fits(tier_store):
    """Budget below the largest single shard: the big shard is served
    uncached, smaller entries (cold blobs) still earn their keep."""
    raw = _raw_nbytes(
        CompressedShardCache(tier_store, mode=1, budget_bytes=1), tier_store)
    budget = max(raw) - 1
    for mode in ("adaptive", 1):
        cache = CompressedShardCache(tier_store, mode=mode,
                                     budget_bytes=budget)
        for p in range(tier_store.num_shards):
            cache.get(p)
            assert cache.cached_bytes <= cache.budget
        # a full sweep is served correctly and SOMETHING was cacheable
        # (cold blobs compress under the raw size; mode 1 keeps small shards)
        assert cache.cached_shards >= 1
        if cache.adaptive:
            cache.audit()


def test_budget_zero_degrades_to_mode_0(tier_store):
    for requested in ("auto", "adaptive", 1, 4):
        cache = CompressedShardCache(tier_store, mode=requested,
                                     budget_bytes=0)
        assert cache.mode == 0 and not cache.adaptive
        shard = cache.get(0)
        assert shard.shard_id == 0
        assert cache.cached_bytes == 0 and cache.cached_shards == 0
        assert cache.stats.hits == 0 and cache.stats.misses == 1
    with pytest.raises(ValueError, match="budget_bytes"):
        CompressedShardCache(tier_store, budget_bytes=-1)


def test_cache_ctor_validates_tier_knobs(tier_store):
    with pytest.raises(ValueError, match="hot_fraction"):
        CompressedShardCache(tier_store, budget_bytes=1, hot_fraction=0.0)
    with pytest.raises(ValueError, match="promote_after"):
        CompressedShardCache(tier_store, budget_bytes=1, promote_after=0)


# ---------------------------------------------------------------------------
# promotion/demotion churn under the 8-thread hammer, audited every op
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("budget_shards", [2, 4])
def test_adaptive_churn_hammer_byte_accounting_exact(tier_store, budget_shards):
    """8 threads hammer a tight adaptive cache; after EVERY operation the
    running byte counters are recounted from the actual tier contents
    (cache.audit()), so any promotion/demotion/eviction accounting race
    fails loudly, not statistically."""
    from repro.graph.storage import GraphStore
    store = GraphStore(tier_store.path)  # private io counters
    sizes = [store.shard_nbytes(p) for p in range(store.num_shards)]
    cache = CompressedShardCache(store, mode="adaptive",
                                 budget_bytes=budget_shards * max(sizes),
                                 promote_after=2)
    per_thread = 40
    errors = []
    barrier = threading.Barrier(8)

    def hammer(seed):
        rng = np.random.default_rng(seed)
        barrier.wait()
        try:
            for sid in rng.integers(0, store.num_shards, size=per_thread):
                shard = cache.get(int(sid))
                assert shard.shard_id == int(sid)
                cache.audit()  # byte accounting verified after every op
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert cache.stats.hits + cache.stats.misses == 8 * per_thread
    # every miss was charged at canonical nbytes, and reads match exactly
    assert cache.stats.disk_bytes == store.io.read
    assert cache.cached_bytes <= cache.budget
    cache.audit()


def test_byte_counters_read_from_another_thread_never_pass_the_budget(
        tier_store):
    """A put counts its entry before ``_enforce`` evicts; a reader on
    another thread at that moment (a memory sampler) must wait for the
    operation to end and read a total inside the budget."""
    from repro.graph.storage import GraphStore
    store = GraphStore(tier_store.path)
    cold = [len(CompressedShardCache(store, mode="adaptive",
                                     budget_bytes=1 << 28)._compress(
        store.read_shard_bytes(p))) for p in range(store.num_shards)]
    cache = CompressedShardCache(store, mode="adaptive",
                                 budget_bytes=2 * max(cold), promote_after=2)
    enforce, readers, reads = cache._enforce, [], []

    def enforce_with_a_reader():
        t = threading.Thread(target=lambda: reads.append(
            cache.cached_bytes))
        t.start()
        t.join(0.02)  # the reader has the floor while the put is open
        readers.append(t)
        enforce()

    cache._enforce = enforce_with_a_reader
    for sid in list(range(store.num_shards)) * 2:
        cache.get(sid)
    for t in readers:
        t.join()
    assert cache.stats.evictions > 0
    assert len(reads) == len(readers) > 0
    assert max(reads) <= cache.budget
    cache.audit()


def test_adaptive_ample_budget_misses_once_per_shard(tier_store):
    """With an ample budget the adaptive cache has static-mode economics:
    exactly one miss (and one canonical-size disk charge) per shard."""
    from repro.graph.storage import GraphStore
    store = GraphStore(tier_store.path)
    cache = CompressedShardCache(store, mode="adaptive", budget_bytes=1 << 28)
    P = store.num_shards
    rng = np.random.default_rng(0)
    for sid in rng.permutation(np.repeat(np.arange(P), 5)):
        cache.get(int(sid))
    assert cache.stats.misses == P
    assert cache.stats.evictions == 0
    assert cache.stats.disk_bytes == sum(
        store.shard_nbytes(p) for p in range(P))
    cache.audit()


# ---------------------------------------------------------------------------
# session plumbing: knobs, env vars, cache_report
# ---------------------------------------------------------------------------
def test_session_cache_report_is_self_consistent(tier_store):
    sess = GraphSession(tier_store, cache_mode="adaptive",
                        cache_budget_bytes=1 << 28)
    sess.run("pagerank", max_iters=4)
    rep = sess.cache_report()
    assert rep["policy"] == "adaptive"
    assert rep["hot_bytes"] + rep["cold_bytes"] == rep["cached_bytes"]
    assert rep["cached_bytes"] <= rep["budget_bytes"]
    assert rep["hot_hits"] + rep["cold_hits"] == rep["hits"]
    assert rep["misses"] == tier_store.num_shards  # ample: one per shard
    # warm sweeps promoted the whole working set: decode cost is being
    # saved on every hot hit from iteration 3 on
    assert rep["hot_shards"] > 0
    assert rep["decode_seconds_saved"] > 0.0
    assert rep["promotions"] >= rep["hot_shards"]
    # per-iteration plumbing: the saved seconds show up in IterationStats
    saved = sum(h.decode_seconds_saved
                for h in sess.engine("pagerank").last_result.history)
    assert saved == pytest.approx(rep["decode_seconds_saved"], abs=1e-9)


def test_static_sessions_report_static_policy(tier_store):
    sess = GraphSession(tier_store, cache_mode=1, cache_budget_bytes=1 << 28)
    sess.run("pagerank", max_iters=2)
    rep = sess.cache_report()
    assert rep["policy"] == "static" and rep["mode"] == 1
    assert rep["promotions"] == rep["demotions"] == 0
    # static mode 1 entries are decompressed arrays: the hot tier, reported
    assert rep["hot_bytes"] == rep["cached_bytes"] > 0


def test_cache_budget_env_alias_and_tier_knobs(monkeypatch):
    monkeypatch.setenv("GRAPHMP_CACHE_BUDGET", str(1 << 21))
    monkeypatch.setenv("GRAPHMP_CACHE_HOT_FRACTION", "0.25")
    monkeypatch.setenv("GRAPHMP_CACHE_PROMOTE_AFTER", "3")
    cfg = EngineConfig.from_env()
    assert cfg.cache_budget_bytes == 1 << 21
    assert cfg.cache_hot_fraction == 0.25
    assert cfg.cache_promote_after == 3
    # the new name wins over the legacy alias when both are set
    monkeypatch.setenv("GRAPHMP_CACHE_BUDGET_BYTES", str(1 << 20))
    assert EngineConfig.from_env().cache_budget_bytes == 1 << 21
    # empty string (unset CI matrix legs) falls back to the default
    monkeypatch.setenv("GRAPHMP_CACHE_BUDGET", "")
    assert EngineConfig.from_env().cache_budget_bytes == 1 << 20  # legacy alias
    monkeypatch.setenv("GRAPHMP_CACHE_BUDGET_BYTES", "")
    assert (EngineConfig.from_env().cache_budget_bytes
            == EngineConfig().cache_budget_bytes)


def test_clear_drops_tiers_and_placement_state(tier_store):
    cache = CompressedShardCache(tier_store, mode="adaptive",
                                 budget_bytes=1 << 28)
    cache.get(0)
    cache.get(0)
    cache.clear()
    assert cache.cached_bytes == 0 and cache.cached_shards == 0
    assert cache.shard_tier(0) == "out"
    hits, misses = cache.stats.hits, cache.stats.misses
    cache.get(0)  # a fresh miss (placement state was reset too)
    assert cache.shard_tier(0) in ("cold", "hot")
    assert (cache.stats.hits, cache.stats.misses) == (hits, misses + 1)
    cache.audit()
