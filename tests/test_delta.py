"""GraphDelta subsystem: mutable overlay, epochs, compaction, incremental.

The contract under test (ISSUE 6):

  * a run over ``DeltaGraphStore(base) + apply(edits)`` is bitwise-identical
    (min-propagation apps) to a run over a freshly preprocessed graph holding
    the merged edge set — across every storage backend, cache mode, and
    prefetch depth;
  * epoch-grained invalidation: mutating one shard drops exactly that
    shard's cache entry (``stale_drops``), clean shards stay resident, and
    the serve memo survives a mutation for incremental-capable apps;
  * ``compact()`` folds only dirty shards into the base; a reopened store is
    indistinguishable from a fresh preprocess of the merged edges;
  * ``run_incremental`` continues a previous fixpoint after monotone deltas
    in fewer iterations and fewer disk bytes than a cold run, and falls back
    to a cold run whenever the shortcut would be unsound (deletes, weight
    increases, unconverged prev, non-incremental apps);
  * a mid-run mutation raises ``ConcurrentMutationError`` (the engine pins
    the epoch at run start) instead of mixing epochs into one result.
"""
import threading

import numpy as np
import pytest

from repro.graph.compact import compact
from repro.graph.delta import DeltaBudgetError, DeltaGraphStore
from repro.graph.preprocess import preprocess_graph
from repro.graph.source import ConcurrentMutationError, graph_token
from repro.graph.storage import GraphStore, write_edge_list
from repro.session import GraphSession

from tests._hypo import HAVE_HYPOTHESIS, given, settings, st

try:
    import networkx as nx
except ImportError:  # pragma: no cover - exercised on minimal installs
    nx = None

needs_networkx = pytest.mark.skipif(nx is None,
                                    reason="networkx not installed")

N = 384
# 1 seed vertex / N = 0.0026 must still trigger selective scheduling
THRESH = 0.05


# ---------------------------------------------------------------------------
# graph construction helpers
# ---------------------------------------------------------------------------
def _random_edges(seed, n=N, m=2000, symmetric=False):
    """Deduplicated random (src, dst, weight) arrays, no self-loops."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    if symmetric:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    keep = src != dst
    src, dst = src[keep], dst[keep]
    _, idx = np.unique(dst.astype(np.int64) * n + src, return_index=True)
    src, dst = src[idx], dst[idx]
    w = rng.uniform(0.5, 2.0, src.size).astype(np.float32)
    return src.astype(np.int64), dst.astype(np.int64), w


def _fresh_inserts(seed, src, dst, n=N, count=50, symmetric=False):
    """``count`` (s, d, w) triples absent from the given edge set."""
    rng = np.random.default_rng(seed + 7)
    have = set(zip(src.tolist(), dst.tolist()))
    out = []
    while len(out) < count:
        s, d = int(rng.integers(0, n)), int(rng.integers(0, n))
        if s == d or (s, d) in have:
            continue
        w = float(rng.uniform(0.5, 2.0))
        have.add((s, d))
        out.append((s, d, w))
        if symmetric and (d, s) not in have:
            have.add((d, s))
            out.append((d, s, w))
    return out


def _preprocess(tmp, name, src, dst, w, n=N, threshold=512, width=64):
    e, g = tmp / f"el_{name}", tmp / f"g_{name}"
    write_edge_list(e, [(src, dst)], weighted=True)
    np.save(e / "weights_00000.npy", np.asarray(w, dtype=np.float32))
    preprocess_graph(e, g, threshold_edge_num=threshold, ell_max_width=width,
                     num_vertices=n)
    return g


def _merged(src, dst, w, inserts):
    ins = np.array(inserts, dtype=np.float64)
    return (np.concatenate([src, ins[:, 0].astype(np.int64)]),
            np.concatenate([dst, ins[:, 1].astype(np.int64)]),
            np.concatenate([w, ins[:, 2].astype(np.float32)]))


@pytest.fixture(scope="module")
def graphs(tmp_path_factory):
    """(base_dir, merged_dir, base edges, inserts) shared across tests that
    only READ the base directory (every mutation happens in an overlay)."""
    tmp = tmp_path_factory.mktemp("delta_graphs")
    src, dst, w = _random_edges(0)
    inserts = _fresh_inserts(0, src, dst)
    base = _preprocess(tmp, "base", src, dst, w)
    ms, md, mw = _merged(src, dst, w, inserts)
    merged = _preprocess(tmp, "merged", ms, md, mw)
    return base, merged, (src, dst, w), inserts


# ---------------------------------------------------------------------------
# overlay == pre-merged, across backends / cache modes / prefetch depths
# ---------------------------------------------------------------------------
MATRIX = [pytest.param(b, d, m, id=f"{b}-depth{d}-mode{m}")
          for b in ("npz", "packed", "memory")
          for d, m in ((0, "auto"), (2, "auto"), (0, 0))]


@pytest.mark.parametrize("backend,depth,mode", MATRIX)
def test_overlay_matches_premerged(graphs, backend, depth, mode):
    base, merged, _, inserts = graphs
    with GraphSession(merged, selective_threshold=THRESH) as ref, \
            GraphSession(base, backend=backend, mutable=True,
                         prefetch_depth=depth, cache_mode=mode,
                         selective_threshold=THRESH) as sess:
        assert isinstance(sess.store, DeltaGraphStore)
        sess.apply_mutations(inserts=inserts)
        assert sess.store.epoch() == 1
        for app, kw in (("sssp", {"source": 0}), ("bfs", {"source": 0}),
                        ("cc", {})):
            got = sess.run(app, **kw).values
            want = ref.run(app, **kw).values
            assert np.array_equal(got, want), app  # bitwise, not just close
        pr = sess.run("pagerank", max_iters=15).values
        pr_ref = ref.run("pagerank", max_iters=15).values
        np.testing.assert_allclose(pr, pr_ref, atol=1e-6)
        assert sess.store.num_edges == ref.store.num_edges


def test_noop_upsert_preserves_content_and_size(graphs):
    """Re-inserting an existing edge with its existing weight yields the
    same edge set, ELL shape and canonical blob size (the edge may move to
    the end of its destination row, so raw bytes are not compared)."""
    base, _, (src, dst, w), _ = graphs
    store = DeltaGraphStore(GraphStore(base))
    before = store.read_shard(0)
    edges_before = sorted(zip(*before.edges()))
    nbytes_before = store.shard_nbytes(0)
    iv = store.intervals
    sel = (dst >= iv[0]) & (dst < iv[1])
    i = int(np.nonzero(sel)[0][0])
    store.apply(inserts=[(int(src[i]), int(dst[i]), float(w[i]))])
    assert store.dirty_shards() == [0]
    after = store.read_shard(0)
    assert sorted(zip(*after.edges())) == edges_before
    assert after.shape == before.shape
    assert store.shard_nbytes(0) == nbytes_before


def test_upsert_collapses_and_updates_weight(tmp_path):
    src = np.array([0, 1, 2]); dst = np.array([1, 2, 0])
    w = np.array([1.0, 1.0, 1.0], dtype=np.float32)
    g = _preprocess(tmp_path, "tri", src, dst, w, n=3, threshold=8, width=8)
    store = DeltaGraphStore(GraphStore(g))
    store.apply(updates=[(0, 1, 5.0)])  # weight upsert, no new edge
    assert store.num_edges == 3
    _, s, v = store.read_shard(0).edges()
    assert v[s == 0] == pytest.approx([5.0])
    in_deg, out_deg = store.read_vertex_info()
    assert in_deg.tolist() == [1, 1, 1] and out_deg.tolist() == [1, 1, 1]


def test_delete_semantics_and_validation(tmp_path):
    src = np.array([0, 1, 2]); dst = np.array([1, 2, 0])
    w = np.ones(3, dtype=np.float32)
    g = _preprocess(tmp_path, "tri", src, dst, w, n=3, threshold=8, width=8)
    store = DeltaGraphStore(GraphStore(g))
    store.apply(deletes=[(1, 2)])
    assert store.num_edges == 2
    in_deg, out_deg = store.read_vertex_info()
    assert in_deg.tolist() == [1, 1, 0] and out_deg.tolist() == [1, 0, 1]
    # deleting an absent edge is a no-op commit for that key
    e = store.apply(deletes=[(1, 2)])
    assert store.num_edges == 2 and e == store.epoch()
    # in one batch, deletes are applied after inserts: the delete wins
    store.apply(inserts=[(1, 2, 9.0)], deletes=[(1, 2)])
    assert store.num_edges == 2
    store.apply(inserts=[(1, 2, 9.0)])
    assert store.num_edges == 3
    with pytest.raises(ValueError, match="vertex set is fixed"):
        store.apply(inserts=[(0, 99)])


def test_epoch_log_and_monotonicity(tmp_path):
    src, dst, w = _random_edges(3, n=64, m=300)
    g = _preprocess(tmp_path, "mono", src, dst, w, n=64, threshold=128,
                    width=32)
    store = DeltaGraphStore(GraphStore(g))
    assert store.monotone_since(0) and store.epoch() == 0
    ins = _fresh_inserts(3, src, dst, n=64, count=4)
    store.apply(inserts=ins)
    assert store.monotone_since(0) is True
    # lowering an existing weight stays monotone; raising one does not
    s0, d0, w0 = ins[0]
    store.apply(updates=[(s0, d0, w0 / 2)])
    assert store.monotone_since(0) is True
    store.apply(updates=[(s0, d0, w0 * 10)])
    assert store.monotone_since(0) is False
    assert store.monotone_since(store.epoch()) is True  # empty suffix
    affected = store.affected_sources_since(0)
    assert s0 in affected.tolist()
    assert store.affected_sources_since(store.epoch()).size == 0


# ---------------------------------------------------------------------------
# epoch-grained cache invalidation
# ---------------------------------------------------------------------------
def test_cache_retains_clean_shards(tmp_path):
    # many small shards so a single-shard mutation is <10% of the graph
    src, dst, w = _random_edges(5, m=4000)
    g = _preprocess(tmp_path, "many", src, dst, w, threshold=128, width=32)
    with GraphSession(g, mutable=True, selective_threshold=THRESH) as sess:
        P = sess.store.num_shards
        assert P >= 10
        sess.warm()
        rep0 = sess.cache_report()
        assert rep0["hot_shards"] + rep0["cold_shards"] == P
        lo = int(sess.store.intervals[0])
        # force every edit into shard 0 (distinct sources, one destination)
        ins = [(s, lo, wt) for s, _d, wt in _fresh_inserts(5, src, dst,
                                                           count=3)]
        sess.apply_mutations(inserts=ins)
        assert sess.store.dirty_shards() == [0]
        misses0 = sess.stats.misses
        sess.warm()  # re-touch every shard: only the dirty one re-reads
        rep1 = sess.cache_report()
        assert rep1["stale_drops"] == 1
        assert sess.stats.misses - misses0 == 1
        resident1 = rep1["hot_shards"] + rep1["cold_shards"]
        assert resident1 == P  # dirty shard re-admitted after re-read
        # >= 80% of entries survived the mutation (here: all but one)
        assert (P - rep1["stale_drops"]) / P >= 0.8


def test_frozen_store_epoch_and_token(graphs):
    base, _, _, _ = graphs
    store = GraphStore(base)
    assert store.epoch() == 0 and store.shard_epoch(0) == 0
    tok = graph_token(store)
    assert tok[1] == "mtime"  # frozen: falls back to property.json mtime
    overlay = DeltaGraphStore(store)
    assert graph_token(overlay)[1] == "mtime"  # pristine overlay: epoch 0
    overlay.apply(inserts=_fresh_inserts(1, *_random_edges(0)[:2], count=1))
    assert graph_token(overlay) == (str(store.path), "epoch", 1)


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["npz", "packed", "memory"])
def test_compaction_roundtrip(graphs, backend, tmp_path):
    base, merged, (src, dst, w), inserts = graphs
    if backend != "memory":
        # compaction rewrites the base in place: work on a private copy
        import shutil
        priv = tmp_path / "priv"
        shutil.copytree(base, priv)
        base = priv
    with GraphSession(merged, selective_threshold=THRESH) as ref:
        want = ref.run("sssp", source=0).values
        ref_nbytes = [ref.store.shard_nbytes(p)
                      for p in range(ref.store.num_shards)]
        ref_edges = ref.store.num_edges
    with GraphSession(base, backend=backend, mutable=True,
                      selective_threshold=THRESH) as sess:
        sess.apply_mutations(inserts=inserts)
        dirty = sess.store.dirty_shards()
        report = compact(sess.store)
        assert report.shards_rewritten == tuple(dirty)
        assert report.bytes_written > 0
        assert sess.store.dirty_shards() == []
        assert sess.store.delta_nbytes() == 0
        assert sess.store.epoch() == 1  # compaction does NOT bump the epoch
        if backend == "packed":
            # append-only rewrite: superseded segments become dead bytes
            assert report.dead_bytes > 0
        else:
            assert report.dead_bytes == 0
        # the session keeps serving correct results over the compacted base
        assert np.array_equal(sess.run("sssp", source=0).values, want)
        # idempotent: nothing left to fold
        assert compact(sess.store).shards_rewritten == ()
    if backend == "memory":
        return  # RAM-resident: compaction cannot (and must not) touch disk
    with GraphSession(base, backend=backend,
                      selective_threshold=THRESH) as reopened:
        assert np.array_equal(reopened.run("sssp", source=0).values, want)
        assert reopened.store.num_edges == ref_edges
        # the shard metadata follows the re-merged shards
        assert [m["slices"] for m in reopened.store.properties["shards"]] \
            == [reopened.store.read_shard(p).num_slices
                for p in range(reopened.store.num_shards)]
        if backend == "npz":
            # disk-byte accounting matches a fresh pack of the merged graph
            got = [reopened.store.shard_nbytes(p)
                   for p in range(reopened.store.num_shards)]
            assert got == ref_nbytes


def test_delta_budget_autocompact_and_error(graphs, tmp_path):
    import shutil
    base, _, (src, dst, _w), _ = graphs
    priv = tmp_path / "priv"
    shutil.copytree(base, priv)
    ins = _fresh_inserts(9, src, dst, count=4)
    store = DeltaGraphStore(GraphStore(priv), delta_budget_bytes=1,
                            auto_compact=True)
    store.apply(inserts=ins[:2])
    assert store.dirty_shards() == []  # budget blown -> auto-compacted
    assert store.epoch() == 1
    frozen = DeltaGraphStore(GraphStore(priv), delta_budget_bytes=1,
                             auto_compact=False)
    with pytest.raises(DeltaBudgetError):
        frozen.apply(inserts=ins[2:])


# ---------------------------------------------------------------------------
# incremental recompute
# ---------------------------------------------------------------------------
@needs_networkx
def test_incremental_sssp_matches_networkx(graphs):
    base, _, (src, dst, w), inserts = graphs
    G = nx.DiGraph()
    G.add_nodes_from(range(N))
    G.add_weighted_edges_from(zip(src.tolist(), dst.tolist(),
                                  np.asarray(w, np.float64).tolist()))
    for s, d, wt in inserts:
        G.add_edge(s, d, weight=wt)
    lengths = nx.single_source_dijkstra_path_length(G, 0)
    want = np.full(N, np.inf)
    for v, dist in lengths.items():
        want[v] = dist
    # cache off: per-iteration disk bytes then reflect every shard fetch, so
    # the incremental-vs-cold I/O comparison is honest, not hidden by hits
    with GraphSession(base, mutable=True, selective_threshold=THRESH,
                      cache_budget_bytes=0) as sess:
        prev = sess.run("sssp", source=0)
        assert prev.converged and prev.epoch == 0 and prev.tag == "sssp:(0,)"
        sess.apply_mutations(inserts=inserts)
        inc = sess.run_incremental("sssp", source=0, prev=prev)
        inc_bytes = sum(h.disk_bytes for h in inc.history)
        cold = sess.run("sssp", source=0)
        cold_bytes = sum(h.disk_bytes for h in cold.history)
    np.testing.assert_allclose(inc.values, want, atol=1e-5)
    assert np.array_equal(inc.values, cold.values)
    assert inc.iterations < cold.iterations
    assert inc_bytes < cold_bytes  # frontier-local: fewer shards touched
    assert inc.epoch == 1


@needs_networkx
def test_incremental_cc_matches_networkx(tmp_path):
    # symmetric graph: directed min-label propagation == connected components
    src, dst, w = _random_edges(11, m=600, symmetric=True)
    inserts = _fresh_inserts(11, src, dst, count=20, symmetric=True)
    g = _preprocess(tmp_path, "sym", src, dst, w)
    G = nx.Graph()
    G.add_nodes_from(range(N))
    G.add_edges_from(zip(src.tolist(), dst.tolist()))
    G.add_edges_from((s, d) for s, d, _ in inserts)
    want = np.empty(N)
    for comp in nx.connected_components(G):
        want[list(comp)] = min(comp)
    with GraphSession(g, mutable=True, selective_threshold=THRESH) as sess:
        prev = sess.run("cc")
        sess.apply_mutations(inserts=inserts)
        inc = sess.run_incremental("cc", prev=prev)
        cold = sess.run("cc")
    assert np.array_equal(inc.values, want)
    assert np.array_equal(inc.values, cold.values)


def test_incremental_fastpath_and_fallbacks(graphs):
    base, _, (src, dst, _w), inserts = graphs
    with GraphSession(base, mutable=True, selective_threshold=THRESH) as sess:
        prev = sess.run("sssp", source=0)
        # unchanged epoch: previous fixpoint returned as-is, zero sweeps
        again = sess.run_incremental("sssp", source=0, prev=prev)
        assert again.iterations == 0 and again.converged
        assert np.array_equal(again.values, prev.values)
        # wrong source: refuse to continue a different query's fixpoint
        with pytest.raises(ValueError, match="incremental recompute"):
            sess.run_incremental("sssp", source=1, prev=prev)
        # a delete breaks monotonicity: falls back to a correct cold run
        sess.apply_mutations(inserts=inserts,
                             deletes=[(int(src[0]), int(dst[0]))])
        assert not sess.store.monotone_since(prev.epoch)
        inc = sess.run_incremental("sssp", source=0, prev=prev)
        cold = sess.run("sssp", source=0)
        assert np.array_equal(inc.values, cold.values)
        # pagerank is not incremental-capable: full run, still correct
        pr_prev = sess.run("pagerank", max_iters=10)
        sess.apply_mutations(inserts=_fresh_inserts(21, src, dst, count=3))
        pr_inc = sess.run_incremental("pagerank", max_iters=10, prev=pr_prev)
        pr_cold = sess.run("pagerank", max_iters=10)
        np.testing.assert_allclose(pr_inc.values, pr_cold.values, atol=1e-6)


# ---------------------------------------------------------------------------
# epoch pinning: mutations cannot tear a running sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [0, 2])
def test_mid_run_mutation_raises(graphs, depth):
    base, _, (src, dst, _w), _ = graphs
    with GraphSession(base, mutable=True, prefetch_depth=depth) as sess:
        gen = sess.iter_run("pagerank", max_iters=10)
        next(gen)  # run is now mid-flight, epoch pinned at 0
        sess.store.apply(inserts=_fresh_inserts(31, src, dst, count=1))
        with pytest.raises(ConcurrentMutationError):
            for _ in gen:
                pass
        # the NEXT run re-syncs to the new epoch and completes fine
        res = sess.run("pagerank", max_iters=5)
        assert res.epoch == 1


# ---------------------------------------------------------------------------
# serving: memo keyed by epoch, apply_mutations drains + refreshes
# ---------------------------------------------------------------------------
def test_service_memo_refresh_across_mutation(graphs):
    base, _, (src, dst, _w), inserts = graphs
    with GraphSession(base, mutable=True, selective_threshold=THRESH) as sess, \
            sess.service(max_batch=4, max_wait_ms=1.0) as svc:
        for s in (0, 1, 2, 3):
            svc.submit("sssp", source=s).result()
        svc.submit("cc").result()
        svc.submit("pagerank").result()
        assert len(svc._memo) == 6
        report = svc.apply_mutations(inserts=inserts)
        assert report.epoch == 1
        assert report.memo_refreshed == 5  # 4 sssp sources + cc
        assert report.memo_dropped == 1    # pagerank: not incremental
        snap = svc.stats.snapshot()
        fut = svc.submit("sssp", source=2)  # must hit the refreshed memo
        got = fut.result().values
        assert svc.stats.snapshot()["memo_hits"] == snap["memo_hits"] + 1
        assert np.array_equal(got, sess.run("sssp", source=2).values)


def test_service_mutation_under_concurrent_traffic(graphs):
    base, _, (src, dst, _w), inserts = graphs
    with GraphSession(base, mutable=True, selective_threshold=THRESH) as sess, \
            sess.service(max_batch=4, max_wait_ms=0.5) as svc:
        errors, stop = [], threading.Event()

        def hammer():
            i = 0
            while not stop.is_set():
                try:
                    svc.submit("sssp", source=i % 8).result(timeout=60)
                except Exception as exc:  # noqa: BLE001 - recorded for assert
                    errors.append(exc)
                i += 1

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for i in range(0, len(inserts), 10):
                svc.apply_mutations(inserts=inserts[i:i + 10])
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors  # no request ever saw a torn or stale graph
        want = sess.run("sssp", source=0).values
        got = svc.submit("sssp", source=0).result().values
        assert np.array_equal(got, want)
    # every mutation landed: final state equals the fully merged graph
    assert sess.store.epoch() == 5


# ---------------------------------------------------------------------------
# property test: overlay edge set == brute-force dict model
# ---------------------------------------------------------------------------
_HN = 48  # tiny graph: the property test runs many examples


def _store_edge_dict(store):
    out = {}
    for p in range(store.num_shards):
        shard = store.read_shard(p)
        local, s, v = shard.edges()
        for li, si, vi in zip(local + shard.start_vertex, s, v):
            out[(int(si), int(li))] = float(np.float32(vi))
    return out


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=25, deadline=None)
@given(st.data())
def test_apply_matches_dict_model(tmp_path_factory, data):
    tmp = tmp_path_factory.mktemp("hypo")
    src, dst, w = _random_edges(17, n=_HN, m=160)
    g = _preprocess(tmp, "h", src, dst, w, n=_HN, threshold=64, width=16)
    store = DeltaGraphStore(GraphStore(g))
    model = {(int(s), int(d)): float(np.float32(x))
             for s, d, x in zip(src, dst, w)}
    vertex = st.integers(0, _HN - 1)
    edge = st.tuples(vertex, vertex).filter(lambda e: e[0] != e[1])
    weight = st.floats(0.25, 4.0, width=32)
    for _ in range(data.draw(st.integers(1, 4))):
        ins = data.draw(st.lists(st.tuples(edge, weight), max_size=12))
        dels = data.draw(st.lists(edge, max_size=6))
        store.apply(inserts=[(s, d, x) for (s, d), x in ins],
                    deletes=dels)
        # replay with last-edit-wins order: inserts first, then deletes
        for (s, d), x in ins:
            model[(s, d)] = float(np.float32(x))
        for s, d in dels:
            model.pop((s, d), None)
        assert _store_edge_dict(store) == model
        assert store.num_edges == len(model)
        in_deg, out_deg = store.read_vertex_info()
        for v in range(_HN):
            assert out_deg[v] == sum(1 for k in model if k[0] == v)
            assert in_deg[v] == sum(1 for k in model if k[1] == v)
