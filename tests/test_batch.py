"""Batched multi-source traversal: K frontiers through one VSW sweep.

Covers the ISSUE-2 acceptance criteria:
  * ``run_batch`` is element-wise identical to K sequential single-source
    runs (hypothesis property over random graphs / shard counts / K);
  * a K=16 batch on a warm session reads no more disk bytes than one
    single-source run (the amortization claim);
  * batched Pallas and jnp-oracle SpMV paths agree on [n, K] inputs for all
    four semirings.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from tests._hypo import given, settings, st
from tests._layouts import random_layout

from repro.core.apps import get_app
from repro.core.engine import BatchRunResult
from repro.core.semiring import SEMIRINGS
from repro.graph.preprocess import preprocess_graph
from repro.graph.storage import write_edge_list
from repro.kernels.spmv import ref
from repro.kernels.spmv.ops import ell_spmv, ell_spmv_batch
from repro.session import GraphSession


# ---------------------------------------------------------------------------
# kernel-level: batched == per-column, Pallas == jnp oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("semiring", sorted(SEMIRINGS))
def test_batched_spmv_paths_agree_all_semirings(semiring):
    rng = np.random.default_rng(42)
    n, rows, K = 257, 120, 7
    layout = random_layout(rng, n, rows, 3000)
    x = (rng.random((n, K)) + 0.1).astype(np.float32)
    args = tuple(jnp.asarray(a) for a in layout) + (rows, semiring)
    pallas = ell_spmv_batch(jnp.asarray(x), *args, use_pallas=True)
    jnp_path = ell_spmv_batch(jnp.asarray(x), *args, use_pallas=False)
    oracle = ref.ell_spmv_batch_ref(jnp.asarray(x), *args)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(oracle),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp_path), np.asarray(oracle),
                               rtol=1e-5)
    # and each column equals the unbatched kernel on that column
    for k in range(K):
        single = ell_spmv(jnp.asarray(x[:, k]), *args, use_pallas=False)
        np.testing.assert_allclose(np.asarray(oracle[:, k]),
                                   np.asarray(single), rtol=1e-5)


# ---------------------------------------------------------------------------
# engine-level acceptance on the shared fixture graph
# ---------------------------------------------------------------------------
def test_run_batch_k16_warm_session_io_and_values(graph_store):
    """K=16 SSSP landmarks: no more disk than ONE single-source run on the
    same warm session, and element-wise equal to 16 sequential runs."""
    total = graph_store.total_shard_bytes()
    sess = GraphSession(graph_store, cache_mode=1,
                        cache_budget_bytes=4 * total)
    sess.warm()
    n = graph_store.num_vertices
    sources = [(i * 37) % n for i in range(16)]

    d0 = sess.stats.disk_bytes
    single = sess.run("sssp", source=sources[0], max_iters=100)
    single_disk = sess.stats.disk_bytes - d0

    d1 = sess.stats.disk_bytes
    batch = sess.run_batch("sssp", sources=sources, max_iters=100)
    batch_disk = sess.stats.disk_bytes - d1
    assert batch_disk <= single_disk  # 16 queries, <= 1 query's disk I/O

    assert len(batch) == 16
    np.testing.assert_array_equal(batch[0].values, single.values)
    for k, s in enumerate(sources[1:], start=1):
        seq = sess.run("sssp", source=s, max_iters=100)
        np.testing.assert_array_equal(batch[k].values, seq.values)


def test_run_batch_personalized_pagerank_columns_independent(graph_store):
    """Each PPR column equals a K=1 personalized run with that seed."""
    sess = GraphSession(graph_store, cache_mode=1, cache_budget_bytes=1 << 26)
    seeds = [3, 11, 29]
    batch = sess.run_batch("pagerank", sources=seeds, max_iters=25)
    for k, s in enumerate(seeds):
        # PPR's own vocabulary (seeds=) dispatches identically to sources=
        solo = sess.run_batch("personalized_pagerank", seeds=[s],
                              max_iters=25)
        np.testing.assert_allclose(batch[k].values, solo[0].values, atol=1e-6)
    # mass concentrates near the seed: the seed itself outranks the median
    for k, s in enumerate(seeds):
        assert batch[k].values[s] > np.median(batch[k].values)


def test_run_batch_honest_per_column_iterations(graph_store):
    """Column accounting: iterations vary per landmark, and the combined
    BatchRunResult stays available on the engine."""
    sess = GraphSession(graph_store)
    sources = (0, 1, 2, 3)
    batch = sess.run_batch("bfs", sources=sources, max_iters=100)
    combined = sess.last_batch_result
    assert isinstance(combined, BatchRunResult)
    assert sess.engine("bfs_multi", sources=sources).last_result is combined
    assert combined.values.shape == (graph_store.num_vertices, 4)
    for k, r in enumerate(batch):
        assert r.iterations == int(combined.column_iterations[k])
        assert r.iterations <= combined.iterations
        assert len(r.history) == r.iterations
        assert r.converged


def test_run_batch_argument_validation(graph_store):
    sess = GraphSession(graph_store)
    with pytest.raises(TypeError, match="needs sources"):
        sess.run_batch("sssp")
    with pytest.raises(TypeError, match="not a batched application"):
        sess.run_batch("cc", sources=[0])
    with pytest.raises(ValueError, match="at least one source"):
        get_app("sssp_multi", sources=())
    with pytest.raises(ValueError, match=">= 0"):
        sess.run_batch("sssp", sources=[0, -1])
    with pytest.raises(TypeError, match="not both"):
        sess.run_batch("ppr", sources=[1], seeds=[2])
    # a wrong kwarg on a genuinely batched app keeps the factory's own
    # message instead of being mislabeled "not a batched application"
    with pytest.raises(TypeError, match="damping"):
        sess.run_batch("sssp", sources=[0], damping=0.5)
    prog = get_app("sssp_multi", sources=(0, 1))
    with pytest.raises(TypeError, match="already fixes its frontiers"):
        sess.run_batch(prog, sources=[2])
    with pytest.raises(TypeError, match="only apply when dispatching by name"):
        sess.run_batch(prog, damping=0.5)  # kwargs must not be dropped


# ---------------------------------------------------------------------------
# property: run_batch == K sequential runs, over random graphs/shards/K
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000), st.integers(1, 6), st.sampled_from([96, 512]))
@settings(max_examples=8, deadline=None)
def test_property_batch_equals_sequential(tmp_path_factory, seed, K,
                                          threshold):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(48, 200))
    m = int(rng.integers(2 * n, 6 * n))
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    base = tmp_path_factory.mktemp(f"prop_{seed}_{K}_{threshold}")
    write_edge_list(base / "el", [(src, dst)], num_vertices=n)
    store = preprocess_graph(str(base / "el"), str(base / "store"),
                             threshold_edge_num=threshold, ell_max_width=128)
    sources = rng.integers(0, n, size=K).tolist()
    sess = GraphSession(store, cache_mode=1, cache_budget_bytes=1 << 24)
    batch = sess.run_batch("sssp", sources=sources, max_iters=n + 1)
    assert len(batch) == K
    for k, s in enumerate(sources):
        seq = sess.run("sssp", source=int(s), max_iters=n + 1)
        np.testing.assert_array_equal(batch[k].values, seq.values)
