"""Edge weights from the configuration (Graph500 kernel 3), through the
edge list and preprocessing into the shards, and the arcs a sweep steps.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_weighted_graph.py
"""
from __future__ import annotations

import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import graph500  # noqa: E402
import oracle  # noqa: E402

from repro.graph.preprocess import preprocess_graph  # noqa: E402
from repro.graph.storage import GraphStore  # noqa: E402
from repro.session import GraphSession  # noqa: E402

CONFIG = {"scale": 8, "edge_factor": 4, "a": 0.57, "b": 0.19, "c": 0.19,
          "directed": False, "weighted": False}


def _digest(src: np.ndarray, dst: np.ndarray) -> str:
    return hashlib.sha256(src.tobytes() + dst.tobytes()).hexdigest()


# sha256 of the int32 src then dst bytes of CONFIG's arcs, taken before the
# generator could make weights: an unweighted graph stays bit for bit
DIGESTS = {
    5: "ad6738d270e39f964a41d08e7cd36aa4a1626ea4e4c16359f54ed45aa57eea71",
    (1 << 31) + 3:
        "22100d14aaa54f41425525cb4291b2ecc591ef40ee06a232b4f442d6c12a6ecd",
    2718281828:
        "c61b0a5d47b1f51de001b4af85c6975ee24dae491e4b919143cefb37fdee7299",
}


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_unweighted_arcs_are_unchanged(seed):
    digest = DIGESTS[seed]
    src, dst, weights = graph500.config_arcs(CONFIG, seed)
    assert weights is None and _digest(src, dst) == digest
    # the weighted graph has the same arcs
    wsrc, wdst, _ = graph500.config_arcs(dict(CONFIG, weighted=True), seed)
    assert _digest(wsrc, wdst) == digest


def test_weights_are_uniform_per_edge_and_per_seed():
    config = dict(CONFIG, scale=10, edge_factor=16, weighted=True)
    m = 16 << 10
    _, _, w = graph500.config_arcs(config, 7)
    _, _, again = graph500.config_arcs(config, 7)
    _, _, other = graph500.config_arcs(config, 8)
    _, _, high = graph500.config_arcs(config, 7 + (1 << 32))
    assert w.dtype == np.float32 and w.shape == (2 * m,)
    assert 0.0 <= w.min() and w.max() < 1.0
    assert np.array_equal(w[:m], w[m:])  # both arcs of an edge
    assert np.array_equal(w, again)
    assert not np.array_equal(w, other) and not np.array_equal(w, high)
    # uniform on [0, 1): mean 1/2, variance 1/12
    assert abs(w[:m].mean() - 0.5) < 5 * np.sqrt(1 / 12 / m)
    assert abs(w[:m].var() - 1 / 12) < 0.01
    assert len(np.unique(w[:m])) > 0.99 * m


@pytest.fixture(scope="module")
def weighted_graph(tmp_path_factory):
    """A scale-10 weighted graph written in 3 chunks and preprocessed into
    several shards."""
    tmp = tmp_path_factory.mktemp("weighted")
    config = dict(CONFIG, scale=10, edge_factor=16, weighted=True)
    n = 1 << 10
    src, dst, w = graph500.config_arcs(config, 11)
    oracle.write_edge_list(tmp / "edges", src, dst, n, w, chunk=12_000)
    preprocess_graph(str(tmp / "edges"), str(tmp / "graph"),
                     threshold_edge_num=1 << 12, val_dtype="float32")
    return tmp, src, dst, w, n


def test_weights_round_trip_through_the_edge_list(weighted_graph):
    tmp, src, dst, w, n = weighted_graph
    assert sorted(p.name for p in (tmp / "edges").glob("weights_*.npy")) \
        == ["weights_00000.npy", "weights_00001.npy", "weights_00002.npy"]
    got = oracle.read_edge_list(tmp / "edges")
    assert got[2] == n
    for a, b in zip(got[:2] + got[3:], (src, dst, w)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    plain = tmp / "plain"
    oracle.write_edge_list(plain, src, dst, n)
    assert oracle.read_edge_list(plain)[3] is None
    assert not list(plain.glob("weights_*"))


def test_weights_reach_the_shards_vals(weighted_graph):
    tmp, src, dst, w, n = weighted_graph
    store = GraphStore(tmp / "graph")
    shards = store.properties["shards"]
    assert len(shards) > 1 and store.properties["weighted"]
    rows = []
    for p in range(len(shards)):
        ell = store.read_shard(p)
        local, s, v = ell.edges()
        rows.append(np.stack([s, local + ell.start_vertex,
                              v.view(np.uint32).astype(np.int64)], 1))
    got = np.concatenate(rows)
    want = np.stack([src, dst, w.view(np.uint32)], 1).astype(np.int64)
    key = lambda a: a[np.lexsort(a.T[::-1])]  # noqa: E731
    assert np.array_equal(key(got), key(want))


def test_a_full_sweep_steps_every_generated_arc(weighted_graph):
    """PageRank sweeps step every shard: the arcs they count are the
    generated ones, which ``spmv_roofline`` sums over a window."""
    tmp, src, _, _, _ = weighted_graph
    session = GraphSession(str(tmp / "graph"), prefetch_depth=2)
    try:
        r = session.run("pagerank", max_iters=3)
    finally:
        session.close()
    assert len(r.history) == 3
    for h in r.history:
        assert h.shards_skipped == 0 and h.ell_arcs == src.size


def _edges(src, dst, w=None):
    """The generated edges (the first half of the arcs) as sorted rows."""
    m = src.size // 2
    cols = [src[:m], dst[:m]] + ([] if w is None else [w[:m].view(np.uint32)])
    a = np.stack(cols, 1).astype(np.int64)
    return a[np.lexsort(a.T[::-1])]


def test_graph_seed_gives_every_seed_one_graph_in_another_order():
    config = dict(CONFIG, scale=10, edge_factor=16, weighted=True,
                  graph_seed=42)
    src, dst, w = graph500.config_arcs(config, 7)
    src2, dst2, w2 = graph500.config_arcs(config, (1 << 31) + 5)
    # another edge order ...
    assert not np.array_equal(src, src2)
    # ... of the same edges, each with its weight: the graph seed 42 draws
    # with no graph_seed
    same = _edges(src, dst, w)
    assert np.array_equal(_edges(src2, dst2, w2), same)
    base = graph500.config_arcs(dict(config, graph_seed=None), 42)
    assert np.array_equal(_edges(*base), same)
    assert np.array_equal(src[src.size // 2:], dst[:dst.size // 2])
    assert np.array_equal(w[:w.size // 2], w[w.size // 2:])
    # and another graph seed, another graph
    other = graph500.config_arcs(dict(config, graph_seed=43), 7)
    assert not np.array_equal(_edges(*other), same)


def test_graph_seed_gives_every_seed_the_same_shards(tmp_path):
    config = dict(CONFIG, scale=10, edge_factor=16, graph_seed=42)
    n = 1 << 10
    shapes = []
    for seed in (7, 2718281828):
        src, dst, _ = graph500.config_arcs(config, seed)
        edges, graph = tmp_path / f"edges{seed}", tmp_path / f"graph{seed}"
        oracle.write_edge_list(edges, src, dst, n)
        preprocess_graph(str(edges), str(graph), threshold_edge_num=1 << 12)
        props = GraphStore(graph).properties
        shapes.append((props["intervals"],
                       [(s["rows"], s["width"]) for s in props["shards"]]))
    assert len(shapes[0][1]) > 1 and shapes[0] == shapes[1]
