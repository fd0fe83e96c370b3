"""The ``sssp`` application (``bench/apps/sssp.py``) and its two readers.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_sssp_app.py

On a small weighted graph with duplicate arcs of different weights, self
loops, a weight of exactly 0, a second component and an isolated vertex:
the reference equals a plain heapq Dijkstra, ``work_edges`` the edges of
each root's component counted by hand, the roots are distinct, seeded and
never an isolated or self-loop-only vertex, and the program's searches
through a session match the reference.
"""
from __future__ import annotations

import heapq
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import oracle  # noqa: E402
import run  # noqa: E402

SSSP = run.app_runner("sssp")
N = 8
# undirected edges (u, v, weight): 0-1 twice with different weights, a
# 0.0 weight, a self loop on a reached vertex, vertex 4 with only a self
# loop, a second component {5, 6}, and vertex 7 isolated
EDGES = [(0, 1, 0.5), (1, 0, 0.25), (1, 2, 0.0), (2, 3, 0.75), (0, 3, 1.5),
         (3, 3, 0.0), (4, 4, 0.125), (5, 6, 0.375)]
COMPONENT_EDGES = {0: 6, 1: 6, 2: 6, 3: 6, 5: 1, 6: 1}  # counted by hand
PROPER = sorted(COMPONENT_EDGES)


def _graph() -> oracle.PullGraph:
    u, v, w = (np.array(c) for c in zip(*EDGES))
    src = np.concatenate([u, v]).astype(np.int32)
    dst = np.concatenate([v, u]).astype(np.int32)
    return oracle.PullGraph(src, dst, N, np.concatenate([w, w]).astype(
        np.float32))


def _app(graph, roots=64, seed=11, session=None):
    return SSSP({"roots": roots}, session, seed,
                np.bincount(graph.src, minlength=N))


def _dijkstra(graph, root) -> np.ndarray:
    out = [[] for _ in range(graph.n)]
    for u, v, w in zip(graph.src, graph.dst, graph.weights):
        out[u].append((int(v), float(w)))
    dist = np.full(graph.n, np.inf)
    dist[root] = 0.0
    heap = [(0.0, root)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for v, w in out[u]:
            if d + w < dist[v]:
                dist[v] = d + w
                heapq.heappush(heap, (d + w, v))
    return dist


def test_roots_are_distinct_seeded_and_have_another_neighbour():
    g = _graph()
    roots = _app(g).roots(g)
    assert sorted(roots) == PROPER  # 64 asked, six vertices qualify
    assert _app(g).roots(g) == roots
    assert _app(g, seed=12).roots(g) != roots
    three = _app(g, roots=3).roots(g)
    assert len(set(three)) == 3 and set(three) <= set(PROPER)
    with pytest.raises(ValueError, match="edge list"):
        _app(g).roots()


def test_reference_is_dijkstra_on_the_raw_arcs():
    g = _graph()
    app = _app(g)
    roots = app.roots(g)
    got = app.reference(g, len(roots))
    want = np.stack([_dijkstra(g, r) for r in roots], 1)
    np.testing.assert_array_equal(got, want)
    assert got[1, roots.index(0)] == 0.25  # the lighter duplicate
    assert got[2, roots.index(1)] == 0.0   # a zero weight is an edge
    assert np.isinf(got[4]).all() and np.isinf(got[7]).all()
    # cycling past the last root
    assert app.reference(g, len(roots) + 1)[:, -1].tolist() == \
        want[:, 0].tolist()
    rounded = app.reference(g, len(roots), oracle.bf16_rounding)
    np.testing.assert_array_equal(rounded, oracle.bf16_rounding(want))


def test_work_edges_count_each_component_edge_once():
    g = _graph()
    app = _app(g)
    roots = app.roots(g)
    assert app.work_edges(g, len(roots)) == sum(COMPONENT_EDGES[r]
                                                for r in roots)
    assert app.work_edges(g, 1) == COMPONENT_EDGES[roots[0]]


def test_searches_through_a_session_match_the_reference(tmp_path):
    from repro.graph.preprocess import preprocess_graph
    from repro.session import GraphSession

    g = _graph()
    oracle.write_edge_list(tmp_path / "edges", g.src, g.dst, N, g.weights)
    preprocess_graph(str(tmp_path / "edges"), str(tmp_path / "graph"),
                     threshold_edge_num=4)
    with GraphSession(str(tmp_path / "graph")) as session:
        app = _app(g, session=session)
        # the store's shards and the edge list agree on the roots
        assert app.roots() == _app(g).roots(g)
        app.call(2)  # warm-up takes the first two roots
        values, units, history, seconds = app.call(3)
    assert units == 3 and len(seconds) == 3 and len(history) >= 3
    assert app._window_roots(g, 3) == app.roots()[2:5]
    np.testing.assert_array_equal(values, app.reference(g, 3))


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def _run(history, units) -> "run.Run":
    return run.Run(setup_s=1.0, window_s=0.01, sweeps=len(history),
                   units=units, work_edges=1000, columns=1, num_edges=1000,
                   num_vertices=100, edge_value=True, history=list(history),
                   cache_delta={},
                   peaks={"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12})


def _sweep(processed, skipped):
    return SimpleNamespace(shards_processed=processed, shards_skipped=skipped)


def test_sweeps_per_search_reads_sweeps_over_searches():
    read = run.metric_reader("sweeps_per_search")
    assert read(_run([_sweep(4, 0)] * 7, units=2)) == pytest.approx(3.5)
    assert read(_run([], units=0)) is None


def test_shards_skipped_share_reads_skipped_over_scheduled():
    read = run.metric_reader("shards_skipped_share")
    history = [_sweep(4, 0), _sweep(1, 3), _sweep(2, 2)]
    assert read(_run(history, units=1)) == pytest.approx(100 * 5 / 12)
    assert read(_run([_sweep(4, 0)], units=1)) == 0.0
    assert read(_run([], units=0)) is None
