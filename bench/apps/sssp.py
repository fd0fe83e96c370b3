"""``app: sssp``: Graph500 kernel 3, single-source shortest paths (K=1).

A unit of work is one search: ``session.run("sssp", source=root)`` through
the session's shared K=1 engine (one ``min_plus`` engine for every root,
``jit_signature=("sssp",)``), run to convergence, so its sweeps shrink to
the frontier and selective scheduling skips shards.

Roots: the mix's ``roots`` search keys, drawn without replacement from
``--seed`` among the vertices with an arc to another vertex (Graph500
draws 64 keys of nonzero degree, self loops not counted).  The draw walks
a seeded permutation of the vertices with arcs and keeps those whose
in-arcs in the preprocessed shards (kernel 1's output, where Graph500's
reference code checks a key's degree) come from another vertex; the graph
is undirected, so in-arcs and out-arcs agree.  Without a session (the
control) it checks the edge list instead.  Warm-up and window take the
roots in order, cycling only past the last.

``work_edges`` is Graph500's TEPS numerator: for each search, the
generated edges whose endpoints the search reached, each undirected edge
once (the arcs with both ends reached over 2; a self loop was written as
two arcs and counts once; duplicates count, as generated).

The reference is a float64 Dijkstra from each root
(``scipy.sparse.csgraph.dijkstra``) over the arcs with each (u, v) pair
reduced to its least weight and self loops dropped: a path only ever takes
the lightest of parallel arcs, and with non-negative weights a self loop
never shortens one.  A weight of exactly 0 stays an arc (an explicit entry
of the sparse matrix).  ``rounding`` rounds the final distances: the
lower-precision control.

Departure from a plain run to convergence: a search still changing after
``MAX_SWEEPS`` sweeps is cut there and reads NaN at every vertex, which
the answer check counts as infinitely wrong (so a fault that keeps a
search from converging reads as not correct, not as an error).  The
scale-20 searches take 27-29 sweeps.
"""
from __future__ import annotations

import time

import numpy as np
import scipy.sparse
import scipy.sparse.csgraph

import oracle

MAX_SWEEPS = 200  # the engine's default cap


class App:
    columns = 1
    edge_value = True  # min_plus adds the edge's weight

    def __init__(self, mix: dict, session, seed: int, out_deg: np.ndarray):
        self.session = session
        self.count = int(mix["roots"])
        order = np.random.default_rng(seed).permutation(
            np.flatnonzero(out_deg > 0))
        self._order = order
        self._roots: list[int] | None = None
        if session is not None:
            self._roots = _draw(order, self.count,
                                _store_has_other(session.store))
        self._next = 0       # the next root a call takes
        self._last = None    # the roots of the last call
        self._graph = None   # (graph, min-weight arc matrix) last built

    def roots(self, graph: oracle.PullGraph | None = None) -> list[int]:
        """The search keys, drawn on first use (from the edge list
        ``graph`` where the app has no session)."""
        if self._roots is None:
            if graph is None:
                raise ValueError("an sssp App without a session draws its "
                                 "roots from the edge list")
            other = np.bincount(graph.src[graph.src != graph.dst],
                                minlength=graph.n) > 0
            self._roots = _draw(self._order, self.count,
                                lambda v: bool(other[v]))
        return self._roots

    def call(self, units: int):
        """``units`` searches: ([n, units] distances, units, every sweep's
        IterationStats, each search's seconds)."""
        roots = self.roots()
        picked = [roots[(self._next + i) % len(roots)] for i in range(units)]
        self._next += units
        self._last = picked
        cols, history, seconds = [], [], []
        for root in picked:
            t = time.perf_counter()
            r = self.session.run("sssp", source=root, max_iters=MAX_SWEEPS)
            seconds.append(time.perf_counter() - t)
            cols.append(r.values if r.converged
                        else np.full(r.values.shape, np.nan, np.float32))
            history += r.history
        return np.stack(cols, 1), units, history, seconds

    def _window_roots(self, graph: oracle.PullGraph, units: int) -> list:
        """The roots of the last call, or the first ``units`` roots."""
        if self._last is not None:
            if len(self._last) != units:
                raise ValueError(f"the last call searched {len(self._last)} "
                                 f"roots, not {units}")
            return self._last
        roots = self.roots(graph)
        return [roots[i % len(roots)] for i in range(units)]

    def _arcs(self, graph: oracle.PullGraph) -> scipy.sparse.csr_matrix:
        if self._graph is None or self._graph[0] is not graph:
            self._graph = (graph, min_weight_arcs(graph))
        return self._graph[1]

    def reference(self, graph: oracle.PullGraph, units: int,
                  rounding=None) -> np.ndarray:
        """``[n, units]`` float64 distances, +inf where unreached."""
        dist = scipy.sparse.csgraph.dijkstra(
            self._arcs(graph), directed=True,
            indices=self._window_roots(graph, units)).T
        return rounding(dist) if rounding is not None else dist

    def work_edges(self, graph: oracle.PullGraph, units: int) -> int:
        arcs = self._arcs(graph)
        total = 0
        for root in self._window_roots(graph, units):
            reached = np.zeros(graph.n, bool)
            reached[scipy.sparse.csgraph.breadth_first_order(
                arcs, root, directed=True, return_predecessors=False)] = True
            total += int(np.count_nonzero(reached[graph.src]
                                          & reached[graph.dst])) // 2
        return total


def _draw(order: np.ndarray, count: int, has_other) -> list[int]:
    """The first ``count`` vertices of ``order`` with an arc to another
    vertex."""
    roots = []
    for v in order:
        if has_other(int(v)):
            roots.append(int(v))
            if len(roots) == count:
                break
    if not roots:
        raise ValueError("no vertex has an arc to another vertex")
    return roots


def _store_has_other(store):
    """``v -> bool``: whether ``v`` has an in-arc from another vertex in the
    store's shards (each shard read once, when first asked)."""
    bounds = np.asarray(store.intervals)
    shards = {}

    def has_other(v: int) -> bool:
        p = int(np.searchsorted(bounds, v, side="right")) - 1
        if p not in shards:
            shards[p] = store.read_shard(p)
        shard = shards[p]
        return bool(np.any(shard.neighbors(v - shard.start_vertex) != v))
    return has_other


def min_weight_arcs(graph: oracle.PullGraph) -> scipy.sparse.csr_matrix:
    """``[n, n]`` float64 CSR of the arcs u -> v, self loops dropped and
    each (u, v) pair at its least weight; zero weights stay entries."""
    n = graph.n
    keep = graph.src != graph.dst
    key = graph.src[keep].astype(np.int64) * n + graph.dst[keep]
    order = np.argsort(key, kind="stable")
    key = key[order]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    weight = np.minimum.reduceat(graph.weights[keep][order], first)
    key = key[first]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=indptr[1:])
    return scipy.sparse.csr_matrix(
        (weight.astype(np.float64), (key % n).astype(np.int32), indptr),
        shape=(n, n))
