"""``app: pagerank``: global PageRank (K=1) and personalised PageRank (K>1).

A unit of work is one sweep over every arc, so ``work_edges`` is the
generated arcs × columns × sweeps.  The reference is pull-mode PageRank in
float64 over the edge list the benchmark wrote:

    r_0 = 1/n everywhere            (personalised: all mass on the seed)
    r_t = reset + d * sum over in-edges (u, v) of r_{t-1}[u] / outdeg(u)

with ``reset = (1 - d)/n`` (personalised: ``1 - d`` on the seed, 0
elsewhere); mass on vertices without out-edges is dropped, as the engine
does.  ``rounding`` rounds the stored vertex values after each step: the
lower-precision control passes bfloat16 rounding there.

The mix's ``tol`` goes to the program, which counts a vertex as changed
while ``|new - old| > tol * |old|``.  At ``tol: -1`` every vertex that
holds rank counts as changed every sweep: PageRank of a fixed number of
sweeps (as LDBC Graphalytics runs it).  Global PageRank, where every
vertex holds at least ``(1 - d)/n``, then steps every shard every sweep
and never stops early, so a window of M units is M full sweeps whatever
the seed; personalised PageRank skips only shards its mass has not
reached.  A positive ``tol`` lets the converging tail skip shards and
stop, so the work of a window would move with M and the seed.
"""
from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

import oracle


class PageRankMix:
    """``app: pagerank``: K=1 is the global PageRank through
    ``session.run``; K>1 is personalised PageRank through ``run_batch`` over
    K distinct seed vertices with out-edges (``out_deg``, counted from the
    generated arcs), drawn from ``--seed``."""

    edge_value = False  # plus_src reads no edge value

    def __init__(self, mix: dict, session, seed: int, out_deg: np.ndarray):
        self.session = session
        self.columns = int(mix["columns"])
        self.damping = float(mix["damping"])
        self.tol = float(mix["tol"])
        self.seeds = None
        if self.columns > 1:
            pool = np.flatnonzero(out_deg > 0)
            rng = np.random.default_rng(seed)
            self.seeds = [int(v) for v in
                          rng.choice(pool, size=self.columns, replace=False)]

    def call(self, sweeps: int):
        """One call of ``sweeps`` sweeps: ([n, K] values, sweeps run,
        per-sweep IterationStats, per-sweep seconds)."""
        s = self.session
        if self.seeds is None:
            r = s.run("pagerank", damping=self.damping, tol=self.tol,
                      max_iters=sweeps)
            values = r.values[:, None]
        else:
            s.run_batch("pagerank", sources=self.seeds, damping=self.damping,
                        tol=self.tol, max_iters=sweeps)
            r = s.last_batch_result
            values = r.values
        return values, r.iterations, r.history, [h.seconds for h in r.history]

    def reference(self, graph: oracle.PullGraph, sweeps: int,
                  rounding=None) -> np.ndarray:
        return pagerank(graph, sweeps, self.damping, self.seeds, rounding)

    def work_edges(self, graph: oracle.PullGraph, sweeps: int) -> int:
        return graph.num_arcs * self.columns * sweeps


App = PageRankMix


def pagerank(g: oracle.PullGraph, iters: int, damping: float,
             seeds: Sequence[int] | None = None,
             rounding: Callable[[np.ndarray], np.ndarray] | None = None
             ) -> np.ndarray:
    """``[n, K]`` float64: global PageRank (``seeds=None``, K=1) or one
    personalised column per seed, after ``iters`` steps."""
    rnd = rounding or (lambda a: a)
    n = g.n
    if seeds is None:
        r = np.full((n, 1), 1.0 / n)
        reset: float | np.ndarray = (1.0 - damping) / n
    else:
        r = np.zeros((n, len(seeds)))
        r[np.asarray(seeds), np.arange(len(seeds))] = 1.0
        reset = r * (1.0 - damping)
    r = rnd(r)
    for _ in range(iters):
        x = rnd(r * g.inv_out[:, None])
        r = rnd(reset + damping * g.pull(x))
    return r
