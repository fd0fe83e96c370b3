"""Plain NumPy reference for the benchmark's answers, from the edge list alone.

It imports nothing of the program under test: it reads the edge list the
benchmark itself wrote (``edges_*.npy``, int64 ``[2, m]`` chunks listed in
``meta.json``) and iterates pull-mode PageRank in float64:

    r_0 = 1/n everywhere            (personalised: all mass on the seed)
    r_t = reset + d * sum over in-edges (u, v) of r_{t-1}[u] / outdeg(u)

with ``reset = (1 - d)/n`` (personalised: ``1 - d`` on the seed, 0
elsewhere); mass on vertices without out-edges is dropped, as the engine
does.  ``rounding`` rounds the stored vertex values after each step: the
lower-precision control passes bfloat16 rounding there.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Callable, Sequence

import ml_dtypes
import numpy as np
import scipy.sparse

# the smallest normal float32: a reference value below it compares
# absolutely, so an exact zero must be produced as an exact zero
_TINY = float(np.finfo(np.float32).tiny)


def write_edge_list(path: Path, src: np.ndarray, dst: np.ndarray, n: int,
                    chunk: int = 1 << 22) -> None:
    """The program's edge-list input format: int64 ``[2, m]`` npy chunks and
    a ``meta.json`` naming them."""
    path.mkdir(parents=True, exist_ok=True)
    files = []
    for i, lo in enumerate(range(0, src.size, chunk)):
        name = f"edges_{i:05d}.npy"
        np.save(path / name, np.stack([src[lo:lo + chunk],
                                       dst[lo:lo + chunk]]).astype(np.int64))
        files.append(name)
    meta = {"num_vertices": int(n), "num_edges": int(src.size),
            "files": files, "weighted": False}
    (path / "meta.json").write_text(json.dumps(meta))


def read_edge_list(path: Path) -> tuple[np.ndarray, np.ndarray, int]:
    """(src, dst, n) as written by ``write_edge_list``."""
    meta = json.loads((path / "meta.json").read_text())
    m = int(meta["num_edges"])
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    lo = 0
    for name in meta["files"]:
        arr = np.load(path / name)
        hi = lo + arr.shape[1]
        src[lo:hi], dst[lo:hi] = arr[0], arr[1]
        lo = hi
    if lo != m:
        raise ValueError(f"edge list holds {lo} edges, meta says {m}")
    return src, dst, int(meta["num_vertices"])


def bf16_rounding(a: np.ndarray) -> np.ndarray:
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


class PullGraph:
    """The in-edges as a sparse count matrix ``A[v, u]`` (duplicate edges
    counted), for pulling sums along them."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        self.n = n
        self.a = scipy.sparse.csr_matrix(
            (np.ones(src.size), (dst, src)), shape=(n, n))
        self.inv_out = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)

    def pull(self, x: np.ndarray) -> np.ndarray:
        """``[n, k]``: the sum of ``x[u]`` over the in-edges (u, v) of v."""
        return self.a @ x


def pagerank(g: PullGraph, iters: int, damping: float,
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


def rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """|got - want| / |want| for every vertex and column; where the
    reference is 0 (no path from a seed yet) the value must be 0 too, and a
    NaN reads as infinitely wrong."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"result shape {got.shape} != reference "
                         f"{want.shape}")
    err = np.abs(got - want) / np.maximum(np.abs(want), _TINY)
    return np.nan_to_num(err, nan=np.inf)
