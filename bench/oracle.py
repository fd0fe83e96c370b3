"""Plain NumPy side of the benchmark's answer check, from the edge list alone.

It imports nothing of the program under test: it writes and reads the edge
list the benchmark itself makes (``edges_*.npy``, int64 ``[2, m]`` chunks,
and with edge weights ``weights_*.npy`` float32 beside each, listed in
``meta.json``), holds the graph for the applications' references
(``bench/apps``), and compares their answers with the program's.
"""
from __future__ import annotations

import json
from pathlib import Path

import ml_dtypes
import numpy as np
import scipy.sparse

# the smallest normal float32: a reference value below it compares
# absolutely, so an exact zero must be produced as an exact zero
_TINY = float(np.finfo(np.float32).tiny)


def write_edge_list(path: Path, src: np.ndarray, dst: np.ndarray, n: int,
                    weights: np.ndarray | None = None,
                    chunk: int = 1 << 22) -> None:
    """The program's edge-list input format: int64 ``[2, m]`` npy chunks,
    with ``weights`` a float32 ``weights_XXXXX.npy`` beside each, and a
    ``meta.json`` naming them."""
    path.mkdir(parents=True, exist_ok=True)
    files = []
    for i, lo in enumerate(range(0, src.size, chunk)):
        name = f"edges_{i:05d}.npy"
        np.save(path / name, np.stack([src[lo:lo + chunk],
                                       dst[lo:lo + chunk]]).astype(np.int64))
        if weights is not None:
            np.save(path / _weights_file(name),
                    weights[lo:lo + chunk].astype(np.float32))
        files.append(name)
    meta = {"num_vertices": int(n), "num_edges": int(src.size),
            "files": files, "weighted": weights is not None}
    (path / "meta.json").write_text(json.dumps(meta))


def read_edge_list(path: Path
                   ) -> tuple[np.ndarray, np.ndarray, int, np.ndarray | None]:
    """(src, dst, n, float32 weights or None) as written by
    ``write_edge_list``."""
    meta = json.loads((path / "meta.json").read_text())
    m = int(meta["num_edges"])
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    weights = np.empty(m, np.float32) if meta["weighted"] else None
    lo = 0
    for name in meta["files"]:
        arr = np.load(path / name)
        hi = lo + arr.shape[1]
        src[lo:hi], dst[lo:hi] = arr[0], arr[1]
        if weights is not None:
            weights[lo:hi] = np.load(path / _weights_file(name))
        lo = hi
    if lo != m:
        raise ValueError(f"edge list holds {lo} edges, meta says {m}")
    return src, dst, int(meta["num_vertices"]), weights


def _weights_file(edges_file: str) -> str:
    return edges_file.replace("edges_", "weights_")


def bf16_rounding(a: np.ndarray) -> np.ndarray:
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


class PullGraph:
    """The in-edges as a sparse count matrix ``A[v, u]`` (duplicate edges
    counted), for pulling sums along them; the arcs themselves, with their
    weights where the graph has them, for references that need more."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int,
                 weights: np.ndarray | None = None):
        self.n = n
        self.src, self.dst, self.weights = src, dst, weights
        self.num_arcs = int(src.size)
        self.a = scipy.sparse.csr_matrix(
            (np.ones(src.size), (dst, src)), shape=(n, n))
        self.inv_out = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)

    def pull(self, x: np.ndarray) -> np.ndarray:
        """``[n, k]``: the sum of ``x[u]`` over the in-edges (u, v) of v."""
        return self.a @ x


def rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """|got - want| / |want| for every vertex and column; where the
    reference is 0 (no path from a seed yet) the value must be 0 too, a
    reference +inf (a vertex no search reached) met by +inf is exact, and a
    NaN reads as infinitely wrong."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"result shape {got.shape} != reference "
                         f"{want.shape}")
    with np.errstate(invalid="ignore"):
        err = np.abs(got - want) / np.maximum(np.abs(want), _TINY)
    err[(want == np.inf) & (got == np.inf)] = 0.0
    return np.nan_to_num(err, nan=np.inf)
