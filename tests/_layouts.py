"""Random sliced-ELL shards for the kernel tests (see repro.core.shards)."""
import numpy as np

from repro.core.shards import CSRShard, csr_to_ell


def random_shard(rng, n: int, rows: int, edges: int, *, cap: int = 64,
                 lane: int = 128, dst=None):
    """An ELLShard of ``edges`` arcs from sources in [0, n) into ``rows``
    destinations (skewed in-degrees unless ``dst`` names them), with
    float32 edge values in [0, 1)."""
    if dst is None:
        dst = (rng.pareto(1.2, edges) * rows / 20).astype(np.int64) % rows
    dst = np.asarray(dst, dtype=np.int64)
    src = rng.integers(0, n, dst.size)
    val = rng.random(dst.size).astype(np.float32)
    order = np.argsort(dst, kind="stable")
    counts = np.bincount(dst, minlength=rows)
    csr = CSRShard(0, 0, rows, np.concatenate([[0], np.cumsum(counts)]),
                   src[order].astype(np.int32), val[order])
    return csr_to_ell(csr, max_width=cap, lane=lane)


def random_layout(rng, n: int, rows: int, edges: int, **kw):
    """(cols, vals, slices, row_map) of a :func:`random_shard` — the
    arguments ``ell_spmv`` takes after the sources."""
    s = random_shard(rng, n, rows, edges, **kw)
    return s.cols, s.vals, s.group_slices(), s.row_map
