"""SpMV kernel microbench: Pallas (interpret on CPU) vs pure-jnp reference.

On this container the Pallas kernels run in interpret mode, so wall-clock
favours the jnp path — the structural numbers that matter for the TPU target
are bytes-per-edge of the ELL layout and padding overhead, reported in the
derived column.  (On real TPU the same pallas_call compiles to fused VMEM
tiles; see kernels/spmv/spmv.py.)"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import get_store, row
from repro.core.shards import CSRShard, csr_to_ell, quantize_edge_vals
from repro.kernels.spmv.ops import describe_dispatch, ell_spmv, ell_spmv_batch

# roofline variant grid (ISSUE satellite: fp32/fp16/int8 × K ∈ {1, 16})
VARIANT_DTYPES = ("float32", "float16", "int8")
VARIANT_KS = (1, 16)
_R, _E, _N = 8192, 1 << 19, 1 << 15  # synthetic shard: rows, edges, sources


def _variant_problem(seed: int = 7):
    """A sliced-ELL shard of _E skewed arcs into _R rows, and sources."""
    rng = np.random.default_rng(seed)
    dst = np.sort((rng.pareto(1.2, _E) * _R / 20).astype(np.int64) % _R)
    csr = CSRShard(0, 0, _R, np.concatenate(
        [[0], np.cumsum(np.bincount(dst, minlength=_R))]),
        rng.integers(0, _N, _E).astype(np.int32),
        (rng.random(_E, dtype=np.float32) * 2.0 - 0.5).astype(np.float32))
    shard = csr_to_ell(csr)
    x = rng.random((_N, max(VARIANT_KS)), dtype=np.float32)
    return shard, x


def spmv_variants(use_pallas="auto", reps: int = 3) -> list[dict]:
    """Time one SpMV per (edge dtype × K) variant; return records for the
    roofline report.

    ``model_bytes`` is the minimum HBM traffic of the path actually taken
    (``describe_dispatch``): edge arrays once (cols int32 + vals at their
    *stored* dtype — the quantization win), sources once, partials out, plus
    the gathered [K, L, C] matrix every path materializes (one write + one
    read).  Achieved bandwidth = model_bytes / seconds, an
    *upper bound* on usefully-moved bytes — honest for compiled backends,
    pessimistic in interpret mode (which is why the report prints the path).
    """
    shard, x_np = _variant_problem()
    cols_np = shard.cols
    cols = jnp.asarray(cols_np)
    layout = (jnp.asarray(shard.group_slices()), jnp.asarray(shard.row_map),
              _R)
    out = []
    for dtype in VARIANT_DTYPES:
        q, scale, zero = quantize_edge_vals(shard.vals, dtype)
        vals = jnp.asarray(q)
        qp = jnp.asarray([scale, zero], jnp.float32)
        for k in VARIANT_KS:
            if k == 1:
                x = jnp.asarray(x_np[:, 0])
                f = lambda: ell_spmv(x, cols, vals, *layout, "min_plus",
                                     use_pallas=use_pallas, qparams=qp)
            else:
                x = jnp.asarray(x_np[:, :k])
                f = lambda: ell_spmv_batch(x, cols, vals, *layout,
                                           "min_plus", use_pallas=use_pallas,
                                           qparams=qp)
            path = describe_dispatch(use_pallas, k=k)
            jax.block_until_ready(f())  # compile
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(f())
            dt = (time.perf_counter() - t0) / reps
            model_bytes = (cols_np.nbytes + q.nbytes        # edge pass
                           + _N * k * 4 + _R * k * 4        # sources + out
                           + 2 * cols_np.size * k * 4)      # gathered matrix
            out.append(dict(dtype=dtype, k=k, seconds=dt,
                            model_bytes=model_bytes, path=path))
    return out


def run() -> list[str]:
    out = []
    store = get_store()
    shard = store.read_shard(0)
    n = store.num_vertices
    x = jnp.asarray(np.random.default_rng(0).random(n).astype(np.float32))
    cols, vals = jnp.asarray(shard.cols), jnp.asarray(shard.vals)
    layout = (jnp.asarray(shard.group_slices()), jnp.asarray(shard.row_map),
              shard.end_vertex - shard.start_vertex)
    for use, tag in ((False, "jnp_ref"), (True, "pallas_interpret")):
        f = lambda: ell_spmv(x, cols, vals, *layout, "plus_src",
                             use_pallas=use)
        jax.block_until_ready(f())  # compile
        t0 = time.perf_counter()
        reps = 20 if not use else 3
        for _ in range(reps):
            jax.block_until_ready(f())
        dt = (time.perf_counter() - t0) / reps
        eps = shard.nnz / dt
        out.append(row(f"kernel_spmv_{tag}", dt * 1e6,
                       f"edges_per_s={eps/1e6:.0f}M"))
    fill = shard.nnz / (shard.shape[0] * shard.shape[1])
    out.append(row("kernel_spmv_ell_layout", 0.0,
                   f"L={shard.shape[0]};C={shard.shape[1]};fill={fill:.2f};"
                   f"bytes_per_edge={shard.padded_bytes()/max(shard.nnz,1):.1f}"))
    return out
