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
from repro.core.shards import quantize_edge_vals
from repro.kernels.spmv.ops import describe_dispatch, ell_spmv, ell_spmv_batch

# roofline variant grid (ISSUE satellite: fp32/fp16/int8 × K ∈ {1, 16})
VARIANT_DTYPES = ("float32", "float16", "int8")
VARIANT_KS = (1, 16)
_R, _W, _N = 2048, 256, 1 << 15  # synthetic ELL problem, ~0.5M edge slots


def _variant_problem(seed: int = 7):
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, _N, (_R, _W)).astype(np.int32)
    cols[rng.random((_R, _W)) < 0.2] = -1  # ~20% padding, like a real shard
    vals = (rng.random((_R, _W), dtype=np.float32) * 2.0 - 0.5).astype(np.float32)
    row_map = np.arange(_R, dtype=np.int32)
    x = rng.random((_N, max(VARIANT_KS)), dtype=np.float32)
    return cols, vals, row_map, x


def spmv_variants(use_pallas="auto", reps: int = 3) -> list[dict]:
    """Time one SpMV per (edge dtype × K) variant; return records for the
    roofline report.

    ``model_bytes`` is the minimum HBM traffic of the path actually taken
    (``describe_dispatch``): edge arrays once (cols int32 + vals at their
    *stored* dtype — the quantization win), sources once, partials out, plus
    the gathered [K, R, W] matrix every path materializes (one write + one
    read).  Achieved bandwidth = model_bytes / seconds, an
    *upper bound* on usefully-moved bytes — honest for compiled backends,
    pessimistic in interpret mode (which is why the report prints the path).
    """
    cols_np, vals_np, row_map_np, x_np = _variant_problem()
    cols = jnp.asarray(cols_np)
    row_map = jnp.asarray(row_map_np)
    out = []
    for dtype in VARIANT_DTYPES:
        q, scale, zero = quantize_edge_vals(vals_np, dtype)
        vals = jnp.asarray(q)
        qp = jnp.asarray([scale, zero], jnp.float32)
        for k in VARIANT_KS:
            if k == 1:
                x = jnp.asarray(x_np[:, 0])
                f = lambda: ell_spmv(x, cols, vals, row_map, _R, "min_plus",
                                     use_pallas=use_pallas, qparams=qp)
            else:
                x = jnp.asarray(x_np[:, :k])
                f = lambda: ell_spmv_batch(x, cols, vals, row_map, _R,
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
                           + 2 * _R * _W * k * 4)           # gathered matrix
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
    rmap = jnp.asarray(shard.row_map)
    R = shard.shape[0]
    for use, tag in ((False, "jnp_ref"), (True, "pallas_interpret")):
        f = lambda: ell_spmv(x, cols, vals, rmap, R, "plus_src", use_pallas=use)
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
                   f"R={shard.shape[0]};W={shard.shape[1]};fill={fill:.2f};"
                   f"bytes_per_edge={shard.padded_bytes()/max(shard.nnz,1):.1f}"))
    return out
