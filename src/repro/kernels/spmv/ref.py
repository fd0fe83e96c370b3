"""Pure-jnp oracles for the blocked-ELL semiring SpMV kernels.

These are the correctness references the Pallas kernels are swept against
(tests/test_kernels_spmv.py) and the fallback path on backends without
Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.semiring import SEMIRINGS, Semiring


def _as_semiring(s: Semiring | str) -> Semiring:
    return SEMIRINGS[s] if isinstance(s, str) else s


# Edge-value storage dtypes that carry affine qparams (see
# repro.core.shards.quantize_edge_vals).  bfloat16 et al. pass through: only
# these two dtypes are produced by the quantizer and carry scale/zero.
QUANTIZED_DTYPES = (jnp.int8, jnp.float16)


def maybe_dequantize(vals: jnp.ndarray, qparams: jnp.ndarray | None) -> jnp.ndarray:
    """Dequantize int8/float16 edge values to float32 with the canonical
    affine formula ``(q - zero) * scale``; other dtypes pass through.

    ``qparams`` is a [2] float32 array (scale, zero); ``None`` means identity
    parameters.  This is the *same* arithmetic the Pallas kernels apply
    in-VMEM, so the jnp fallback and the kernel agree bitwise.
    """
    if vals.dtype not in QUANTIZED_DTYPES:
        return vals
    if qparams is None:
        return vals.astype(jnp.float32)
    qp = qparams.astype(jnp.float32)
    # NOTE: backends may contract this multiply into an FMA with a following
    # semiring add (min_plus's `w + s`), which single-rounds.  All dispatch
    # paths contract identically — they stay bitwise-equal to each other —
    # but can sit 1 ulp from a dequantize-then-combine oracle.
    return (vals.astype(jnp.float32) - qp[1]) * qp[0]


def ell_fold_ref(xg: jnp.ndarray, vals: jnp.ndarray, cols: jnp.ndarray,
                 semiring: Semiring | str) -> jnp.ndarray:
    """[R, W] gathered sources + edge vals -> [R, 1] per-ELL-row partials.

    ``cols < 0`` marks padded slots (contribute the reduce identity).
    """
    sem = _as_semiring(semiring)
    mask = cols >= 0
    return sem.fold(vals, xg, mask, axis=-1)[:, None]


def ell_gather_fold_ref(x_blk: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
                        semiring: Semiring | str) -> jnp.ndarray:
    """2-D-tiled variant: cols index a small *local* source block x_blk [VB]."""
    sem = _as_semiring(semiring)
    mask = cols >= 0
    xg = x_blk[jnp.where(mask, cols, 0)]
    return sem.fold(vals, xg, mask, axis=-1)[:, None]


def ell_fold_batch_ref(xg: jnp.ndarray, vals: jnp.ndarray, cols: jnp.ndarray,
                       semiring: Semiring | str) -> jnp.ndarray:
    """Batched fold: [K, R, W] gathered sources + shared [R, W] edges -> [R, K].

    Sources arrive column-major (the Pallas kernel's layout): the [R, W]
    edge tile broadcasts along the leading column dim, so each column is
    computed exactly as the single-column fold computes it — backends that
    contract a dequantize-multiply into the semiring add do so identically
    for K = 1 and K > 1.  ``cols < 0`` slots contribute the reduce identity
    in every column.
    """
    sem = _as_semiring(semiring)
    return sem.fold(vals, xg, cols >= 0, axis=-1).T


def segment_combine(partials: jnp.ndarray, row_map: jnp.ndarray,
                    num_segments: int, semiring: Semiring | str) -> jnp.ndarray:
    """Fold wrapped ELL rows of the same destination: [R] -> [num_segments]."""
    sem = _as_semiring(semiring)
    p = partials.reshape(-1)
    if sem.is_plus:
        return jax.ops.segment_sum(p, row_map, num_segments=num_segments)
    if sem.is_max:
        return jax.ops.segment_max(p, row_map, num_segments=num_segments)
    return jax.ops.segment_min(p, row_map, num_segments=num_segments)


def segment_combine_batch(partials: jnp.ndarray, row_map: jnp.ndarray,
                          num_segments: int, semiring: Semiring | str) -> jnp.ndarray:
    """Batched wrapped-row fold: [R, K] -> [num_segments, K].

    Every column folds in ONE 1-D segment op over a flattened
    [K * num_segments] id space (column k owns ids [k*S, (k+1)*S)): XLA:TPU
    compiles the equivalent 2-D windowed scatter ~50x slower (~12 s per
    shard shape).  Ids outside [0, num_segments) are dropped, as the 2-D
    form drops them, instead of spilling into the next column's range.
    """
    R, K = partials.shape
    in_range = (row_map >= 0) & (row_map < num_segments)
    col_base = jnp.arange(K, dtype=row_map.dtype)[:, None] * num_segments
    ids = jnp.where(in_range[None, :], row_map[None, :] + col_base,
                    K * num_segments)
    flat = segment_combine(partials.T, ids.reshape(-1), K * num_segments,
                           semiring)
    return flat.reshape(K, num_segments).T


def ell_spmv_ref(x: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
                 row_map: jnp.ndarray, num_segments: int,
                 semiring: Semiring | str) -> jnp.ndarray:
    """Full shard update oracle: gather + fold + segment-combine.

    x: [n] resident source values; cols/vals: [R, W] blocked-ELL;
    row_map: [R] local destination row per ELL row; -> [num_segments].
    """
    mask = cols >= 0
    xg = x[jnp.where(mask, cols, 0)]
    partials = ell_fold_ref(xg, vals, cols, semiring)
    return segment_combine(partials, row_map, num_segments, semiring)


def ell_spmv_batch_ref(x: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
                       row_map: jnp.ndarray, num_segments: int,
                       semiring: Semiring | str) -> jnp.ndarray:
    """Batched shard update oracle: x is [n, K] -> [num_segments, K]."""
    xg = x.T[:, jnp.where(cols >= 0, cols, 0)]   # [K, R, W]
    partials = ell_fold_batch_ref(xg, vals, cols, semiring)
    return segment_combine_batch(partials, row_map, num_segments, semiring)
