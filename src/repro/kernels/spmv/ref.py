"""Pure-jnp oracles for the sliced-ELL semiring SpMV.

These are the correctness references the Pallas kernel is swept against
(tests/test_kernels_spmv.py) and the fallback path on backends without
Pallas support.  ``ell_spmv_ref`` / ``ell_spmv_batch_ref`` are the layout's
definition: every slot sends COMBINE(edge, source) to the destination its
virtual row maps to, with no grouping, so they also referee the grouped
fold and the slice combine the dispatch paths share.  ``ell_fold_ref``
reduces each row group with strided slices, independently of the kernel's
reshape-and-reduce body.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.semiring import SEMIRINGS, Semiring
from repro.core.shards import GROUP_ROWS


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
    parameters.  This is the *same* arithmetic the Pallas kernel applies
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
    """[K, L, C] gathered sources + [L, C] edge vals -> [K, L / GROUP_ROWS,
    C] group partials: group g of lane j reduces rows g*GROUP_ROWS ..
    (g+1)*GROUP_ROWS - 1, first row first.

    ``cols < 0`` marks padded slots (contribute the reduce identity).
    """
    sem = _as_semiring(semiring)
    contrib = sem.combine(vals[None], xg)
    contrib = jnp.where(cols[None] >= 0, contrib,
                        jnp.asarray(sem.identity, contrib.dtype))
    reduce = (jnp.add if sem.is_plus else
              jnp.maximum if sem.is_max else jnp.minimum)
    out = contrib[:, 0::GROUP_ROWS]
    for r in range(1, GROUP_ROWS):
        out = reduce(out, contrib[:, r::GROUP_ROWS])
    return out


def _segment(data, ids, num_segments: int, sem: Semiring):
    """The semiring's segment reduce over the leading axis; ids outside
    [0, num_segments) are dropped, empty segments hold the identity."""
    if sem.is_plus:
        return jax.ops.segment_sum(data, ids, num_segments=num_segments)
    if sem.is_max:
        return jax.ops.segment_max(data, ids, num_segments=num_segments)
    return jax.ops.segment_min(data, ids, num_segments=num_segments)


def segment_combine(partials: jnp.ndarray, row_map: jnp.ndarray,
                    num_segments: int, semiring: Semiring | str) -> jnp.ndarray:
    """Fold virtual rows into their destinations: [V] -> [num_segments]
    (a padding row's id -1 is dropped)."""
    return _segment(partials.reshape(-1), row_map, num_segments,
                    _as_semiring(semiring))


def segment_combine_batch(partials: jnp.ndarray, row_map: jnp.ndarray,
                          num_segments: int, semiring: Semiring | str) -> jnp.ndarray:
    """Batched virtual-row fold: [V, K] -> [num_segments, K].

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


def slice_combine(groups: jnp.ndarray, slices: jnp.ndarray,
                  row_map: jnp.ndarray, num_segments: int,
                  semiring: Semiring | str) -> jnp.ndarray:
    """[K, L / GROUP_ROWS, C] group partials -> [num_segments, K]: each
    slice reduces its groups (``slices``, ascending, a padding group's id S
    dropped), then each virtual row (slice s, lane j is virtual row s*C + j)
    folds into its destination through ``row_map``."""
    sem = _as_semiring(semiring)
    K, G, C = groups.shape
    S = row_map.shape[0] // C
    # one flat segment op for every column, as in segment_combine_batch
    col_base = jnp.arange(K, dtype=slices.dtype)[:, None] * S
    ids = jnp.where(slices[None, :] < S, slices[None, :] + col_base, K * S)
    per_slice = _segment(groups.reshape(K * G, C), ids.reshape(-1), K * S,
                         sem)                                # [K*S, C]
    return segment_combine_batch(per_slice.reshape(K, S * C).T, row_map,
                                 num_segments, sem)


def _slot_destinations(slices: jnp.ndarray, row_map: jnp.ndarray, C: int):
    """[L, C] local destination of every slot (-1 on padding)."""
    S = row_map.shape[0] // C
    row_slice = jnp.repeat(slices, GROUP_ROWS)               # [L]
    vrow = row_slice[:, None] * C + jnp.arange(C)[None, :]
    return jnp.where(row_slice[:, None] < S,
                     row_map[jnp.minimum(vrow, S * C - 1)], -1)


def ell_spmv_ref(x: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
                 slices: jnp.ndarray, row_map: jnp.ndarray, num_segments: int,
                 semiring: Semiring | str) -> jnp.ndarray:
    """Full shard update oracle: x [n]; cols/vals [L, C]; slices [L /
    GROUP_ROWS] slice of each row group; row_map [S*C] local destination
    per virtual row; -> [num_segments]."""
    return ell_spmv_batch_ref(x[:, None], cols, vals, slices, row_map,
                              num_segments, semiring)[:, 0]


def ell_spmv_batch_ref(x: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
                       slices: jnp.ndarray, row_map: jnp.ndarray,
                       num_segments: int,
                       semiring: Semiring | str) -> jnp.ndarray:
    """Batched shard update oracle: x is [n, K] -> [num_segments, K]; one
    segment reduce over every slot, straight to its destination."""
    sem = _as_semiring(semiring)
    mask = cols >= 0
    contrib = sem.combine(vals[..., None], x[jnp.where(mask, cols, 0)])
    contrib = jnp.where(mask[..., None], contrib,
                        jnp.asarray(sem.identity, contrib.dtype))
    dst = _slot_destinations(slices, row_map, cols.shape[1])
    dst = jnp.where(mask, dst, -1)
    K = x.shape[1]
    return _segment(contrib.reshape(-1, K), dst.reshape(-1), num_segments,
                    sem)
