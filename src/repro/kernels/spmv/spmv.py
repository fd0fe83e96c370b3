"""Pallas TPU kernels for blocked-ELL semiring SpMV (the paper's hot loop).

GraphMP's per-shard update — "pull source values, combine along in-edges,
reduce per destination" — is the compute hot-spot of the whole system.  On
TPU we lay shards out as blocked-ELL (DESIGN.md §2/§4) and fuse
mask→combine→reduce in VMEM.  Sources are always pre-gathered by XLA (the
HBM gather is XLA-native; an in-kernel gather from VMEM does not lower on
TPU Mosaic), and the kernels fold the gathered tiles:

  * ``ell_fold_pallas``        — [R, W] tiles to [R, 1] partials.  Grid is
    (rows/TR, W/TW) with sequential accumulation over the W axis into the
    revisited output block (identity-init at the first W step).
  * ``ell_fold_batch_pallas``  — K frontiers against one edge tile.  The
    gathered sources arrive column-major, [K, R, W], so each column is a
    plain [tr, tw] tile: the kernel loads the edge tile ONCE per block and
    runs the single-column fold on every column of the block.  No edge tile
    is ever broadcast to a new rank (Mosaic cannot lay that out), and each
    column's reduction is the K=1 kernel's reduction, tile for tile.

Edge values may arrive quantized (int8/float16, see
``repro.core.shards.quantize_edge_vals``); every kernel dequantizes them
in-VMEM from a (1, 2) float32 (scale, zero) qparams block, so HBM traffic
for edge values is the *quantized* byte count.

All kernels are validated in interpret mode against `ref.py` over
shape/dtype/semiring sweeps (tests/test_kernels_spmv.py), and compiled for
a described TPU v5e at real widths (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.semiring import SEMIRINGS, Semiring
from repro.core.shards import LANE, SUBLANE

DEFAULT_TR = 256  # row-tile (multiple of 8 sublanes)
DEFAULT_TW = 512  # width-tile (multiple of 128 lanes)

# VMEM budget for the gathered-source block of the batched kernel: the
# [tk, tr, tw] block is the largest resident array, so (tr, tk) shrink until
# it fits (TPU cores have ~16 MB VMEM; 2 MB leaves room for edges + output
# under double buffering).
TILE_BYTES_BUDGET = 2 << 20

# Smallest row tile the batched kernel shrinks to: int8 edge tiles are laid
# out in (32, 128) VMEM tiles, so 32 rows keeps every edge dtype aligned.
MIN_BATCH_TR = 32

# Edge-value dtypes that carry affine qparams (scale, zero).  bfloat16 and
# other float dtypes pass through the semiring untouched.
QUANTIZED_DTYPES = (jnp.int8, jnp.float16)


def _as_semiring(s: Semiring | str) -> Semiring:
    return SEMIRINGS[s] if isinstance(s, str) else s


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_block_bytes(shape, itemsize: int = 4) -> int:
    """Actual VMEM footprint of a block of the given shape.

    VMEM lays blocks out in (8 sublane, 128 lane) tiles over the two minor
    dims, so both are padded up: a [tr, 1] block really occupies tr * 128
    elements, not tr.  Every byte budget in this module must be compared
    against this padded size.
    """
    dims = list(shape)
    if len(dims) >= 1:
        dims[-1] = _round_up(dims[-1], LANE)
    if len(dims) >= 2:
        dims[-2] = _round_up(dims[-2], SUBLANE)
    total = itemsize
    for d in dims:
        total *= d
    return total


def _is_quantized(vals) -> bool:
    return vals.dtype in QUANTIZED_DTYPES


def _qparams_2d(qparams) -> jnp.ndarray:
    """Canonical (1, 2) float32 (scale, zero) block for the kernels."""
    if qparams is None:
        qparams = jnp.asarray([1.0, 0.0], jnp.float32)
    return jnp.asarray(qparams, jnp.float32).reshape(1, 2)


def _edge_tile(vals_ref, qp_ref):
    """Edge-value tile, dequantized in-VMEM when a qparams block is present.

    The affine formula matches ``ref.maybe_dequantize`` exactly so the jnp
    fallback and the kernels agree bitwise.
    """
    if qp_ref is None:
        return vals_ref[...]
    return (vals_ref[...].astype(jnp.float32) - qp_ref[0, 1]) * qp_ref[0, 0]


def _fold_tile(sem: Semiring, vals, xg, cols):
    """[tr, tw] edges × [(tk,) tr, tw] gathered sources -> [(tk,) tr, 1] partials."""
    mask = cols >= 0
    contrib = sem.combine(vals, xg)
    contrib = jnp.where(mask, contrib, jnp.asarray(sem.identity, contrib.dtype))
    if sem.is_plus:
        return jnp.sum(contrib, axis=-1, keepdims=True)
    if sem.is_max:
        return jnp.max(contrib, axis=-1, keepdims=True)
    return jnp.min(contrib, axis=-1, keepdims=True)


def _batch_tiles(R: int, W: int, K: int, itemsize: int = 4) -> tuple[int, int, int]:
    """(tk, tr, tw) such that the [tk, tr, tw] source block fits the budget.

    ``tw`` is the single-column kernel's width tile, never shrunk: every
    column then reduces over exactly the lanes ``ell_fold_pallas`` would,
    so a batched column equals its solo run bitwise.  Rows shrink first
    (rows are independent), then the column block.
    """
    tw = min(DEFAULT_TW, W)
    tr = min(DEFAULT_TR, R)
    tk = K
    floor_r = min(R, MIN_BATCH_TR)
    while vmem_block_bytes((tk, tr, tw), itemsize) > TILE_BYTES_BUDGET and tr > floor_r:
        tr = max(tr // 2, floor_r)
    while vmem_block_bytes((tk, tr, tw), itemsize) > TILE_BYTES_BUDGET and tk > 1:
        tk = -(-tk // 2)
    return tk, tr, tw


def _split_qp(rest):
    """Kernel arg unpacking: rest is (out_ref,) or (qp_ref, out_ref)."""
    if len(rest) == 2:
        return rest[0], rest[1]
    return None, rest[0]


def _ell_fold_kernel(xg_ref, vals_ref, cols_ref, *rest, sem: Semiring):
    qp_ref, out_ref = _split_qp(rest)
    w_step = pl.program_id(1)
    partial = _fold_tile(sem, _edge_tile(vals_ref, qp_ref), xg_ref[...],
                         cols_ref[...])

    @pl.when(w_step == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(w_step != 0)
    def _acc():
        out_ref[...] = sem.reduce(out_ref[...], partial)


@functools.partial(jax.jit, static_argnames=("semiring", "tr", "tw", "interpret"))
def ell_fold_pallas(xg: jnp.ndarray, vals: jnp.ndarray, cols: jnp.ndarray,
                    semiring: str, tr: int = DEFAULT_TR, tw: int = DEFAULT_TW,
                    interpret: bool = True, qparams=None) -> jnp.ndarray:
    """[R, W] -> [R, 1] per-row semiring partials (pre-gathered sources)."""
    sem = _as_semiring(semiring)
    R, W = xg.shape
    tr = min(tr, R)
    tw = min(tw, W)
    grid = (pl.cdiv(R, tr), pl.cdiv(W, tw))
    quant = _is_quantized(vals)
    in_specs = [
        pl.BlockSpec((tr, tw), lambda i, j: (i, j)),
        pl.BlockSpec((tr, tw), lambda i, j: (i, j)),
        pl.BlockSpec((tr, tw), lambda i, j: (i, j)),
    ]
    args = [xg, vals, cols]
    if quant:
        in_specs.append(pl.BlockSpec((1, 2), lambda i, j: (0, 0)))
        args.append(_qparams_2d(qparams))
    return pl.pallas_call(
        functools.partial(_ell_fold_kernel, sem=sem),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tr, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), xg.dtype),
        interpret=interpret,
    )(*args)


def _ell_fold_batch_kernel(xg_ref, vals_ref, cols_ref, *rest, sem: Semiring):
    qp_ref, out_ref = _split_qp(rest)
    w_step = pl.program_id(2)
    # the edge tile is loaded once and broadcast along the LEADING (column)
    # dim of the (tk, tr, tw) source block — the (sublane, lane) layout of
    # every [tr, tw] slice is untouched, so each column folds exactly as in
    # ell_fold_pallas
    partial = _fold_tile(sem, _edge_tile(vals_ref, qp_ref)[None],
                         xg_ref[...], cols_ref[...][None])

    @pl.when(w_step == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(w_step != 0)
    def _acc():
        out_ref[...] = sem.reduce(out_ref[...], partial)


@functools.partial(jax.jit, static_argnames=("semiring", "interpret"))
def ell_fold_batch_pallas(xg: jnp.ndarray, vals: jnp.ndarray, cols: jnp.ndarray,
                          semiring: str, interpret: bool = True,
                          qparams=None) -> jnp.ndarray:
    """Batched fold: [K, R, W] gathered sources + [R, W] edges -> [R, K].

    Grid is (K/TK, rows/TR, W/TW) with the W axis innermost-sequential,
    exactly like ``ell_fold_pallas``; tiles shrink (``_batch_tiles``) so the
    [tk, tr, tw] source block fits VMEM.
    """
    sem = _as_semiring(semiring)
    K, R, W = xg.shape
    tk, tr, tw = _batch_tiles(R, W, K, xg.dtype.itemsize)
    grid = (pl.cdiv(K, tk), pl.cdiv(R, tr), pl.cdiv(W, tw))
    quant = _is_quantized(vals)
    in_specs = [
        pl.BlockSpec((tk, tr, tw), lambda c, i, j: (c, i, j)),
        pl.BlockSpec((tr, tw), lambda c, i, j: (i, j)),
        pl.BlockSpec((tr, tw), lambda c, i, j: (i, j)),
    ]
    args = [xg, vals, cols]
    if quant:
        in_specs.append(pl.BlockSpec((1, 2), lambda c, i, j: (0, 0)))
        args.append(_qparams_2d(qparams))
    out = pl.pallas_call(
        functools.partial(_ell_fold_batch_kernel, sem=sem),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tk, tr, 1), lambda c, i, j: (c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, R, 1), xg.dtype),
        interpret=interpret,
    )(*args)
    return out[:, :, 0].T
