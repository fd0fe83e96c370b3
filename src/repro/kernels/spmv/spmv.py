"""Pallas TPU kernel for the sliced-ELL semiring SpMV (the paper's hot loop).

GraphMP's per-shard update — "pull source values, combine along in-edges,
reduce per destination" — is the compute hot-spot of the whole system.  On
TPU a shard is a sliced ELL with one virtual row per lane
(``repro.core.shards.ELLShard``): a slice's rows run down the sublanes, so
folding a virtual row is a reduction along the sublane axis, lane by lane.
Sources are always pre-gathered by XLA (the HBM gather is XLA-native; an
in-kernel gather from VMEM does not lower on TPU Mosaic), and the kernel
folds the gathered tiles:

  * ``ell_fold_pallas`` — K frontiers against one edge tile.  The gathered
    sources arrive column-major, [K, L, C]; a grid step takes one column
    of a [tb, C] row tile (the column axis innermost, so the edge tile
    stays put while the columns pass), masks sentinel slots to the
    identity and reduces every group of ``GROUP_ROWS`` rows per lane,
    giving [K, L / GROUP_ROWS, C] group partials.  A group never straddles
    two slices, so the caller finishes a slice with a segment reduce over
    its groups.  Every column runs the K=1 body, so a batched column
    equals its K=1 fold bit for bit, and every K compiles the same kernel
    body.

Edge values may arrive quantized (int8/float16, see
``repro.core.shards.quantize_edge_vals``); the kernel dequantizes them
in-VMEM from a (1, 2) float32 (scale, zero) qparams block, so HBM traffic
for edge values is the *quantized* byte count.

The kernel is validated in interpret mode against `ref.py` over
shape/dtype/semiring sweeps (tests/test_kernels_spmv.py), and compiled for
a described TPU v5e at real shapes (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.semiring import SEMIRINGS, Semiring
from repro.core.shards import GROUP_ROWS, ROW_ALIGN

# Row tiles tried, largest first; each is a multiple of ROW_ALIGN, which
# every ELL row count is, so one of them divides it.  A [512, 128] float32
# tile is 256 KiB of VMEM.
ROW_TILES = tuple(t for t in (512, 256, 128, 64, 32) if t % ROW_ALIGN == 0)

# Edge-value dtypes that carry affine qparams (scale, zero).  bfloat16 and
# other float dtypes pass through the semiring untouched.
QUANTIZED_DTYPES = (jnp.int8, jnp.float16)


def _as_semiring(s: Semiring | str) -> Semiring:
    return SEMIRINGS[s] if isinstance(s, str) else s


def _is_quantized(vals) -> bool:
    return vals.dtype in QUANTIZED_DTYPES


def _qparams_2d(qparams) -> jnp.ndarray:
    """Canonical (1, 2) float32 (scale, zero) block for the kernel."""
    if qparams is None:
        qparams = jnp.asarray([1.0, 0.0], jnp.float32)
    return jnp.asarray(qparams, jnp.float32).reshape(1, 2)


def _edge_tile(vals_ref, qp_ref):
    """Edge-value tile, dequantized in-VMEM when a qparams block is present.

    The affine formula matches ``ref.maybe_dequantize`` exactly so the jnp
    fallback and the kernel agree bitwise.
    """
    if qp_ref is None:
        return vals_ref[...]
    return (vals_ref[...].astype(jnp.float32) - qp_ref[0, 1]) * qp_ref[0, 0]


def fold_groups(sem: Semiring, vals, xg, cols):
    """[tb, C] edges × [k, tb, C] gathered sources -> [k, tb / GROUP_ROWS,
    C] partials: the semiring reduce of each lane over each group of rows,
    sentinel slots (``cols < 0``) contributing the identity."""
    contrib = sem.combine(vals[None], xg)
    contrib = jnp.where(cols[None] >= 0, contrib,
                        jnp.asarray(sem.identity, contrib.dtype))
    k, tb, c = contrib.shape
    groups = contrib.reshape(k, tb // GROUP_ROWS, GROUP_ROWS, c)
    if sem.is_plus:
        return jnp.sum(groups, axis=2)
    if sem.is_max:
        return jnp.max(groups, axis=2)
    return jnp.min(groups, axis=2)


def _row_tile(L: int) -> int:
    """The largest row tile that divides L, so that no block is partial."""
    for tb in ROW_TILES:
        if L % tb == 0:
            return tb
    raise ValueError(f"ELL row count {L} is not a multiple of {ROW_ALIGN}; "
                     f"lay shards out with csr_to_ell")


def _split_qp(rest):
    """Kernel arg unpacking: rest is (out_ref,) or (qp_ref, out_ref)."""
    if len(rest) == 2:
        return rest[0], rest[1]
    return None, rest[0]


def _ell_fold_kernel(xg_ref, vals_ref, cols_ref, *rest, sem: Semiring):
    qp_ref, out_ref = _split_qp(rest)
    out_ref[...] = fold_groups(sem, _edge_tile(vals_ref, qp_ref),
                               xg_ref[...], cols_ref[...])


@functools.partial(jax.jit, static_argnames=("semiring", "interpret"))
def ell_fold_pallas(xg: jnp.ndarray, vals: jnp.ndarray, cols: jnp.ndarray,
                    semiring: str, interpret: bool = True,
                    qparams=None) -> jnp.ndarray:
    """[K, L, C] gathered sources + [L, C] edges -> [K, L / GROUP_ROWS, C]
    group partials.  Grid is (L/tb, K), one column a step; the blocks
    never overlap."""
    sem = _as_semiring(semiring)
    K, L, C = xg.shape
    tb = _row_tile(L)
    in_specs = [
        pl.BlockSpec((1, tb, C), lambda i, c: (c, i, 0)),
        pl.BlockSpec((tb, C), lambda i, c: (i, 0)),
        pl.BlockSpec((tb, C), lambda i, c: (i, 0)),
    ]
    args = [xg, vals, cols]
    if _is_quantized(vals):
        in_specs.append(pl.BlockSpec((1, 2), lambda i, c: (0, 0)))
        args.append(_qparams_2d(qparams))
    return pl.pallas_call(
        functools.partial(_ell_fold_kernel, sem=sem),
        grid=(L // tb, K),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tb // GROUP_ROWS, C),
                               lambda i, c: (c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, L // GROUP_ROWS, C), xg.dtype),
        interpret=interpret,
    )(*args)
