"""jit'd public wrappers for the sliced-ELL SpMV kernel.

Dispatch is honest about the platform (``_resolve``): TPU compiles the
kernel; everything else runs it in interpret mode.  ``use_pallas`` selects
the family:

  * ``"auto"``  — fastest correct path per platform.  TPU compiles the
    Pallas fold for every K: XLA gathers the sources column-major, the fold
    kernel reduces each lane's row groups, and two XLA segment reduces
    finish the slices and the destinations.  On CPU the single-column path
    keeps Pallas in interpret mode (cheap enough, keeps the lowering
    exercised) but the BATCHED [n, K] fold takes pure jnp — interpret mode
    executes the grid step-by-step in Python with cost scaling in K, which
    would erase exactly the amortization ``run_batch``/GraphService exist
    for.  Non-CPU interpreting backends (gpu, until the kernel is ported)
    take jnp for every K: the jnp path is fully XLA-compiled there, while
    interpret mode would be step-by-step Python.  These demotions apply
    only when *interpreting*, never on TPU.
  * ``True``    — force Pallas (interpret off TPU; the A/B referee tests
    use this).
  * ``False``   — force the pure-jnp oracle path.

Quantized edge values (int8/float16 + affine qparams) are dequantized
in-kernel on the Pallas path and via the bit-identical
``ref.maybe_dequantize`` on the jnp path.  ``describe_dispatch`` reports the
path a given configuration takes (used by the benchmark and docs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.spmv import ref as _ref
from repro.kernels.spmv import spmv as _pallas

# Backends allowed to COMPILE the Pallas kernel; anything else interprets
# (or, under "auto", demotes to jnp — see _pick_path).  TPU-only on purpose:
# the kernel is written for, compiled for and tested on Mosaic TPU alone.
# Do not add a GPU backend here until it is ported to (and tested on) one.
_COMPILED_BACKENDS = ("tpu",)


def _resolve(use_pallas) -> tuple[bool, bool]:
    """-> (use_pallas, interpret), dispatching on the *actual* platform.

    ``use_pallas=False`` short-circuits to the jnp path (no dead interpret
    flag); otherwise interpret mode is everything off ``_COMPILED_BACKENDS``.
    """
    if not use_pallas:  # False
        return False, False
    return True, jax.default_backend() not in _COMPILED_BACKENDS


def _pick_path(use_pallas, k: int) -> tuple[bool, bool]:
    """-> (use the Pallas fold kernel?, interpret) for a K-column call.

    The spmv dispatch table (docs/ARCHITECTURE.md "Kernels"):
      * jnp    — use_pallas=False anywhere, or "auto" on an interpreting
        backend with K > 1 or off-CPU (interpret mode earns its keep only as
        the cheap single-column CPU referee; on GPU the jnp path is fully
        XLA-compiled while interpret mode is step-by-step Python).
      * pallas — everything else: XLA gather + the fold kernel; compiled
        on TPU, interpreted elsewhere.
    """
    use, interp = _resolve(use_pallas)
    if use and use_pallas == "auto" and interp \
            and (k > 1 or jax.default_backend() != "cpu"):
        use = False
    return use, interp and use


def describe_dispatch(use_pallas="auto", *, k: int = 1) -> str:
    """Human-readable path ``ell_spmv``/``ell_spmv_batch`` takes for K
    columns on this process's default backend: ``jnp`` |
    ``pallas:<mode>:gather+fold``."""
    use, interp = _pick_path(use_pallas, k)
    if not use:
        return "jnp"
    return f"pallas:{'interpret' if interp else 'compiled'}:gather+fold"


def _safe(cols):
    """Padded slots (cols < 0) gather row 0; the fold masks them out."""
    return jnp.where(cols >= 0, cols, 0)


@functools.partial(jax.jit, static_argnames=("semiring", "use_pallas"))
def ell_fold(xg, vals, cols, semiring: str, use_pallas="auto", qparams=None):
    """[K, L, C] gathered sources -> [K, L / GROUP_ROWS, C] group partials."""
    use, interp = _pick_path(use_pallas, xg.shape[0])
    if use:
        return _pallas.ell_fold_pallas(xg, vals, cols, semiring,
                                       interpret=interp, qparams=qparams)
    return _ref.ell_fold_ref(xg, _ref.maybe_dequantize(vals, qparams), cols,
                             semiring)


@functools.partial(jax.jit, static_argnames=("semiring", "use_pallas"))
def ell_gather_fold(x_blk, cols, vals, semiring: str, use_pallas="auto",
                    qparams=None):
    """2-D-tiled fold: cols index a local source block x_blk [VB]; ->
    [L / GROUP_ROWS, C] group partials."""
    return ell_fold(x_blk[_safe(cols)][None], vals, cols, semiring,
                    use_pallas=use_pallas, qparams=qparams)[0]


@functools.partial(jax.jit, static_argnames=("semiring", "num_segments", "use_pallas"))
def ell_spmv(x, cols, vals, slices, row_map, num_segments: int, semiring: str,
             use_pallas="auto", qparams=None):
    """Full shard update: gather + fold + slice combine.

    x: [n] resident source array; cols/vals: [L, C] sliced ELL; slices:
    [L / GROUP_ROWS] slice of each row group; row_map: [S*C] local
    destination per virtual row.  Returns [num_segments] partials for the
    shard's destination interval (identity where a row has no edges).
    """
    groups = ell_fold(x[_safe(cols)][None], vals, cols, semiring,
                      use_pallas=use_pallas, qparams=qparams)
    return _ref.slice_combine(groups, slices, row_map, num_segments,
                              semiring)[:, 0]


@functools.partial(jax.jit, static_argnames=("semiring", "num_segments", "use_pallas"))
def ell_spmv_batch(x, cols, vals, slices, row_map, num_segments: int,
                   semiring: str, use_pallas="auto", qparams=None):
    """Batched shard update: one edge pass serves K frontiers.

    x: [n, K] resident source matrix; returns [num_segments, K] partials —
    column k is exactly ``ell_spmv(x[:, k], ...)``.  The gather is
    column-major ([K, L, C]), so each column folds as a plain [L, C] tile
    against one load of the edge tile.
    """
    groups = ell_fold(x.T[:, _safe(cols)], vals, cols, semiring,
                      use_pallas=use_pallas, qparams=qparams)
    return _ref.slice_combine(groups, slices, row_map, num_segments, semiring)
