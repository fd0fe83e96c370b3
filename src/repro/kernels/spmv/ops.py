"""jit'd public wrappers for the SpMV kernels.

Dispatch is honest about the platform (``_resolve``): backends where the
kernels are known-correct compiled (tpu — see ``_COMPILED_BACKENDS`` for why
that list is TPU-only) compile them; everything else runs them in interpret
mode.  ``use_pallas`` selects the family:

  * ``"auto"``  — fastest correct path per platform.  TPU compiles the
    Pallas kernels for every K: XLA gathers the sources, then the fold
    kernel (K = 1) or the batched fold kernel (K > 1) reduces them.  On CPU
    the single-column path keeps Pallas in interpret mode (cheap enough,
    keeps the lowering exercised) but the BATCHED [n, K] fold takes pure
    jnp — interpret mode executes the grid step-by-step in Python with cost
    scaling in K, which would erase exactly the amortization
    ``run_batch``/GraphService exist for.  Non-CPU interpreting backends
    (gpu, until the kernels are ported) take jnp for every K: the jnp path
    is fully XLA-compiled there, while interpret mode would be step-by-step
    Python.  These demotions apply only when *interpreting*, never on TPU.
  * ``True``    — force Pallas (interpret off TPU; the A/B referee tests
    use this).
  * ``False``   — force the pure-jnp oracle path.

Quantized edge values (int8/float16 + affine qparams) are dequantized
in-kernel on the Pallas paths and via the bit-identical
``ref.maybe_dequantize`` on the jnp path.  ``describe_dispatch`` reports the
path a given configuration takes (used by the roofline report and docs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.spmv import ref as _ref
from repro.kernels.spmv import spmv as _pallas

# Backends allowed to COMPILE the Pallas kernels; anything else interprets
# (or, under "auto", demotes to jnp — see _pick_path).  TPU-only on purpose:
# every kernel in spmv.py accumulates into a revisited out_ref across the W
# grid axis (pl.when(w_step != 0) read-modify-write), which is only safe
# because TPU executes the grid sequentially.  GPU backends (cuda/rocm/
# triton) run grid programs in parallel, so that accumulation races.  Do not
# add a GPU backend here until the kernels are ported to (and tested on) one.
_COMPILED_BACKENDS = ("tpu",)


def _resolve(use_pallas) -> tuple[bool, bool]:
    """-> (use_pallas, interpret), dispatching on the *actual* platform.

    ``use_pallas=False`` short-circuits to the jnp path (no dead interpret
    flag); otherwise interpret mode is everything off ``_COMPILED_BACKENDS``
    — including GPU, whose parallel grid execution would race the kernels'
    sequential W-axis accumulation if compiled (see the comment on
    ``_COMPILED_BACKENDS``).
    """
    if not use_pallas:  # False
        return False, False
    return True, jax.default_backend() not in _COMPILED_BACKENDS


def _pick_path(use_pallas, k: int) -> tuple[bool, bool]:
    """-> (use the Pallas fold kernels?, interpret) for a K-column call.

    The spmv dispatch table (docs/ARCHITECTURE.md "Kernels"):
      * jnp    — use_pallas=False anywhere, or "auto" on an interpreting
        backend with K > 1 or off-CPU (interpret mode earns its keep only as
        the cheap single-column CPU referee; on GPU the jnp path is fully
        XLA-compiled while interpret mode is step-by-step Python).
      * pallas — everything else: XLA gather + the fold kernel (K = 1) or
        the batched fold kernel (K > 1); compiled on TPU, interpreted
        elsewhere.
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
    use, interp = _pick_path(use_pallas, 1)
    if use:
        return _pallas.ell_fold_pallas(xg, vals, cols, semiring,
                                       interpret=interp, qparams=qparams)
    return _ref.ell_fold_ref(xg, _ref.maybe_dequantize(vals, qparams), cols,
                             semiring)


@functools.partial(jax.jit, static_argnames=("semiring", "use_pallas"))
def ell_gather_fold(x_blk, cols, vals, semiring: str, use_pallas="auto",
                    qparams=None):
    """2-D-tiled fold: cols index a local source block x_blk [VB]."""
    return ell_fold(x_blk[_safe(cols)], vals, cols, semiring,
                    use_pallas=use_pallas, qparams=qparams)


@functools.partial(jax.jit, static_argnames=("semiring", "num_segments", "use_pallas"))
def ell_spmv(x, cols, vals, row_map, num_segments: int, semiring: str,
             use_pallas="auto", qparams=None):
    """Full shard update: gather + fold + segment combine.

    x: [n] resident source array; returns [num_segments] partials for the
    shard's destination interval (identity where the interval has no edges).
    """
    use, interp = _pick_path(use_pallas, 1)
    if not use:
        return _ref.ell_spmv_ref(x, cols, _ref.maybe_dequantize(vals, qparams),
                                 row_map, num_segments, semiring)
    partials = _pallas.ell_fold_pallas(x[_safe(cols)], vals, cols, semiring,
                                       interpret=interp, qparams=qparams)
    return _ref.segment_combine(partials, row_map, num_segments, semiring)


@functools.partial(jax.jit, static_argnames=("semiring", "num_segments", "use_pallas"))
def ell_spmv_batch(x, cols, vals, row_map, num_segments: int, semiring: str,
                   use_pallas="auto", qparams=None):
    """Batched shard update: one edge pass serves K frontiers.

    x: [n, K] resident source matrix; returns [num_segments, K] partials —
    column k is exactly ``ell_spmv(x[:, k], ...)``.  Both paths gather
    column-major ([K, R, W]), so each column folds as a plain [R, W] tile
    (the Pallas kernel against one load of the edge tile).
    """
    use, interp = _pick_path(use_pallas, x.shape[1])
    xg = x.T[:, _safe(cols)]                      # [K, R, W], one gather
    if use:
        partials = _pallas.ell_fold_batch_pallas(
            xg, vals, cols, semiring, interpret=interp, qparams=qparams)
    else:
        partials = _ref.ell_fold_batch_ref(
            xg, _ref.maybe_dequantize(vals, qparams), cols, semiring)
    return _ref.segment_combine_batch(partials, row_map, num_segments, semiring)
