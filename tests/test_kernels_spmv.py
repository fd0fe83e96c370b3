"""Pallas kernel sweeps vs the pure-jnp oracle (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypo import given, settings, st

from repro.core.semiring import SEMIRINGS
from repro.kernels.spmv import ops, ref, spmv
from repro.kernels.spmv.ops import (describe_dispatch, ell_fold,
                                    ell_gather_fold, ell_spmv, ell_spmv_batch)

SEMIS = list(SEMIRINGS)
SHAPES = [(8, 128), (64, 256), (256, 128), (512, 640)]
DTYPES = [np.float32, np.dtype("bfloat16")]


def _make(rng, n, R, W, dtype):
    cols = rng.integers(-1, n, size=(R, W)).astype(np.int32)
    vals = rng.random((R, W)).astype(np.float32).astype(dtype)
    x = (rng.random(n).astype(np.float32) + 0.1).astype(dtype)
    row_map = np.sort(rng.integers(0, max(R // 2, 1), size=R)).astype(np.int32)
    return cols, vals, x, row_map


@pytest.mark.parametrize("semiring", SEMIS)
@pytest.mark.parametrize("shape", SHAPES)
def test_ell_spmv_vs_ref(semiring, shape):
    R, W = shape
    rng = np.random.default_rng(R * W)
    cols, vals, x, row_map = _make(rng, 1000, R, W, np.float32)
    out = ell_spmv(jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
                   jnp.asarray(row_map), R, semiring, use_pallas=True)
    want = ref.ell_spmv_ref(jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
                            jnp.asarray(row_map), R, semiring)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("semiring", SEMIS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_ell_fold_dtypes(semiring, dtype):
    rng = np.random.default_rng(3)
    cols, vals, x, _ = _make(rng, 300, 64, 256, dtype)
    xg = x[np.where(cols >= 0, cols, 0)]
    out = ell_fold(jnp.asarray(xg), jnp.asarray(vals), jnp.asarray(cols),
                   semiring, use_pallas=True)
    want = ref.ell_fold_ref(jnp.asarray(xg), jnp.asarray(vals), jnp.asarray(cols),
                            semiring)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype != np.float32 else 1e-6)


@pytest.mark.parametrize("semiring", SEMIS)
def test_ell_gather_fold_vs_ref(semiring):
    rng = np.random.default_rng(9)
    VB = 512
    cols, vals, x, _ = _make(rng, VB, 128, 384, np.float32)
    out = ell_gather_fold(jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
                          semiring, use_pallas=True)
    want = ref.ell_gather_fold_ref(jnp.asarray(x), jnp.asarray(cols),
                                   jnp.asarray(vals), semiring)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@given(st.integers(0, 10_000), st.sampled_from(SEMIS))
@settings(max_examples=20, deadline=None)
def test_property_random_small(seed, semiring):
    rng = np.random.default_rng(seed)
    R = 8 * rng.integers(1, 5)
    W = 128 * rng.integers(1, 3)
    cols, vals, x, row_map = _make(rng, int(rng.integers(2, 500)), R, W, np.float32)
    out = ell_spmv(jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
                   jnp.asarray(row_map), R, semiring, use_pallas=True)
    want = ref.ell_spmv_ref(jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
                            jnp.asarray(row_map), R, semiring)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


def test_all_masked_rows_give_identity():
    for semiring in SEMIS:
        sem = SEMIRINGS[semiring]
        cols = jnp.full((8, 128), -1, jnp.int32)
        vals = jnp.zeros((8, 128), jnp.float32)
        x = jnp.ones((16,), jnp.float32)
        out = ell_spmv(x, cols, vals, jnp.zeros((8,), jnp.int32), 8, semiring,
                       use_pallas=True)
        assert np.asarray(out)[1:].tolist() == [sem.identity] * 7


# ---------------------------------------------------------------------------
# batched fold kernel (column-major [K, R, W] gather layout) + dispatch
# ---------------------------------------------------------------------------
EXACT_SEMIS = ["min_plus", "max_src"]  # no float re-association: bitwise


def _make_batch(rng, n, R, W, K):
    cols = rng.integers(-1, n, size=(R, W)).astype(np.int32)
    vals = rng.random((R, W)).astype(np.float32)
    x = rng.random((n, K)).astype(np.float32)
    row_map = np.sort(rng.integers(0, max(R // 2, 1), size=R)).astype(np.int32)
    return cols, vals, x, row_map


def _gather_cols_major(x, cols):
    """[n, K] sources gathered column-major: [K, R, W]."""
    return np.ascontiguousarray(x.T[:, np.where(cols >= 0, cols, 0)])


@pytest.mark.parametrize("semiring", SEMIS)
@pytest.mark.parametrize("k", [1, 5])
def test_batch_columns_match_solo_fold_bitwise(semiring, k):
    """Each column of the batched kernel is the single-column fold kernel's
    result, bit for bit, on every semiring: both reduce the same [tr, tw]
    tiles in the same order (run_batch columns equal solo runs)."""
    rng = np.random.default_rng(42 + k)
    n, R, W = 700, 64, 256
    cols, vals, x, _ = _make_batch(rng, n, R, W, k)
    out = np.asarray(spmv.ell_fold_batch_pallas(
        jnp.asarray(_gather_cols_major(x, cols)), jnp.asarray(vals),
        jnp.asarray(cols), semiring, interpret=True))
    assert out.shape == (R, k)
    for c in range(k):
        xg = x[np.where(cols >= 0, cols, 0), c]
        solo = spmv.ell_fold_pallas(jnp.asarray(xg), jnp.asarray(vals),
                                    jnp.asarray(cols), semiring,
                                    interpret=True)
        assert np.array_equal(out[:, c], np.asarray(solo)[:, 0])


@pytest.mark.parametrize("semiring", SEMIS)
def test_batch_native_layout_vs_ref(semiring):
    """ell_fold_batch_pallas consumes the column-major [K, R, W] gather
    layout natively and matches the oracle."""
    rng = np.random.default_rng(5)
    cols, vals, x, _ = _make_batch(rng, 400, 72, 384, 6)
    xg = jnp.asarray(_gather_cols_major(x, cols))
    out = spmv.ell_fold_batch_pallas(xg, jnp.asarray(vals), jnp.asarray(cols),
                                     semiring, interpret=True)
    want = ref.ell_fold_batch_ref(xg, jnp.asarray(vals), jnp.asarray(cols),
                                  semiring)
    assert out.shape == (72, 6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


def _count_gathers_outside_pallas(jaxpr) -> int:
    """Walk a jaxpr (descending into pjit etc.) counting gather ops that are
    NOT inside a pallas_call — i.e. XLA-materialized gathers in HBM."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "gather":
            count += 1
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                count += _count_gathers_outside_pallas(inner)
    return count


def test_batch_pallas_path_gathers_once():
    """The Pallas batch path gathers the [K, R, W] sources in ONE XLA
    gather (no per-column gathers, no second gather for the layout)."""
    rng = np.random.default_rng(0)
    n, R, W, k = 600, 16, 128, 3
    cols, vals, x, row_map = _make_batch(rng, n, R, W, k)
    args = (jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
            jnp.asarray(row_map))
    jaxpr = jax.make_jaxpr(
        lambda *a: ell_spmv_batch(*a, R, "min_plus", use_pallas=True))(*args)
    assert _count_gathers_outside_pallas(jaxpr.jaxpr) == 1


def test_dispatch_table_cpu():
    """docs/ARCHITECTURE.md dispatch table, executable form (CPU backend)."""
    assert describe_dispatch(False, k=1) == "jnp"
    assert describe_dispatch(False, k=16) == "jnp"
    # auto on an interpreting backend: single-column keeps the cheap Pallas
    # referee path, batched falls back to jnp
    assert describe_dispatch("auto", k=1) == "pallas:interpret:gather+fold"
    assert describe_dispatch("auto", k=16) == "jnp"
    # forced Pallas: the fold kernels for every K
    assert describe_dispatch(True, k=1) == "pallas:interpret:gather+fold"
    assert describe_dispatch(True, k=16) == "pallas:interpret:gather+fold"


def test_resolve_no_dead_interpret_flag():
    """use_pallas=False short-circuits; 'auto'/True interpret only off the
    compiled backends (the old code forced interpret on GPU)."""
    assert ops._resolve(False) == (False, False)
    use, interp = ops._resolve("auto")
    assert use is True
    assert interp == (jax.default_backend() not in ops._COMPILED_BACKENDS)


def test_compiled_dispatch_is_tpu_only(monkeypatch):
    """TPU compiles the fold kernels for every K under 'auto'.  GPU backends
    run grid programs in parallel, so the kernels' sequential W-axis
    accumulation must never compile there: 'auto' demotes to the
    fully-XLA-compiled jnp path, forced True keeps the interpret referee."""
    assert ops._COMPILED_BACKENDS == ("tpu",)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._resolve("auto") == (True, False)
    for k in (1, 16):
        assert describe_dispatch("auto", k=k) == "pallas:compiled:gather+fold"
        assert describe_dispatch(True, k=k) == "pallas:compiled:gather+fold"
        assert describe_dispatch(False, k=k) == "jnp"
    for backend in ("gpu", "cuda", "rocm"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops._resolve("auto") == (True, True)
        assert describe_dispatch("auto", k=1) == "jnp"
        assert describe_dispatch("auto", k=16) == "jnp"
        assert describe_dispatch(True, k=16) == "pallas:interpret:gather+fold"


def test_vmem_block_bytes_padding():
    """VMEM tiles the two minor dims to (8 sublane, 128 lane): a K=1 column
    occupies 128 lanes per row, which the unpadded n*k*itemsize model
    under-counted by 128x."""
    assert spmv.vmem_block_bytes((1000, 1), 4) == 1000 * 128 * 4
    assert spmv.vmem_block_bytes((32, 100, 16), 4) == 32 * 104 * 128 * 4
    # aligned shapes pad to themselves
    assert spmv.vmem_block_bytes((256, 8, 128), 4) == 256 * 8 * 128 * 4


def test_batch_tiles_keep_solo_width():
    """The batched kernel never shrinks the width tile: each column then
    reduces over exactly the lanes ell_fold_pallas reduces over."""
    for (R, W, K) in [(512, 1024, 1), (80_000, 512, 16), (80_000, 128, 256),
                      (8, 128, 3)]:
        _tk, _tr, tw = spmv._batch_tiles(R, W, K, 4)
        assert tw == min(spmv.DEFAULT_TW, W)


def test_batch_tiles_respect_padded_budget():
    """Auto-shrunk [tk, tr, tw] tiles fit TILE_BYTES_BUDGET under the padded
    model (or sit at the floor: one column of MIN_BATCH_TR rows)."""
    for (R, W, K) in [(512, 1024, 1), (512, 1024, 16), (64, 256, 4),
                      (80_000, 512, 256)]:
        tk, tr, tw = spmv._batch_tiles(R, W, K, 4)
        at_floor = tk == 1 and tr <= min(R, spmv.MIN_BATCH_TR)
        assert (spmv.vmem_block_bytes((tk, tr, tw), 4)
                <= spmv.TILE_BYTES_BUDGET) or at_floor


@pytest.mark.parametrize("semiring", EXACT_SEMIS)
def test_ops_batch_paths_agree_bitwise(semiring):
    """Public ell_spmv_batch: forced-Pallas, forced-jnp, and auto all agree
    bitwise on exact semirings."""
    rng = np.random.default_rng(17)
    cols, vals, x, row_map = _make_batch(rng, 500, 32, 128, 4)
    args = (jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
            jnp.asarray(row_map), 32, semiring)
    outs = [np.asarray(ell_spmv_batch(*args, use_pallas=up))
            for up in (True, False, "auto")]
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


def test_segment_combine_batch_drops_out_of_range_ids():
    """Flattened batched combine: an id at num_segments (the sharded
    engine's padded rows) is dropped, never spilled into the next column."""
    partials = jnp.asarray(np.arange(12, dtype=np.float32).reshape(4, 3))
    row_map = jnp.asarray(np.array([0, 1, 1, 2], np.int32))
    out = np.asarray(ref.segment_combine_batch(partials, row_map, 2,
                                               "plus_times"))
    want = np.array([[0, 1, 2], [3 + 6, 4 + 7, 5 + 8]], np.float32)
    assert np.array_equal(out, want)
