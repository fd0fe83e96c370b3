"""Pallas kernel sweeps vs the pure-jnp oracle (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypo import given, settings, st
from tests._layouts import random_layout, random_shard

from repro.core.semiring import SEMIRINGS
from repro.core.shards import (GROUP_ROWS, ROW_ALIGN, dequantize_edge_vals,
                               quantize_edge_vals)
from repro.kernels.spmv import ops, ref, spmv
from repro.kernels.spmv.ops import (describe_dispatch, ell_fold,
                                    ell_gather_fold, ell_spmv, ell_spmv_batch)

SEMIS = list(SEMIRINGS)
# (destination rows, edges) of one shard: one slice, several slices of
# unequal depth, hubs past the cap, a wide interval
SHAPES = [(16, 40), (300, 3000), (64, 5000), (2000, 12000)]
DTYPES = [np.float32, np.dtype("bfloat16")]


def _args(layout, x=None):
    return tuple(jnp.asarray(a) for a in ((x,) if x is not None else ())
                 + tuple(layout))


def _oracle(layout, x, rows, semiring):
    """``ref.ell_spmv_ref`` on ``layout``: sums in float64, where a float32
    sum of thousands of slots in slot order can itself stray 1e-5 from the
    exact value; min and max in float32, which they never round."""
    if not SEMIRINGS[semiring].is_plus:
        return np.asarray(ref.ell_spmv_ref(*_args(layout, x), rows, semiring))
    cols, vals, slices, row_map = layout
    with jax.enable_x64(True):
        return np.asarray(ref.ell_spmv_ref(
            jnp.asarray(x, jnp.float64), jnp.asarray(cols),
            jnp.asarray(vals, jnp.float64), jnp.asarray(slices),
            jnp.asarray(row_map), rows, semiring))


@pytest.mark.parametrize("semiring", SEMIS)
@pytest.mark.parametrize("shape", SHAPES)
def test_ell_spmv_vs_ref(semiring, shape):
    """The Pallas path against the jnp path, and both against the
    slot-by-slot oracle: sums within rtol 1e-5 (they re-associate), min and
    max exactly."""
    rows, edges = shape
    rng = np.random.default_rng(rows * edges)
    layout = random_layout(rng, 1000, rows, edges)
    x = rng.random(1000).astype(np.float32)
    out = np.asarray(ell_spmv(*_args(layout, x), rows, semiring,
                              use_pallas=True))
    jnp_path = np.asarray(ell_spmv(*_args(layout, x), rows, semiring,
                                   use_pallas=False))
    np.testing.assert_allclose(out, jnp_path, rtol=1e-6)
    want = _oracle(layout, x, rows, semiring)
    for got in (out, jnp_path):
        if SEMIRINGS[semiring].is_plus:
            np.testing.assert_allclose(got, want, rtol=1e-5)
        else:
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("semiring", SEMIS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_ell_fold_dtypes(semiring, dtype):
    rng = np.random.default_rng(3)
    cols, vals, _, _ = random_layout(rng, 300, 200, 4000)
    x = (rng.random(300).astype(np.float32) + 0.1).astype(dtype)
    vals = vals.astype(dtype)
    xg = x[np.where(cols >= 0, cols, 0)][None]
    out = ell_fold(jnp.asarray(xg), jnp.asarray(vals), jnp.asarray(cols),
                   semiring, use_pallas=True)
    want = ref.ell_fold_ref(jnp.asarray(xg), jnp.asarray(vals),
                            jnp.asarray(cols), semiring)
    assert out.shape == (1, cols.shape[0] // GROUP_ROWS, cols.shape[1])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype != np.float32 else 1e-6)


@pytest.mark.parametrize("semiring", SEMIS)
def test_ell_gather_fold_vs_ref(semiring):
    rng = np.random.default_rng(9)
    VB = 512
    cols, vals, _, _ = random_layout(rng, VB, 400, 6000)
    x = rng.random(VB).astype(np.float32)
    out = ell_gather_fold(jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
                          semiring, use_pallas=True)
    xg = x[np.where(cols >= 0, cols, 0)][None]
    want = ref.ell_fold_ref(jnp.asarray(xg), jnp.asarray(vals),
                            jnp.asarray(cols), semiring)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@given(st.integers(0, 10_000), st.sampled_from(SEMIS))
@settings(max_examples=20, deadline=None)
def test_property_random_small(seed, semiring):
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(1, 300))
    n = int(rng.integers(2, 500))
    layout = random_layout(rng, n, rows, int(rng.integers(0, 3000)),
                           cap=int(rng.integers(1, 200)))
    x = rng.random(n).astype(np.float32)
    out = ell_spmv(*_args(layout, x), rows, semiring, use_pallas=True)
    want = _oracle(layout, x, rows, semiring)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


@pytest.mark.parametrize("semiring", SEMIS)
def test_padded_row_map_changes_nothing(semiring):
    """A row map padded with slices that map no row (as staging pads it to
    the store's slice count) gives the same result, bit for bit."""
    rng = np.random.default_rng(17)
    shard = random_shard(rng, 600, 300, 4000)
    x = rng.random(600).astype(np.float32)
    rows = 300
    layout = (shard.cols, shard.vals, shard.group_slices(), shard.row_map)
    padded = layout[:3] + (shard.staged_row_map(2 * shard.num_slices + 3),)
    want = ell_spmv(*_args(layout, x), rows, semiring, use_pallas=True)
    got = ell_spmv(*_args(padded, x), rows, semiring, use_pallas=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_all_masked_rows_give_identity():
    """A shard without edges (every slot a sentinel, every virtual row
    padding) gives the identity on every destination row."""
    for semiring in SEMIS:
        sem = SEMIRINGS[semiring]
        layout = random_layout(np.random.default_rng(0), 16, 8, 0)
        out = ell_spmv(*_args(layout, np.ones(16, np.float32)), 8, semiring,
                       use_pallas=True)
        assert np.asarray(out).tolist() == [sem.identity] * 8


# ---------------------------------------------------------------------------
# the fold against the oracle: semirings x K x edge dtypes x shard kinds
# ---------------------------------------------------------------------------
FOLD_SEMIS = ["plus_times", "min_plus", "max_src"]
EDGE_DTYPES = ["float32", "int8", "float16"]
SHARD_KINDS = {
    # skewed in-degrees over 300 rows, some past the cap
    "skewed": lambda rng: random_shard(rng, 700, 300, 5000),
    # an interval of 512 rows of which only the first 40 have in-edges
    "empty_interval": lambda rng: random_shard(
        rng, 700, 512, 900, dst=rng.integers(0, 40, 900)),
    # every edge into one hub: one destination, wrapped at the cap
    "single_hub": lambda rng: random_shard(
        rng, 700, 64, 3000, cap=128, dst=np.full(3000, 17)),
}


@pytest.mark.parametrize("kind", SHARD_KINDS)
@pytest.mark.parametrize("edge_dtype", EDGE_DTYPES)
@pytest.mark.parametrize("k", [1, 16])
@pytest.mark.parametrize("semiring", FOLD_SEMIS)
def test_fold_vs_ref(semiring, k, edge_dtype, kind):
    """The Pallas fold (interpreted) through ``ell_spmv``/``ell_spmv_batch``
    against the slot-by-slot oracle on dequantized values: every
    destination of every column, in-degree-0 rows included."""
    rng = np.random.default_rng(11)
    shard = SHARD_KINDS[kind](rng)
    rows = shard.end_vertex - shard.start_vertex
    q, scale, zero = quantize_edge_vals(shard.vals, edge_dtype)
    qp = jnp.asarray([scale, zero], jnp.float32)
    vdq = dequantize_edge_vals(q, scale, zero)
    x = rng.random((700, k)).astype(np.float32)
    layout = (shard.cols, q, shard.group_slices(), shard.row_map)
    oracle = (shard.cols, vdq, shard.group_slices(), shard.row_map)
    if k == 1:
        got = ell_spmv(*_args(layout, x[:, 0]), rows, semiring,
                       use_pallas=True, qparams=qp)[:, None]
    else:
        got = ell_spmv_batch(*_args(layout, x), rows, semiring,
                             use_pallas=True, qparams=qp)
    want = ref.ell_spmv_batch_ref(*_args(oracle, x), rows, semiring)
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (rows, k)
    # rows without in-edges hold the identity in every column
    has_edge = np.zeros(rows, bool)
    has_edge[shard.row_map[shard.row_map >= 0]] = True
    assert (got[~has_edge] == SEMIRINGS[semiring].identity).all()
    # min/max never round: within 1 ulp (a dequantize-multiply contracted
    # into the add); sums re-associate
    np.testing.assert_allclose(got, want, rtol=1e-5 if semiring ==
                               "plus_times" else 3e-7)


# ---------------------------------------------------------------------------
# batched fold (column-major [K, L, C] gather layout) + dispatch
# ---------------------------------------------------------------------------
EXACT_SEMIS = ["min_plus", "max_src"]  # no float re-association: bitwise


def _gather_cols_major(x, cols):
    """[n, K] sources gathered column-major: [K, L, C]."""
    return np.ascontiguousarray(x.T[:, np.where(cols >= 0, cols, 0)])


@pytest.mark.parametrize("semiring", SEMIS)
@pytest.mark.parametrize("k", [1, 5])
def test_batch_columns_match_solo_fold_bitwise(semiring, k):
    """Each column of a K-column fold is the single-column fold's result,
    bit for bit, on every semiring: every lane and column reduces its own
    row groups (run_batch columns equal solo runs)."""
    rng = np.random.default_rng(42 + k)
    n = 700
    cols, vals, _, _ = random_layout(rng, n, 300, 5000)
    x = rng.random((n, k)).astype(np.float32)
    out = np.asarray(spmv.ell_fold_pallas(
        jnp.asarray(_gather_cols_major(x, cols)), jnp.asarray(vals),
        jnp.asarray(cols), semiring, interpret=True))
    assert out.shape == (k, cols.shape[0] // GROUP_ROWS, cols.shape[1])
    for c in range(k):
        solo = spmv.ell_fold_pallas(
            jnp.asarray(_gather_cols_major(x[:, c:c + 1], cols)),
            jnp.asarray(vals), jnp.asarray(cols), semiring, interpret=True)
        assert np.array_equal(out[c], np.asarray(solo)[0])


@pytest.mark.parametrize("semiring", SEMIS)
def test_batch_native_layout_vs_ref(semiring):
    """ell_fold_pallas consumes the column-major [K, L, C] gather layout
    natively and matches the oracle."""
    rng = np.random.default_rng(5)
    cols, vals, _, _ = random_layout(rng, 400, 200, 6000)
    x = rng.random((400, 6)).astype(np.float32)
    xg = jnp.asarray(_gather_cols_major(x, cols))
    out = spmv.ell_fold_pallas(xg, jnp.asarray(vals), jnp.asarray(cols),
                               semiring, interpret=True)
    want = ref.ell_fold_ref(xg, jnp.asarray(vals), jnp.asarray(cols),
                            semiring)
    assert out.shape == (6, cols.shape[0] // GROUP_ROWS, cols.shape[1])
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
    """The Pallas batch path gathers the [K, L, C] sources in ONE XLA
    gather (no per-column gathers, no second gather for the layout)."""
    rng = np.random.default_rng(0)
    n, rows, k = 600, 50, 3
    layout = random_layout(rng, n, rows, 800)
    x = rng.random((n, k)).astype(np.float32)
    jaxpr = jax.make_jaxpr(
        lambda *a: ell_spmv_batch(*a, rows, "min_plus", use_pallas=True))(
            *_args(layout, x))
    assert _count_gathers_outside_pallas(jaxpr.jaxpr) == 1


def test_dispatch_table_cpu():
    """docs/ARCHITECTURE.md dispatch table, executable form (CPU backend)."""
    assert describe_dispatch(False, k=1) == "jnp"
    assert describe_dispatch(False, k=16) == "jnp"
    # auto on an interpreting backend: single-column keeps the cheap Pallas
    # referee path, batched falls back to jnp
    assert describe_dispatch("auto", k=1) == "pallas:interpret:gather+fold"
    assert describe_dispatch("auto", k=16) == "jnp"
    # forced Pallas: the fold kernel for every K
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
    """TPU compiles the fold kernel for every K under 'auto'.  The kernel
    is written and tested for Mosaic TPU alone: on GPU backends 'auto'
    demotes to the fully-XLA-compiled jnp path, forced True keeps the
    interpret referee."""
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


def test_batch_tiles_keep_solo_width():
    """Row tiles never split the lanes or a row group, and divide the
    array: every block spans all C lanes, holds whole groups of GROUP_ROWS
    rows and whole (8, 128) output tiles, so no block is partial; a row
    count off the alignment is refused."""
    for L in (512, 8704, 2944, ROW_ALIGN, 96 * ROW_ALIGN):
        tb = spmv._row_tile(L)
        assert L % tb == 0 and tb % ROW_ALIGN == 0
        assert (tb // GROUP_ROWS) % 8 == 0
    assert spmv._row_tile(8704) == 512 and spmv._row_tile(2944) == 128
    with pytest.raises(ValueError, match="csr_to_ell"):
        spmv._row_tile(ROW_ALIGN + 8)


@pytest.mark.parametrize("semiring", EXACT_SEMIS)
def test_ops_batch_paths_agree_bitwise(semiring):
    """Public ell_spmv_batch: forced-Pallas, forced-jnp, and auto all agree
    bitwise on exact semirings."""
    rng = np.random.default_rng(17)
    layout = random_layout(rng, 500, 100, 3000)
    x = rng.random((500, 4)).astype(np.float32)
    args = _args(layout, x) + (100, semiring)
    outs = [np.asarray(ell_spmv_batch(*args, use_pallas=up))
            for up in (True, False, "auto")]
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


def test_segment_combine_batch_drops_out_of_range_ids():
    """Flattened batched combine: an id at num_segments or a padding
    virtual row's -1 is dropped, never spilled into another column."""
    partials = jnp.asarray(np.arange(15, dtype=np.float32).reshape(5, 3))
    row_map = jnp.asarray(np.array([0, 1, 1, 2, -1], np.int32))
    out = np.asarray(ref.segment_combine_batch(partials, row_map, 2,
                                               "plus_times"))
    want = np.array([[0, 1, 2], [3 + 6, 4 + 7, 5 + 8]], np.float32)
    assert np.array_equal(out, want)
