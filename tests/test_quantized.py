"""Quantized edge values (int8/fp16): helpers, dequant-in-kernel, codecs,
engine end-to-end, and the GRAPHMP_DEVICES=2 Pallas leg.

Tolerance contract (docs/ARCHITECTURE.md "Kernels"):
  * vs the fp32 oracle on the TRUE values — bounded error: per-edge
    |v - v_hat| <= scale/2 for int8 (affine, range widened to include 0)
    and <= 2^-11 |v| for fp16; min/max semirings propagate the per-edge
    bound unamplified.
  * across dispatch paths (pallas fold / jnp fallback) —
    BITWISE on exact (min/max) semirings: every path applies the identical
    (q - zero) * scale arithmetic, so the referee property survives
    quantization.
  * vs the fp32 oracle on the DEQUANTIZED values — bitwise when the
    semiring's combine ignores the edge value (max_src/min_src); within
    1 ulp for min_plus, where backends may contract dequant-multiply +
    semiring-add into a single-rounded FMA (identically on every path).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.shards import (ELLShard, dequantize_edge_vals,
                               quantize_edge_vals, quantize_shard)
from repro.kernels.spmv import ref, spmv
from repro.kernels.spmv.ops import ell_spmv, ell_spmv_batch
from tests._layouts import random_layout

REPO = Path(__file__).resolve().parent.parent
EXACT_SEMIS = ["min_plus", "max_src"]
QDTYPES = ["int8", "float16"]


# ---------------------------------------------------------------------------
# quantizer helpers
# ---------------------------------------------------------------------------
def test_int8_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    vals = (rng.random((64, 128), np.float32) * 20 - 5).astype(np.float32)
    q, scale, zero = quantize_edge_vals(vals, "int8")
    assert q.dtype == np.int8
    err = np.abs(dequantize_edge_vals(q, scale, zero) - vals)
    assert float(err.max()) <= scale / 2 + 1e-7


def test_int8_constant_and_zero_exact():
    const = np.full((8, 16), 3.25, np.float32)
    q, scale, zero = quantize_edge_vals(const, "int8")
    assert np.array_equal(dequantize_edge_vals(q, scale, zero), const)
    # 0 is always exactly representable (padded slots store 0)
    with_zero = np.array([[0.0, 7.5]], np.float32)
    q, scale, zero = quantize_edge_vals(with_zero, "int8")
    assert dequantize_edge_vals(q, scale, zero)[0, 0] == 0.0
    # ...including when vmin < 0 makes the raw zero point fractional: the
    # quantizer rounds it to an integer so dequant(q(0)) == 0.0 exactly
    mixed = np.array([[-3.7, 0.0, 11.1]], np.float32)
    q, scale, zero = quantize_edge_vals(mixed, "int8")
    assert zero == np.rint(zero)
    assert dequantize_edge_vals(q, scale, zero)[0, 1] == 0.0


def test_float16_roundtrip_error_bound():
    rng = np.random.default_rng(1)
    vals = rng.random((32, 64), np.float32).astype(np.float32)
    q, scale, zero = quantize_edge_vals(vals, "float16")
    assert (q.dtype, scale, zero) == (np.float16, 1.0, 0.0)
    err = np.abs(dequantize_edge_vals(q, scale, zero) - vals)
    assert float(err.max()) <= 2.0 ** -11 * float(np.abs(vals).max()) + 1e-7


def test_quantize_shard_fields_and_accounting():
    rng = np.random.default_rng(2)
    cols = rng.integers(-1, 100, (16, 128)).astype(np.int32)
    vals = rng.random((16, 128), np.float32)
    s = ELLShard(0, 0, 10, cols, vals, np.arange(128, dtype=np.int32),
                 np.array([0, 16], np.int32), int((cols >= 0).sum()))
    q = quantize_shard(s, "int8")
    assert q.quantized and q.vals.dtype == np.int8
    # decoded-byte accounting shrinks with the stored dtype (cache budgets
    # and pipeline staged-bytes see the compressed footprint)
    assert q.decoded_nbytes() < s.decoded_nbytes()
    np.testing.assert_allclose(q.vals_f32(), vals, atol=q.val_scale / 2 + 1e-7)
    # re-quantizing to float32 restores a plain shard
    back = quantize_shard(q, "float32")
    assert not back.quantized and back.val_scale == 1.0


# ---------------------------------------------------------------------------
# dequant-in-kernel vs oracles
# ---------------------------------------------------------------------------
def _problem(rng, n=700, rows=200, edges=5000, K=4):
    cols, _, slices, row_map = random_layout(rng, n, rows, edges)
    vals = np.where(cols >= 0, rng.random(cols.shape, np.float32) * 4 - 1,
                    0.0).astype(np.float32)
    # sources in [4, 5): every edge's w + x stays in [3, 8), away from 0,
    # so one rounding of the dequantize-multiply (an FMA contraction) is
    # within 3e-7 of the result
    x = (rng.random((n, K)) + 4).astype(np.float32)
    return cols, vals, x, (jnp.asarray(slices), jnp.asarray(row_map), rows)


@pytest.mark.parametrize("semiring", EXACT_SEMIS)
@pytest.mark.parametrize("dtype", QDTYPES)
def test_quantized_paths_bitwise_identical(semiring, dtype):
    """All three dispatch paths (forced-Pallas, forced-jnp, auto)
    produce bit-identical results on quantized values — the referee
    property the engine's correctness story leans on."""
    rng = np.random.default_rng(3)
    cols, vals, x, tail = _problem(rng)
    q, scale, zero = quantize_edge_vals(vals, dtype)
    qp = jnp.asarray([scale, zero], jnp.float32)
    outs1 = [np.asarray(ell_spmv(
        jnp.asarray(x[:, 0]), jnp.asarray(cols), jnp.asarray(q),
        *tail, semiring, use_pallas=up, qparams=qp))
        for up in (True, False, "auto")]
    assert np.array_equal(outs1[0], outs1[1])
    assert np.array_equal(outs1[0], outs1[2])
    outsK = [np.asarray(ell_spmv_batch(
        jnp.asarray(x), jnp.asarray(cols), jnp.asarray(q),
        *tail, semiring, use_pallas=up, qparams=qp))
        for up in (True, False, "auto")]
    assert np.array_equal(outsK[0], outsK[1])
    assert np.array_equal(outsK[0], outsK[2])


@pytest.mark.parametrize("semiring", EXACT_SEMIS)
@pytest.mark.parametrize("dtype", QDTYPES)
@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "jnp"])
def test_quantized_vs_dequantized_oracle(semiring, dtype, use_pallas):
    """vs the fp32 oracle on pre-dequantized values: bitwise for max_src
    (combine ignores the edge value); within 1 ulp for min_plus, where the
    backend single-rounds dequant * scale + src into an FMA."""
    rng = np.random.default_rng(3)
    cols, vals, x, tail = _problem(rng)
    q, scale, zero = quantize_edge_vals(vals, dtype)
    qp = jnp.asarray([scale, zero], jnp.float32)
    vdq = jnp.asarray(dequantize_edge_vals(q, scale, zero))
    out1 = np.asarray(ell_spmv(
        jnp.asarray(x[:, 0]), jnp.asarray(cols), jnp.asarray(q),
        *tail, semiring, use_pallas=use_pallas, qparams=qp))
    want1 = np.asarray(ref.ell_spmv_ref(
        jnp.asarray(x[:, 0]), jnp.asarray(cols), vdq, *tail, semiring))
    outK = np.asarray(ell_spmv_batch(
        jnp.asarray(x), jnp.asarray(cols), jnp.asarray(q),
        *tail, semiring, use_pallas=use_pallas, qparams=qp))
    wantK = np.asarray(ref.ell_spmv_batch_ref(
        jnp.asarray(x), jnp.asarray(cols), vdq, *tail,
        semiring))
    if semiring == "max_src":
        assert np.array_equal(out1, want1)
        assert np.array_equal(outK, wantK)
    else:  # min_plus: 1-ulp FMA contraction slack
        np.testing.assert_allclose(out1, want1, rtol=3e-7)
        np.testing.assert_allclose(outK, wantK, rtol=3e-7)


@pytest.mark.parametrize("dtype", QDTYPES)
def test_quantized_tolerance_vs_fp32_oracle(dtype):
    """min_plus: the result error vs TRUE fp32 values is bounded by the
    per-edge quantization error (min propagates, never amplifies)."""
    rng = np.random.default_rng(4)
    cols, vals, x, tail = _problem(rng)
    q, scale, zero = quantize_edge_vals(vals, dtype)
    qp = jnp.asarray([scale, zero], jnp.float32)
    out = np.asarray(ell_spmv(jnp.asarray(x[:, 0]), jnp.asarray(cols),
                              jnp.asarray(q), *tail,
                              "min_plus", use_pallas=True, qparams=qp))
    want = np.asarray(ref.ell_spmv_ref(jnp.asarray(x[:, 0]), jnp.asarray(cols),
                                       jnp.asarray(vals), *tail, "min_plus"))
    bound = (scale / 2 if dtype == "int8"
             else 2.0 ** -11 * float(np.abs(vals).max()))
    finite = np.isfinite(want)
    assert float(np.abs(out[finite] - want[finite]).max()) <= bound + 1e-6


@pytest.mark.parametrize("dtype", QDTYPES)
def test_batch_kernel_dequantizes_like_solo(dtype):
    """The batched fold kernel dequantizes in-VMEM exactly as the
    single-column kernel does: every column is bitwise its solo fold."""
    rng = np.random.default_rng(5)
    cols, vals, x, _ = _problem(rng)
    q, scale, zero = quantize_edge_vals(vals, dtype)
    qp = jnp.asarray([scale, zero], jnp.float32)
    vdq = jnp.asarray(dequantize_edge_vals(q, scale, zero))
    safe = np.where(cols >= 0, cols, 0)
    out = np.asarray(spmv.ell_fold_pallas(
        jnp.asarray(x.T[:, safe]), jnp.asarray(q), jnp.asarray(cols),
        "min_plus", interpret=True, qparams=qp))
    for k in range(x.shape[1]):
        solo = spmv.ell_fold_pallas(jnp.asarray(x[safe, k][None]),
                                    jnp.asarray(q), jnp.asarray(cols),
                                    "min_plus", interpret=True, qparams=qp)
        assert np.array_equal(out[k], np.asarray(solo)[0])
    want = ref.ell_fold_ref(jnp.asarray(x.T[:, safe]), vdq,
                            jnp.asarray(cols), "min_plus")
    np.testing.assert_allclose(out, np.asarray(want), rtol=3e-7)


def test_bfloat16_vals_not_dequantized():
    """bf16 edge values are a compute dtype, not a quantized storage dtype —
    they must pass through the semiring untouched (no qparams arithmetic)."""
    rng = np.random.default_rng(6)
    cols, vals, x, tail = _problem(rng)
    vb = jnp.asarray(vals).astype(jnp.bfloat16)
    xb = jnp.asarray(x[:, 0]).astype(jnp.bfloat16)
    out = ell_spmv(xb, jnp.asarray(cols), vb, *tail,
                   "min_plus", use_pallas=True)
    want = ref.ell_spmv_ref(xb, jnp.asarray(cols), vb, *tail, "min_plus")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2)


# ---------------------------------------------------------------------------
# storage round-trips (all three ShardSource backends)
# ---------------------------------------------------------------------------
def _weighted_store(tmp_path, val_dtype, name="store"):
    from repro.graph.generate import materialize, rmat_edges
    from repro.graph.preprocess import preprocess_graph
    from repro.graph.storage import write_edge_list

    src, dst = materialize(rmat_edges(scale=8, edge_factor=8, seed=13))
    el = tmp_path / f"el_{name}"
    if not (el / "meta.json").exists():
        write_edge_list(el, [(src, dst)], weighted=True)
    return preprocess_graph(str(el), str(tmp_path / name),
                            threshold_edge_num=1024, ell_max_width=256,
                            val_dtype=val_dtype)


@pytest.mark.parametrize("dtype", QDTYPES)
def test_quantized_blob_roundtrip_three_backends(tmp_path, dtype):
    from repro.graph.memory import MemoryGraphStore
    from repro.graph.packed import PackedGraphStore, pack_graph
    from repro.graph.source import unpack_shard_npz

    store = _weighted_store(tmp_path, dtype)
    assert store.properties["val_dtype"] == dtype
    packed = PackedGraphStore(pack_graph(store))
    mem = MemoryGraphStore.from_source(store)
    for p in range(store.num_shards):
        base = store.read_shard(p)
        assert base.vals.dtype == np.dtype(dtype)
        for other in (packed.read_shard(p), mem.read_shard(p),
                      unpack_shard_npz(p, store.read_shard_bytes(p)),
                      unpack_shard_npz(p, packed.read_shard_bytes(p)),
                      unpack_shard_npz(p, mem.read_shard_bytes(p))):
            assert other.vals.dtype == base.vals.dtype
            assert np.array_equal(other.vals, base.vals)
            assert (other.val_scale, other.val_zero) == \
                (base.val_scale, base.val_zero)
            assert np.array_equal(other.cols, base.cols)


def test_unweighted_store_ignores_edge_dtype(tmp_path, monkeypatch):
    """Unweighted graphs keep unit float32 vals (the npz codec elides them);
    GRAPHMP_EDGE_DTYPE only applies to weighted inputs."""
    from repro.graph.generate import materialize, rmat_edges
    from repro.graph.preprocess import preprocess_graph
    from repro.graph.storage import write_edge_list

    monkeypatch.setenv("GRAPHMP_EDGE_DTYPE", "int8")
    src, dst = materialize(rmat_edges(scale=7, edge_factor=4, seed=3))
    write_edge_list(tmp_path / "el", [(src, dst)])
    store = preprocess_graph(str(tmp_path / "el"), str(tmp_path / "store"),
                             threshold_edge_num=1024)
    assert store.properties["val_dtype"] == "float32"
    assert store.read_shard(0).vals.dtype == np.float32


def test_env_knob_and_validation(tmp_path, monkeypatch):
    from repro.graph.preprocess import resolve_val_dtype

    monkeypatch.delenv("GRAPHMP_EDGE_DTYPE", raising=False)
    assert resolve_val_dtype(None) == "float32"
    monkeypatch.setenv("GRAPHMP_EDGE_DTYPE", "float16")
    assert resolve_val_dtype(None) == "float16"
    assert resolve_val_dtype("int8") == "int8"  # explicit arg wins
    with pytest.raises(ValueError):
        resolve_val_dtype("int4")


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", QDTYPES)
def test_session_quantized_pallas_vs_jnp_bitwise(tmp_path, dtype):
    """SSSP over a quantized store: forced-Pallas (dequant-in-kernel)
    and forced-jnp (host dequant formula) agree bitwise — the referee
    property the CI kernels job leans on."""
    from repro.core.engine import EngineConfig
    from repro.session import GraphSession

    store = _weighted_store(tmp_path, dtype)
    outs = {}
    for up in (True, False):
        sess = GraphSession(store, config=EngineConfig(use_pallas=up))
        res = sess.run("sssp", source=0)
        outs[up] = np.asarray(res.values)
    assert np.array_equal(outs[True], outs[False])


def test_session_quantized_close_to_fp32(tmp_path):
    """int8 SSSP distances track the fp32 store within hops * scale/2."""
    from repro.session import GraphSession

    f32 = _weighted_store(tmp_path, "float32", name="s32")
    q8 = _weighted_store(tmp_path, "int8", name="s8")
    r32 = GraphSession(f32).run("sssp", source=0)
    r8 = GraphSession(q8).run("sssp", source=0)
    a, b = np.asarray(r32.values), np.asarray(r8.values)
    finite = np.isfinite(a) & np.isfinite(b)
    assert (np.isfinite(a) == np.isfinite(b)).all()
    scale = max(s.val_scale for s in (q8.read_shard(p)
                                      for p in range(q8.num_shards)))
    hops = max(r32.iterations, r8.iterations)
    assert float(np.abs(a[finite] - b[finite]).max()) <= hops * scale / 2 + 1e-5


def test_delta_mutation_keeps_quantized_dtype(tmp_path):
    """Edge mutations on a quantized store re-quantize the merged shard at
    the store's recorded val_dtype and runs still work."""
    from repro.graph.delta import DeltaGraphStore
    from repro.session import GraphSession

    store = _weighted_store(tmp_path, "int8")
    delta = DeltaGraphStore(store)
    n = store.num_vertices
    delta.apply(inserts=[(0, n - 1, 0.5), (1, n - 1, 0.25)])
    merged_dirty = [delta.read_shard(p) for p in range(delta.num_shards)
                    if delta.shard_epoch(p) > 0]
    assert merged_dirty, "mutation should dirty at least one shard"
    assert all(s.vals.dtype == np.int8 for s in merged_dirty)
    res = GraphSession(delta).run("sssp", source=0)
    assert np.isfinite(np.asarray(res.values)).any()


# ---------------------------------------------------------------------------
# GRAPHMP_DEVICES=2 leg: Pallas kernels under the sharded engine
# ---------------------------------------------------------------------------
def test_sharded_engine_fused_bitwise_two_devices(tmp_path):
    """ShardedVSWEngine with GRAPHMP_USE_PALLAS=1 (Pallas kernels) over a
    quantized store is bitwise-identical to the single-device engine."""
    code = textwrap.dedent("""
        import numpy as np
        from repro.graph.generate import rmat_edges, materialize
        from repro.graph.storage import write_edge_list
        from repro.graph.preprocess import preprocess_graph
        from repro.core.engine import EngineConfig
        from repro.session import GraphSession
        import tempfile

        src, dst = materialize(rmat_edges(scale=8, edge_factor=8, seed=13))
        base = tempfile.mkdtemp()
        write_edge_list(base + "/el", [(src, dst)], weighted=True)
        store = preprocess_graph(base + "/el", base + "/store",
                                 threshold_edge_num=1024, ell_max_width=256,
                                 val_dtype="int8")
        vals = {}
        for d in (1, 2):
            cfg = EngineConfig(use_pallas=True, num_devices=d)
            res = GraphSession(store, config=cfg).run("sssp", source=0)
            vals[d] = np.asarray(res.values)
        assert np.array_equal(vals[1], vals[2]), "D=2 diverged from D=1"
        print("OK", np.isfinite(vals[1]).sum())
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = str(REPO / "src")
    env["GRAPHMP_USE_PALLAS"] = "1"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "OK" in r.stdout
