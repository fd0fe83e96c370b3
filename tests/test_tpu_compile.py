"""Compile the SpMV kernel and the shard step for a described TPU v5e at
the benchmark cells' real shapes.

Nothing runs: XLA:TPU and Mosaic compile each kernel for ``v5e:2x2``'s first
chip from shapes alone, so a kernel the TPU compiler refuses (a layout it
cannot lower, a block over the VMEM limit) fails here, on a CPU machine,
instead of on the chip.  Covers every kernel ``ops._pick_path`` can choose
on TPU — the fold at K=1 and K=16 in f32 and int8 — the dispatching
``ell_spmv`` / ``ell_spmv_batch`` over an n = 2^22 frontier, and the
engine's ``shard_step`` for PageRank and personalised PageRank.

The topology is described inside a module fixture — never at import — so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.apps import get_app
from repro.core.engine import make_shard_step
from repro.core.shards import GROUP_ROWS
from repro.kernels.spmv import ops, spmv

N = 1 << 22      # frontier length: a scale-22 graph
SEGMENTS = 49152  # the destination rows a shard step covers at scale 21/22
K = 16           # run_batch / GraphService micro-batch width
# (rows L, slices S) of the cells' shards: almost every shard of 2^20
# edges, and the smaller last shard of the scale-22 graph
SHAPES = ((8704, 192), (2944, 48))
EDGE_DTYPES = {"f32": jnp.float32, "int8": jnp.int8}


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is in
    return compiled


@pytest.mark.parametrize("edge_dtype", EDGE_DTYPES)
@pytest.mark.parametrize("width", SHAPES)
def test_fold_kernel_compiles(one_chip, width, edge_dtype):
    """The K=1 fold at a shard's real rows (``width`` is its (L, S))."""
    L, _S = width

    def fold(xg, vals, cols, qp):
        return spmv.ell_fold_pallas(xg, vals, cols, "min_plus",
                                    interpret=False, qparams=qp)

    _compile(one_chip, fold, ((1, L, 128), jnp.float32),
             ((L, 128), EDGE_DTYPES[edge_dtype]), ((L, 128), jnp.int32),
             ((2,), jnp.float32))


@pytest.mark.parametrize("semiring", ["min_plus", "plus_src"])
@pytest.mark.parametrize("edge_dtype", EDGE_DTYPES)
@pytest.mark.parametrize("width", SHAPES)
def test_batch_fold_kernel_compiles(one_chip, width, edge_dtype, semiring):
    L, _S = width

    def fold(xg, vals, cols, qp):
        return spmv.ell_fold_pallas(xg, vals, cols, semiring,
                                    interpret=False, qparams=qp)

    _compile(one_chip, fold, ((K, L, 128), jnp.float32),
             ((L, 128), EDGE_DTYPES[edge_dtype]), ((L, 128), jnp.int32),
             ((2,), jnp.float32))


def _layout_shapes(L, S, edge_dtype=jnp.float32):
    return (((L, 128), jnp.int32), ((L, 128), edge_dtype),
            ((L // GROUP_ROWS,), jnp.int32), ((S * 128,), jnp.int32))


@pytest.mark.parametrize("k", [1, K])
@pytest.mark.parametrize("width", SHAPES)
def test_dispatched_spmv_compiles(one_chip, monkeypatch, width, k):
    """The public ops as the engine calls them under use_pallas="auto",
    steered onto their TPU branch: XLA gather + kernel + slice combine."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.describe_dispatch("auto", k=k) == "pallas:compiled:gather+fold"
    x_shape = (N,) if k == 1 else (N, k)
    op = ops.ell_spmv if k == 1 else ops.ell_spmv_batch

    def spmv_step(x, cols, vals, slices, row_map):
        return op(x, cols, vals, slices, row_map, SEGMENTS, "min_plus")

    compiled = _compile(one_chip, spmv_step, (x_shape, jnp.float32),
                        *_layout_shapes(*width))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 30  # fits one v5e's HBM


@pytest.mark.parametrize("app", ["pagerank", "personalized_pagerank",
                                 "sssp"])
@pytest.mark.parametrize("width", SHAPES)
def test_shard_step_compiles(one_chip, monkeypatch, width, app):
    """The engine's whole shard step (``jit_shard_step``: gather, fold,
    slice combine, post, update) as the cells run it: PageRank at K=1,
    personalised PageRank at K=16 and SSSP at K=1 (``min_plus`` over
    float32 edge values), on a scale-22 vertex array."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if app in ("pagerank", "sssp"):
        program = get_app(app)
        vshape, extra = (N + SEGMENTS,), ()
    else:
        program = get_app(app, seeds=tuple(range(K)))
        vshape = (N + SEGMENTS, K)
        extra = ((vshape, jnp.float32), ((), jnp.int32))
    step = make_shard_step(program, N, SEGMENTS, "auto",
                           batched=app == "personalized_pagerank")
    _compile(one_chip, step, (vshape, jnp.float32), (vshape, jnp.float32),
             (vshape, jnp.float32), *extra, *_layout_shapes(*width),
             ((2,), jnp.float32), ((), jnp.int32), ((), jnp.int32))
