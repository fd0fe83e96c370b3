"""Multi-device semantics, run in subprocesses with XLA_FLAGS-forced device
counts (the main test process must keep seeing 1 CPU device)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_distributed_vsw_matches_single_device():
    out = run_with_devices("""
        import numpy as np, jax
        from repro.graph.generate import rmat_edges, materialize
        from repro.core.distributed import partition_for_mesh, DistributedVSW
        from repro.core import apps

        src, dst = materialize(rmat_edges(scale=9, edge_factor=8, seed=3))
        n = 1 << 9
        mesh8 = jax.make_mesh((8,), ('data',),
                              axis_types=(jax.sharding.AxisType.Auto,))
        g8 = partition_for_mesh(src, dst, n, 8)
        vals8, it8 = DistributedVSW(g8, apps.cc(), mesh8).run(100)
        # oracle fixpoint
        ref = np.arange(g8.num_vertices, dtype=np.float64)
        for _ in range(200):
            new = ref.copy(); np.minimum.at(new, dst, ref[src])
            if (new == ref).all(): break
            ref = new
        assert (vals8 == ref).all(), 'cc mismatch on 8 devices'
        print('OK', it8)
    """)
    assert "OK" in out


def test_distributed_vsw_pagerank_8dev():
    out = run_with_devices("""
        import numpy as np, jax
        from repro.graph.generate import rmat_edges, materialize
        from repro.core.distributed import partition_for_mesh, DistributedVSW
        from repro.core import apps

        src, dst = materialize(rmat_edges(scale=8, edge_factor=8, seed=5))
        n = 1 << 8
        mesh = jax.make_mesh((8,), ('data',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = partition_for_mesh(src, dst, n, 8)
        eng = DistributedVSW(g, apps.pagerank(), mesh)
        vals, _ = eng.run(30)
        out_deg = np.bincount(src, minlength=g.num_vertices)
        pr = np.full(g.num_vertices, 1.0/g.num_vertices)
        for _ in range(30):
            c = pr / np.maximum(out_deg, 1)
            s = np.zeros_like(pr); np.add.at(s, dst, c[src])
            pr = 0.15/g.num_vertices + 0.85*s
        assert np.abs(vals - pr).max() < 1e-5, np.abs(vals - pr).max()
        print('OK')
    """)
    assert "OK" in out


def test_distributed_vsw_non_divisible_n():
    """Regression: n not divisible by the device count.  partition_for_mesh
    pads the intervals; the padding rows must not absorb PageRank mass,
    join the CC label space, or be counted as changed vertices."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.graph.generate import rmat_edges, materialize
        from repro.core.distributed import partition_for_mesh, DistributedVSW
        from repro.core import apps

        src, dst = materialize(rmat_edges(scale=9, edge_factor=8, seed=3))
        n = 500  # 500 % 8 != 0
        keep = (src < n) & (dst < n)
        src, dst = src[keep], dst[keep]
        mesh = jax.make_mesh((8,), ('data',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = partition_for_mesh(src, dst, n, 8)
        assert g.num_vertices == n, g.num_vertices
        assert g.padded_num_vertices == 504, g.padded_num_vertices

        vals, _ = DistributedVSW(g, apps.cc(), mesh).run(100)
        assert vals.shape == (n,), vals.shape
        ref = np.arange(n, dtype=np.float64)
        for _ in range(200):
            new = ref.copy(); np.minimum.at(new, dst, ref[src])
            if (new == ref).all(): break
            ref = new
        assert (vals == ref).all(), 'cc: padding leaked into labels'

        pr_vals, _ = DistributedVSW(g, apps.pagerank(), mesh).run(30)
        out_deg = np.bincount(src, minlength=n)
        pr = np.full(n, 1.0 / n)
        for _ in range(30):
            c = pr / np.maximum(out_deg, 1)
            s = np.zeros_like(pr); np.add.at(s, dst, c[src])
            pr = 0.15 / n + 0.85 * s
        err = np.abs(pr_vals - pr).max()
        assert err < 1e-5, f'pagerank: padding absorbed mass ({err})'
        print('OK')
    """)
    assert "OK" in out


def test_distributed_vsw_honors_config():
    """EngineConfig fields the prototype supports must be honored (not
    silently dropped), and the replicated-Bloom selective schedule must
    keep SSSP exact while devices get skipped."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.graph.generate import rmat_edges, materialize
        from repro.core.distributed import partition_for_mesh, DistributedVSW
        from repro.core import apps
        from repro.core.engine import EngineConfig

        src, dst = materialize(rmat_edges(scale=9, edge_factor=8, seed=11))
        n = 500
        keep = (src < n) & (dst < n)
        src, dst = src[keep], dst[keep]
        mesh = jax.make_mesh((8,), ('data',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = partition_for_mesh(src, dst, n, 8)
        assert len(g.blooms) == 8

        cfg = EngineConfig(use_pallas=False, selective_threshold=0.5)
        eng = DistributedVSW(g, apps.sssp(source=3), mesh, config=cfg)
        assert eng.use_pallas is False
        assert eng.selective_threshold == 0.5
        # threshold 0.5 forces Bloom probing from the 1-vertex frontier on
        flags = eng._schedule_flags(np.array([3]), 1.0 / n)
        assert flags.dtype == bool and flags.shape == (8,)
        dist, _ = eng.run(100)

        init = np.full(n, np.inf); init[3] = 0.0
        ref = init.copy()
        for _ in range(200):
            new = ref.copy(); np.minimum.at(new, dst, ref[src] + 1.0)
            if (new == ref).all(): break
            ref = new
        assert np.array_equal(dist, ref.astype(np.float32)), 'sssp mismatch'
        print('OK')
    """)
    assert "OK" in out


def test_spmv_2d_partition():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.distributed import spmv_2d, stack_layouts
        from repro.kernels.spmv import ref
        from tests._layouts import random_layout

        mesh = jax.make_mesh((2, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        rng = np.random.default_rng(0)
        D, S, R, nloc = 2, 2, 16, 64
        n = S * nloc
        # cols are LOCAL source indices into each device's x block
        tiles = [[random_layout(rng, nloc, R, 200) for s in range(S)]
                 for d in range(D)]
        cols, vals, slices, row_map = (
            a.reshape((D, S) + a.shape[1:])
            for a in stack_layouts([t for row in tiles for t in row]))
        x = rng.random(n).astype(np.float32)
        out = spmv_2d(jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
                      jnp.asarray(slices), jnp.asarray(row_map), R,
                      'plus_times', mesh)
        # oracle: per dst-block, sum over src blocks of local spmv
        want = np.zeros((D, R), np.float32)
        for d in range(D):
            for s in range(S):
                xb = x[s*nloc:(s+1)*nloc]
                seg = ref.ell_spmv_ref(jnp.asarray(xb),
                                       *(jnp.asarray(a) for a in tiles[d][s]),
                                       R, 'plus_times')
                want[d] += np.asarray(seg)
        got = np.asarray(out).reshape(D, R)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        print('OK')
    """)
    assert "OK" in out


def test_spmv_2d_min_semiring():
    """min_plus over the 2-D partition: the cross-src-block combine is a
    pmin (all_gather + fold), not a psum — must match the elementwise min
    of per-block single-device SpMVs EXACTLY (min never rounds)."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import spmv_2d, stack_layouts
        from repro.kernels.spmv import ref
        from tests._layouts import random_layout

        mesh = jax.make_mesh((2, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        rng = np.random.default_rng(1)
        D, S, R, nloc = 2, 2, 16, 48  # nloc deliberately unaligned
        n = S * nloc
        tiles = [[random_layout(rng, nloc, R, 150) for s in range(S)]
                 for d in range(D)]
        cols, vals, slices, row_map = (
            a.reshape((D, S) + a.shape[1:])
            for a in stack_layouts([t for row in tiles for t in row]))
        x = rng.random(n).astype(np.float32)
        out = spmv_2d(jnp.asarray(x), jnp.asarray(cols), jnp.asarray(vals),
                      jnp.asarray(slices), jnp.asarray(row_map), R,
                      'min_plus', mesh)
        want = np.full((D, R), np.inf, np.float32)
        for d in range(D):
            for s in range(S):
                xb = x[s*nloc:(s+1)*nloc]
                seg = ref.ell_spmv_ref(jnp.asarray(xb),
                                       *(jnp.asarray(a) for a in tiles[d][s]),
                                       R, 'min_plus')
                want[d] = np.minimum(want[d], np.asarray(seg))
        got = np.asarray(out).reshape(D, R)
        assert np.array_equal(got, want), np.abs(got - want).max()
        print('OK')
    """)
    assert "OK" in out


def test_model_train_step_dp_tp_matches_single_device():
    """One train step on a (2 data × 2 model) mesh == single-device step."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.dist.context import make_rules, ShardCtx
        from repro.models.model import build_model
        from repro.train import OptConfig, make_init_state, make_train_step
        from repro.launch.dryrun import state_shardings
        from repro.launch.shapes import batch_shardings

        cfg = get_config('mixtral-8x22b').reduced()
        opt = OptConfig(warmup_steps=1, decay_steps=10)
        rng = np.random.default_rng(0)
        batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32))),
                 'targets': jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)))}

        # single device
        m1 = build_model(cfg)
        s1 = make_init_state(m1, opt)(jax.random.PRNGKey(0))
        st1, met1 = jax.jit(make_train_step(m1, opt))(s1, batch)

        # 2x2 mesh
        mesh = jax.make_mesh((2, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        ctx = make_rules(mesh, cfg)
        m2 = build_model(cfg, ctx)
        s2 = make_init_state(m2, opt)(jax.random.PRNGKey(0))
        sh = state_shardings(jax.eval_shape(lambda: s2), ctx)
        step2 = jax.jit(make_train_step(m2, opt), in_shardings=(sh, None))
        st2, met2 = step2(s2, batch)
        d = abs(float(met1['loss']) - float(met2['loss']))
        assert d < 2e-2, d
        print('OK', float(met1['loss']), float(met2['loss']))
    """)
    assert "OK" in out


def test_ep_modes_agree():
    """a2a EP, replicated EP, and the local path give the same MoE loss."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.dist.context import make_rules
        from repro.models.model import build_model

        cfg = get_config('kimi-k2-1t-a32b').reduced()
        rng = np.random.default_rng(0)
        batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32))),
                 'targets': jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)))}
        m0 = build_model(cfg)
        params = m0.init(jax.random.PRNGKey(0))
        base, _ = jax.jit(m0.loss_fn)(params, batch)
        mesh = jax.make_mesh((2, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        for mode in ('a2a', 'replicated'):
            ctx = make_rules(mesh, cfg, ep_mode=mode)
            m = build_model(cfg, ctx)
            loss, _ = jax.jit(m.loss_fn)(params, batch)
            d = abs(float(loss) - float(base))
            assert d < 2e-2, (mode, float(loss), float(base))
        print('OK', float(base))
    """)
    assert "OK" in out


def test_elastic_checkpoint_resharding():
    """Save on a 4-device mesh, restore on 8 devices (different sharding)."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.checkpoint import CheckpointManager

        mesh4 = jax.make_mesh((4,), ('data',),
                              axis_types=(jax.sharding.AxisType.Auto,))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh4, P('data')))
        with tempfile.TemporaryDirectory() as td:
            ck = CheckpointManager(td)
            ck.save(1, {'x': x}, sync=True)
            mesh8 = jax.make_mesh((8,), ('data',),
                                  axis_types=(jax.sharding.AxisType.Auto,))
            sh8 = {'x': NamedSharding(mesh8, P('data'))}
            restored, step = ck.restore({'x': jax.eval_shape(lambda: x)},
                                        shardings=sh8)
            assert restored['x'].sharding.num_devices == 8
            np.testing.assert_array_equal(np.asarray(restored['x']),
                                          np.asarray(x))
        print('OK')
    """)
    assert "OK" in out


def test_serve_2d_expert_layout_matches():
    """Serve-time 2-D MoE layout (EP over data + ff-TP over model) == local."""
    out = run_with_devices("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.dist.context import make_rules
        from repro.models.model import build_model

        # f32 so the comparison is exact (bf16 adds reduction-order ulps)
        cfg = dataclasses.replace(get_config('kimi-k2-1t-a32b').reduced(),
                                  dtype='float32')
        rng = np.random.default_rng(0)
        B, S = 4, 16
        toks = rng.integers(0, cfg.vocab_size, (B, S))
        m0 = build_model(cfg, remat=False)
        params = m0.init(jax.random.PRNGKey(0))
        x, positions = m0._embed_inputs(params, {'tokens': jnp.asarray(toks)})
        h, _, _ = m0._run_groups(params, x, positions)
        ref = m0._logits(params, h)

        mesh = jax.make_mesh((2, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        ctx = make_rules(mesh, cfg, serve_fsdp=False)
        assert ctx.rules['experts'] == 'data', ctx.rules['experts']
        m2 = build_model(cfg, ctx, remat=False)
        x2, pos2 = m2._embed_inputs(params, {'tokens': jnp.asarray(toks)})
        h2, _, _ = m2._run_groups(params, x2, pos2)
        got = m2._logits(params, h2)
        d = float(jnp.abs(got - ref).max())
        assert d < 1e-4, d
        print('OK', d)
    """)
    assert "OK" in out
