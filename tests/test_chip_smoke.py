"""chip_smoke.py's phases on the CPU at scale 10, with interpret kernels.

The script itself refuses to run without a TPU; these tests call its phase
functions directly (forcing the Pallas kernels in interpret mode, K=16
included) so a broken phase or check fails tier-1 before a chip run.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph(cs, tmp_path_factory):
    g = cs.make_graph(str(tmp_path_factory.mktemp("smoke")), 10, 16, 0)
    ref = cs.Reference(g.src, g.dst, g.n)
    return g, ref, cs.pick_landmarks(ref, cs.LANDMARKS, 0)


def test_chip_smoke_refuses_without_tpu(cs, capsys):
    assert cs.main(["--scale", "8"]) == 2
    out = capsys.readouterr().out
    assert '"ok"' not in out  # no result line without an accelerator


def test_chip_smoke_graph_matches_scale(cs, graph):
    g, ref, landmarks = graph
    assert g.n == 1 << 10 and g.src.size == 16 << 10
    assert g.num_shards >= 1 and g.shard_bytes > 0
    assert len(set(landmarks)) == cs.LANDMARKS
    assert landmarks[0] == int(np.argmax(ref.out_deg))


def test_chip_smoke_phases_cpu(cs, graph, monkeypatch):
    g, _, landmarks = graph
    ref = cs.Reference(g.src, g.dst, g.n)  # answers warm on a thread
    monkeypatch.setattr(cs, "SERVICE_DISTINCT", 24)
    monkeypatch.setattr(cs, "SERVICE_REPEATS", 1)
    monkeypatch.setattr(cs, "SERVICE_THREADS", 4)
    # a budget below the graph's size: disk reads and eviction run, as at
    # scale 22 under the default 1 GiB
    records = cs.run_phases(g.graph_dir, ref, landmarks, use_pallas=True,
                            cache_budget_bytes=g.shard_bytes // 2)
    assert [r["phase"] for r in records] == [
        "pagerank", "sssp", "cc", "run_batch_sssp_k16", "service"]
    for r in records:
        assert r["ok"] and r["iterations"] > 0 and r["edges"] > 0
        assert r["dispatch_k1"] == r["dispatch_k16"] \
            == "pallas:interpret:gather+fold"
    assert records[0]["disk_bytes"] > 0
    assert records[-1]["queries"] == 28
    assert records[-1]["memo_hits"] >= 4  # each client's re-ask


def test_chip_smoke_reference_bfs_matches_per_source(cs, graph):
    """The 64-bit packed multi-source BFS equals one plain frontier BFS per
    source, and hop limits truncate it exactly."""
    g, ref, landmarks = graph
    fresh = cs.Reference(g.src, g.dst, g.n)
    fresh.bfs_many(landmarks)
    for s in landmarks[:4]:
        level = np.full(g.n, np.inf, np.float32)
        level[s], frontier, d = 0, {s}, 0
        while frontier:
            d += 1
            nxt = set(g.dst[np.isin(g.src, list(frontier))].tolist())
            nxt = {v for v in nxt if np.isinf(level[v])}
            level[list(nxt)] = d
            frontier = nxt
        assert np.array_equal(fresh.levels(s), level)
        assert np.array_equal(fresh.levels(s, 2),
                              np.where(level <= 2, level, np.inf))


def test_rmat_quadrant_draw_matches_generator_choice():
    """rmat_edges draws each quadrant with three compares; the graph is
    bit-identical to the Generator.choice(4, p=...) formulation."""
    from repro.graph.generate import rmat_edges

    probs = np.array([0.57, 0.19, 0.19, 0.05])
    rng = np.random.default_rng(5)
    src = np.zeros(3000, np.int64)
    dst = np.zeros(3000, np.int64)
    for _ in range(9):
        q = rng.choice(4, size=3000, p=probs)
        src, dst = (src << 1) | (q >> 1), (dst << 1) | (q & 1)
    got_src, got_dst = next(rmat_edges(9, 6, seed=5, chunk=3000))
    assert np.array_equal(got_src, src) and np.array_equal(got_dst, dst)


def test_chip_smoke_check_catches_wrong_answer(cs, graph):
    g, ref, landmarks = graph

    class Fake:
        values = ref.levels(landmarks[0]) + 1.0

    with pytest.raises(cs.SmokeFailure):
        cs._check_answer(ref, "sssp", {"source": landmarks[0]}, Fake())


def test_chip_smoke_multi_device_cpu():
    """--chips 4's phase on four emulated CPU devices: bitwise 4 vs 1."""
    code = textwrap.dedent(f"""
        import importlib.util, sys, tempfile
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(REPO / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = cs
        spec.loader.exec_module(cs)
        g = cs.make_graph(tempfile.mkdtemp(), 9, 16, 1)
        ref = cs.Reference(g.src, g.dst, g.n)
        recs = cs.phase_multi_device(g.graph_dir, ref,
                                     cs.pick_landmarks(ref, 16, 1), 4)
        assert all(r["ok"] for r in recs), recs
        print("OK", [r["phase"] for r in recs])
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "devices4_vs_1" in r.stdout


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and no directory is set in code;
    without it the cache lands at the fixed <checkout>/.jax_cache."""
    import jax

    from repro import compile_cache

    set_dirs = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: (
        set_dirs.append(value) if name == "jax_compilation_cache_dir"
        else None))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert set_dirs == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() == str(REPO / ".jax_cache")
        assert set_dirs == [str(REPO / ".jax_cache")]


def test_import_repro_initialises_no_backend():
    code = ("import repro, repro.compile_cache, repro.session\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "PYTHONPATH": str(REPO / "src")})
    assert r.returncode == 0, r.stderr[-2000:]
