"""The benchmark harness on the CPU at tiny scale.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench

The generator, the byte model, the trace reduction (on a trace recorded on
a v5e, ``bench/testdata``) and the answer check: every mix's answer matches
the reference through the harness's own entry points, and the check reads
``correct: false`` for the bfloat16 control and for each fault the timed
path can have on one chip.  A min-plus application written to a file of
its own, on a weighted graph, goes through the same run and checks.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import graph500  # noqa: E402
import oracle  # noqa: E402
import roofline  # noqa: E402
import run  # noqa: E402
import xtrace  # noqa: E402

from repro.core.cache import CompressedShardCache  # noqa: E402
from repro.core.engine import VSWEngine  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CPU_PEAKS = {"hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}
TESTDATA = sorted((BENCH / "testdata").glob("*.xplane.pb"))


# ---------------------------------------------------------------------------
# generator
# ---------------------------------------------------------------------------
def test_generator_is_deterministic_per_seed():
    a = graph500.kronecker_edges(5, 8, 4, 0.57, 0.19, 0.19)
    b = graph500.kronecker_edges(5, 8, 4, 0.57, 0.19, 0.19)
    c = graph500.kronecker_edges(6, 8, 4, 0.57, 0.19, 0.19)
    assert a[0].size == 2 * 4 << 8 and a[0].dtype == np.int32
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert 0 <= a[0].min() and max(a[0].max(), a[1].max()) < 1 << 8


def test_seeds_past_32_bits_differ():
    lo = graph500.kronecker_edges(3, 8, 4, 0.57, 0.19, 0.19)
    hi = graph500.kronecker_edges(3 + (1 << 32), 8, 4, 0.57, 0.19, 0.19)
    assert not np.array_equal(lo[0], hi[0])
    with pytest.raises(ValueError):
        graph500.seed_key(-1)


def test_quadrant_frequencies_match_abcd():
    m = 1 << 18
    a, b, c = 0.57, 0.19, 0.19
    ii, jj = graph500.quadrant_bits(jax.random.key(0), scale=1, m=m,
                                    a=a, b=b, c=c)
    q = np.asarray(ii) * 2 + np.asarray(jj)
    freq = np.bincount(q, minlength=4) / m
    want = np.array([a, b, c, 1 - a - b - c])
    sigma = np.sqrt(want * (1 - want) / m)
    assert np.all(np.abs(freq - want) < 5 * sigma), (freq, want)


def test_undirected_graph_holds_each_edge_both_ways():
    config = {"scale": 8, "edge_factor": 4, "a": 0.57, "b": 0.19, "c": 0.19,
              "directed": False, "weighted": False}
    src, dst, weights = graph500.config_arcs(config, 5)
    n, m = 1 << 8, 4 << 8
    assert src.size == dst.size == 2 * m and weights is None
    assert np.array_equal(src[m:], dst[:m]) and np.array_equal(dst[m:],
                                                                src[:m])
    # as a multiset of arcs the graph equals its transpose
    assert np.array_equal(np.sort(src.astype(np.int64) * n + dst),
                          np.sort(dst.astype(np.int64) * n + src))
    with pytest.raises(ValueError):
        graph500.config_arcs(dict(config, directed=True), 5)


def test_vertex_permutation_is_a_bijection():
    seed, scale, ef = 9, 9, 8
    n, m = 1 << scale, ef << scale
    src, dst = graph500.kronecker_edges(seed, scale, ef, 0.57, 0.19, 0.19)
    src, dst = src[:m], dst[:m]  # the generated edges, before reversal
    k_bits, k_vperm, _ = jax.random.split(graph500.seed_key(seed), 3)
    perm = np.asarray(jax.random.permutation(k_vperm, n))
    assert np.array_equal(np.sort(perm), np.arange(n))
    ii, jj = graph500.quadrant_bits(k_bits, scale=scale, m=m, a=0.57,
                                    b=0.19, c=0.19)
    # a bijection moves degrees between labels and never merges them
    for before, after in ((ii, src), (jj, dst)):
        assert np.array_equal(np.sort(np.bincount(np.asarray(before),
                                                  minlength=n)),
                              np.sort(np.bincount(after, minlength=n)))
    assert np.array_equal(np.bincount(src, minlength=n),
                          np.bincount(perm[np.asarray(ii)], minlength=n))


# ---------------------------------------------------------------------------
# byte model and peaks
# ---------------------------------------------------------------------------
def test_roofline_bytes_on_a_known_shard():
    from repro.core.shards import CSRShard, csr_to_ell

    # 4 destination rows, 10 edges: the count is the algorithm's, so the
    # same shard laid out at two lane widths (different padding) counts alike
    row = np.array([0, 1, 4, 4, 10])
    col = np.array([3, 0, 1, 2, 0, 1, 2, 3, 0, 1], dtype=np.int32)
    csr = CSRShard(shard_id=0, start_vertex=0, end_vertex=4, row=row, col=col,
                   val=None)
    narrow, wide = csr_to_ell(csr, lane=8), csr_to_ell(csr, lane=128)
    assert narrow.cols.size != wide.cols.size
    for ell in (narrow, wide):
        assert roofline.spmv_bytes(ell.nnz, 4, 1) == 10 * (4 + 4) + 4 * 8
        assert roofline.spmv_bytes(ell.nnz, 4, 16) == 10 * (4 + 64) + 4 * 128
    assert roofline.spmv_bytes(10, 4, 1, edge_value=True) == 10 * 12 + 32
    assert roofline.spmv_ops(10, 16) == 160
    peak = {"hbm_bytes_per_s": 1e3, "flops_per_s": 1e9}
    assert roofline.least_seconds(112, 10, peak) == (0.112, "memory")


def test_unknown_device_has_no_peaks():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def _synthetic_trace():
    ms = 1e6
    gather = "%gather.7 = f32[8]{0:T(8)} gather(f32[4]{0} %x, s32[8] %i)"
    ops = [("%fusion = f32[8]{0:T(1024)S(1)} fusion(f32[4]{0} %x), "
            "kind=kCustom, calls=%fused_computation", 0 * ms, 2 * ms),
           (gather, 1 * ms, 3 * ms),
           ("%copy-start = (f32[4]{0:T(1024)}, u32[]{:S(2)}) copy-start("
            "f32[4]{0} %x)", 5 * ms, 6 * ms),
           (gather, 9 * ms, 12 * ms)]
    modules = [("jit_shard_step(3)", 0 * ms, 3 * ms),
               ("jit_changed_fn(4)", 5 * ms, 6 * ms),
               ("jit_shard_step(3)", 9 * ms, 12 * ms)]
    host = [("fetch", 2.5 * ms, 5 * ms), ("decode", 3 * ms, 4.5 * ms),
            ("stage", 6 * ms, 9 * ms)]
    return xtrace.Trace(window=(0.0, 10 * ms),
                        device_ops={"/device:TPU:0": ops},
                        device_modules={"/device:TPU:0": modules},
                        host=host)


def test_reduction_on_a_synthetic_trace():
    tr = _synthetic_trace()
    assert tr.window_s == pytest.approx(0.010)
    # [0,3] + [5,6] + [9,10] clipped to the window
    assert xtrace.busy_seconds(tr) == pytest.approx(0.005)
    assert xtrace.module_seconds(tr, "shard_step") == pytest.approx(0.004)
    assert xtrace.module_seconds(tr, "no_such_module") is None
    ops = dict(xtrace.top_ops(tr))
    assert ops == pytest.approx({"shard_step/gather.7 gather": 0.003,
                                 "shard_step/fusion fusion kCustom": 0.002,
                                 "changed_fn/copy-start copy-start": 0.001})
    gaps = dict(xtrace.idle_gaps(tr))
    # gap [3,5]: decode covers 1.5 ms, fetch 2 ms -> fetch; [6,9] -> stage
    assert gaps == pytest.approx({"fetch": 0.002, "stage": 0.003})


def _traced_run(trace) -> "run.Run":
    return run.Run(setup_s=1.0, window_s=0.01, sweeps=2, units=2,
                   work_edges=2000, columns=1,
                   num_edges=1000, num_vertices=100, edge_value=False,
                   history=[SimpleNamespace(ell_arcs=1000,
                                            shards_processed=2,
                                            shards_skipped=0)] * 2,
                   cache_delta={},
                   peaks={"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12},
                   trace=trace)


def test_spmv_roofline_reads_the_shard_step_modules():
    read = run.metric_reader("spmv_roofline")
    tr = _synthetic_trace()
    # 2 full sweeps x (1000 x 8 + 100 x 8) bytes at 1 GB/s over 4 ms of
    # modules
    assert read(_traced_run(tr)) == pytest.approx(100 * 17.6e-6 / 0.004)
    assert read(_traced_run(None)) is None


def test_spmv_roofline_fails_loudly_without_its_modules():
    tr = _synthetic_trace()
    for plane, mods in tr.device_modules.items():
        tr.device_modules[plane] = [(name.replace("shard_step", "step2"), s,
                                     e) for name, s, e in mods]
    with pytest.raises(ValueError, match="shard_step"):
        run.metric_reader("spmv_roofline")(_traced_run(tr))


@pytest.mark.skipif(not TESTDATA, reason="no recorded trace")
def test_reduction_on_a_chip_trace():
    tr = xtrace.load(TESTDATA[0])
    assert list(tr.device_ops) == ["/device:TPU:0"]
    events = tr.device_ops["/device:TPU:0"]
    # busy time by a different method: a timeline at 100 ns resolution
    lo, hi = tr.window
    step = 100.0
    marks = np.zeros(int((hi - lo) / step) + 1, np.int32)
    for _, s, e in events:
        a = int(np.ceil((max(s, lo) - lo) / step))
        b = int(np.ceil((min(e, hi) - lo) / step))
        if b > a:
            marks[a] += 1
            marks[b] -= 1
    busy_ref = np.count_nonzero(np.cumsum(marks) > 0) * step * 1e-9
    busy = xtrace.busy_seconds(tr)
    assert busy == pytest.approx(busy_ref, abs=1e-6 + 1e-3 * busy)
    assert 0 < busy < tr.window_s
    step_s = xtrace.module_seconds(tr, "shard_step")
    assert step_s is not None and 0 < step_s <= tr.window_s
    ops = xtrace.top_ops(tr)
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(s for _, s in ops) <= busy * 1.0001
    gaps = xtrace.idle_gaps(tr)
    assert 0 < len(gaps) <= 10
    assert sum(s for _, s in gaps) <= tr.window_s - busy + 1e-6


# ---------------------------------------------------------------------------
# the contract's file and the data files it names
# ---------------------------------------------------------------------------
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_existing_files():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    for c in SPEC["configs"]:
        assert _NAME.match(c["name"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and "assumed" in cfg
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in SPEC["workloads"]:
        _, _, cfg, mix = run.load_cell(w["name"])
        # four chips only for a configuration that shards over four devices
        assert _NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["chips"] == 1 or cfg["session"]["num_devices"] == 4
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert callable(run.app_runner(mix["app"]))
        assert mix["limits"]["max_rel_err"] > 0
        e2e = run.cell_metrics(SPEC, w["name"], trace=False)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.cell_metrics(SPEC, w["name"], trace=True)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert _NAME.match(m["name"])
        assert callable(run.metric_reader(m["name"]))
    assert SPEC["end_to_end"][-1]["name"] == "setup_s"


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120)
    assert proc.returncode == 2
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr


# ---------------------------------------------------------------------------
# whole runs on the CPU at tiny scale
# ---------------------------------------------------------------------------
def _tiny_run(tmp_path, monkeypatch, traffic: str | dict, **kw) -> dict:
    """One run of the mix ``traffic`` (a mix file's name, or the mix itself)
    on the first cell's configuration cut to a scale-10 graph, several
    shards, an edge cache smaller than the graph (disk reads and eviction
    run); weighted where the mix's application reads edge values."""
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    _, cell, config, _ = run.load_cell(SPEC["workloads"][0]["name"])
    mix = traffic if isinstance(traffic, dict) else json.loads(
        (BENCH / "mixes" / f"{traffic}.json").read_text())
    weighted = run.app_runner(mix["app"]).edge_value
    config = dict(config, scale=10, weighted=weighted)
    config["preprocess"] = dict(config["preprocess"],
                                threshold_edge_num=1 << 12)
    config["session"] = dict(config["session"], cache_budget_bytes=1 << 17)
    return run.run_cell(SPEC, cell, config, mix, seed=(1 << 31) + 3,
                        seconds=0.2, trace=False, require_tpu=False,
                        peaks=CPU_PEAKS, workdir=tmp_path / "work", **kw)


# every mix file, those no cell uses yet too, so a later cell can add one
# as data alone
MIXES = sorted(p.stem for p in (BENCH / "mixes").glob("*.json"))


@pytest.mark.parametrize("traffic", MIXES)
def test_mix_answer_matches_the_reference(tmp_path, monkeypatch, capsys,
                                          traffic):
    res = _tiny_run(tmp_path, monkeypatch, traffic)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for check in res["checks"].values():
        assert check["value"] <= check["limit"]
    assert set(res["metrics"]) == {"edges_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert not (tmp_path / "work").exists()  # the run cleans up
    lines = capsys.readouterr().out.splitlines()
    window = json.loads(next(ln for ln in lines
                             if ln.startswith("bench window "))[13:])
    assert window["compiles"] == 0 and window["sweeps"] >= 1
    assert window["padded_slots_per_edge"] > 1


@pytest.mark.parametrize("traffic", MIXES)
def test_bf16_control_is_not_correct(tmp_path, monkeypatch, traffic):
    res = _tiny_run(tmp_path, monkeypatch, traffic, control="bf16")
    assert res["correct"] is False and res["failed"] > 0


def _unchanged_state(orig):
    def sweep(self, x, src, *args):
        _dst, changed = orig(self, x, src, *args)
        return src, changed
    return sweep


def _half_the_shards(orig):
    def schedule(self, active_ids, active_ratio):
        keep, selective = orig(self, active_ids, active_ratio)
        return keep[::2], selective
    return schedule


def _altered_answer(orig):
    def sweep(self, *args):
        dst, changed = orig(self, *args)
        return dst.at[3].multiply(1.001), changed
    return sweep


# the faults a one-chip cell can have, as (engine method, wrapper); the
# exchange between chips has no place in it
FAULTS = {"state_unchanged": ("_sweep", _unchanged_state),
          "half_the_shards": ("_schedule", _half_the_shards),
          "answer_altered": ("_sweep", _altered_answer)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("traffic", MIXES)
def test_fault_in_the_timed_path_is_not_correct(tmp_path, monkeypatch,
                                                traffic, fault):
    method, wrap = FAULTS[fault]
    monkeypatch.setattr(VSWEngine, method, wrap(getattr(VSWEngine, method)))
    res = _tiny_run(tmp_path, monkeypatch, traffic)
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > \
        res["checks"]["max_rel_err"]["limit"]


def test_cache_over_its_budget_is_not_correct(tmp_path, monkeypatch):
    """The edge cache's budget is a guarantee of the configuration: a cache
    that keeps eight times the budget gives right answers, and fails."""
    orig = CompressedShardCache.__init__

    def init(self, store, *args, budget_bytes, **kw):
        orig(self, store, *args, budget_bytes=8 * budget_bytes, **kw)

    monkeypatch.setattr(CompressedShardCache, "__init__", init)
    res = _tiny_run(tmp_path, monkeypatch, MIXES[0])
    check = res["checks"]["cache_peak_bytes"]
    assert res["failed"] == 0 and res["correct"] is False
    assert check["value"] > check["limit"]


# ---------------------------------------------------------------------------
# applications found by name
# ---------------------------------------------------------------------------
def test_apps_are_found_by_name():
    app = run.app_runner("pagerank")
    assert app.edge_value is False and callable(app.call)
    with pytest.raises(FileNotFoundError, match="no_such_app"):
        run.app_runner("no_such_app")


# A min-plus application that lives outside ``bench/apps``, as a later one
# is added: SSSP from roots drawn from the seed, one search a unit, against
# a plain Dijkstra over the weighted edge list.
MINPLUS = '''
import heapq

import numpy as np


class App:
    columns = 1
    edge_value = True

    def __init__(self, mix, session, seed, out_deg):
        self.session = session
        pool = np.flatnonzero(out_deg > 0)
        rng = np.random.default_rng(seed)
        self.roots = [int(v) for v in rng.choice(pool, size=mix["roots"],
                                                 replace=False)]

    def _root(self, i):
        return self.roots[i % len(self.roots)]

    def call(self, units):
        values, history, seconds = [], [], []
        for i in range(units):
            r = self.session.run("sssp", source=self._root(i),
                                 max_iters=64)
            values.append(r.values)
            history += r.history
            seconds.append(sum(h.seconds for h in r.history))
        return np.stack(values, 1), units, history, seconds

    def reference(self, graph, units, rounding=None):
        rnd = rounding or (lambda a: a)
        out = [[] for _ in range(graph.n)]
        for u, v, w in zip(graph.src, graph.dst, graph.weights):
            out[u].append((v, float(w)))
        cols = []
        for i in range(units):
            d = np.full(graph.n, np.inf)
            d[self._root(i)] = 0.0
            heap = [(0.0, self._root(i))]
            while heap:
                du, u = heapq.heappop(heap)
                if du > d[u]:
                    continue
                for v, w in out[u]:
                    dv = float(rnd(np.array([du + w]))[0])
                    if dv < d[v]:
                        d[v] = dv
                        heapq.heappush(heap, (dv, v))
            cols.append(d)
        return np.stack(cols, 1)

    def work_edges(self, graph, units):
        return graph.num_arcs * units
'''
MINPLUS_MIX = {"app": "minplus", "roots": 4, "warmup_units": 1,
               "limits": {"max_rel_err": 1e-4}}


def _minplus_run(tmp_path, monkeypatch, **kw) -> dict:
    apps = tmp_path / "apps"
    apps.mkdir()
    (apps / "minplus.py").write_text(MINPLUS)
    monkeypatch.setattr(run, "APPS", apps)
    return _tiny_run(tmp_path, monkeypatch, MINPLUS_MIX, **kw)


def test_weighted_minplus_app_from_its_own_file_is_correct(tmp_path,
                                                          monkeypatch,
                                                          capsys):
    res = _minplus_run(tmp_path, monkeypatch)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % (1 << 10) == 0 and res["attempted"] > 0
    assert res["checks"]["max_rel_err"]["value"] <= 1e-4
    lines = capsys.readouterr().out.splitlines()
    window = json.loads(next(ln for ln in lines
                             if ln.startswith("bench window "))[13:])
    # searches converge: later sweeps skip shards
    assert window["compiles"] == 0 and window["sweeps"] > window["units"]
    assert window["shards_skipped_share"] > 0
    assert 0 < window["arcs_skipped_share"] < 1


def test_weighted_minplus_bf16_control_is_not_correct(tmp_path, monkeypatch):
    res = _minplus_run(tmp_path, monkeypatch, control="bf16")
    assert res["correct"] is False and res["failed"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_weighted_minplus_fault_is_not_correct(tmp_path, monkeypatch, fault):
    method, wrap = FAULTS[fault]
    monkeypatch.setattr(VSWEngine, method, wrap(getattr(VSWEngine, method)))
    res = _minplus_run(tmp_path, monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > \
        res["checks"]["max_rel_err"]["limit"]


# ---------------------------------------------------------------------------
# what the readers count
# ---------------------------------------------------------------------------
def test_rel_err_reads_unreached_as_exact_and_nan_as_wrong():
    inf, nan = np.inf, np.nan
    worst = np.finfo(np.float64).max  # nan_to_num's reading of inf
    want = np.array([[inf, 2.0], [inf, 2.0], [2.0, 2.0], [0.0, 0.0],
                     [inf, 1.0]])
    got = np.array([[inf, inf], [3.0, nan], [2.0, 2.0], [0.0, 1e-30],
                    [nan, -inf]])
    err = oracle.rel_err(got, want)
    assert err[0, 0] == 0 and err[2, 0] == 0 and err[3, 0] == 0
    tiny = float(np.finfo(np.float32).tiny)  # a reference 0 compares absolutely
    assert err[2, 1] == 0 and err[3, 1] == pytest.approx(1e-30 / tiny)
    # finite or NaN where the reference is inf; inf, NaN or -inf where it
    # is finite
    for v, k in ((1, 0), (4, 0), (0, 1), (1, 1), (4, 1)):
        assert err[v, k] >= worst, (v, k)


def test_spmv_roofline_counts_only_the_shards_stepped():
    """A converging window over two shards of 70 and 30 vertices: one full
    sweep (1000 arcs) and one that skipped shard 0 and stepped shard 1's
    250 arcs; the vertex traffic is that of the intervals stepped."""
    read = run.metric_reader("spmv_roofline")
    r = _traced_run(_synthetic_trace())
    full = read(r)
    ms = 1e6
    r.trace.host += [("graphmp.sweep", 0.5 * ms, 4 * ms),
                     ("graphmp.step", 1 * ms, 2 * ms),
                     ("graphmp.step", 2 * ms, 3 * ms),
                     ("graphmp.sweep", 5 * ms, 9.5 * ms),
                     ("graphmp.step", 6 * ms, 7 * ms)]
    r.trace.host_args = [{}] * 3 + [{"sweep": 0}, {"sweep": 0, "shard": 0},
                                    {"sweep": 0, "shard": 1}, {"sweep": 1},
                                    {"sweep": 1, "shard": 1}]
    r.shard_vertices = [70, 30]
    r.history = [SimpleNamespace(ell_arcs=1000, shards_processed=2,
                                 shards_skipped=0),
                 SimpleNamespace(ell_arcs=250, shards_processed=1,
                                 shards_skipped=1)]
    # (1250 x 8 + (100 + 30) x 8) bytes at 1 GB/s over 4 ms of modules
    assert read(r) == pytest.approx(100 * 11.04e-6 / 0.004)
    assert read(r) < full
    # a sweep that skipped shards with step spans that name none is an error
    r.trace.host_args = [{}] * len(r.trace.host)
    with pytest.raises(ValueError, match="graphmp.step"):
        read(r)


def test_edges_per_s_is_the_apps_work_over_the_window():
    r = _traced_run(None)
    assert run.metric_reader("edges_per_s")(r) == pytest.approx(2000 / 0.01)
