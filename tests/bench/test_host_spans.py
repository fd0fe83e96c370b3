"""Device idle time put down to the program's spans, and the staging
counters, as the benchmark reads them (``bench/host_spans.py`` and the
``pipeline_idle_share``, ``engine_idle_share``,
``pipeline_stage_s_per_sweep`` and ``pipeline_h2d_gb_per_sweep`` readers).

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_host_spans.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import host_spans  # noqa: E402
import roofline  # noqa: E402
import run  # noqa: E402
import xtrace  # noqa: E402

IDLE = ("pipeline_idle_share", "engine_idle_share")
COUNTERS = ("pipeline_stage_s_per_sweep", "pipeline_h2d_gb_per_sweep")
# a traced window recorded on a v5e with the spans, and beside it the
# run's counters and metrics as the chip printed them
CHIP = BENCH / "testdata" / "tpu_v5e_pagerank_s14_spans.xplane.pb"


def _run(trace=None, history=(), sweeps=2) -> "run.Run":
    return run.Run(setup_s=1.0, window_s=0.02, sweeps=sweeps, units=sweeps,
                   work_edges=1000 * sweeps, columns=1,
                   num_edges=1000, num_vertices=100, edge_value=False,
                   history=list(history), cache_delta={},
                   peaks={"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12},
                   trace=trace)


def _synthetic_trace(host=None):
    """A 20 ms window; the device busy in [0,3] [5,6] [9,12] [15,16] ms,
    so idle 12 ms."""
    ms = 1e6
    ops = [(f"%op.{i} = f32[8]{{0}} fusion()", s * ms, e * ms)
           for i, (s, e) in enumerate([(0, 3), (5, 6), (9, 12), (15, 16)])]
    if host is None:
        host = [("graphmp.sweep", 0, 20),
                ("graphmp.fetch", 3, 6),        # prefetch thread: no share
                ("graphmp.wait", 3, 5),         # 2 ms idle
                ("graphmp.step", 6, 8),         # 2 ms idle
                ("graphmp.schedule", 7.5, 9.5),  # +1 ms: [8, 9]
                ("graphmp.changed", 12, 13),    # 1 ms
                ("graphmp.wait", 13, 14),       # 1 ms
                ("graphmp.step", 13.5, 14.5),   # +0.5 ms past the wait
                ("graphmp.wait", 19, 25)]       # 1 ms inside the window
    return xtrace.Trace(window=(0.0, 20 * ms),
                        device_ops={"/device:TPU:0": ops},
                        device_modules={"/device:TPU:0": []},
                        host=[(n, s * ms, e * ms) for n, s, e in host])


def _read(name, r):
    return run.metric_reader(name)(r)


def test_idle_split_between_wait_and_engine_spans():
    r = _run(_synthetic_trace())
    assert _read("pipeline_idle_share", r) == pytest.approx(100 * 4 / 20)
    assert _read("engine_idle_share", r) == pytest.approx(100 * 4.5 / 20)
    assert _read("device_idle_share", r) == pytest.approx(100 * 12 / 20)


def test_idle_shares_on_random_traces_match_a_timeline():
    """Against a 1 ns timeline: each share is the idle time under its
    family, waiting first, and the two never sum past the device's idle."""
    rng = np.random.default_rng(5)
    names = sorted(host_spans.WAIT | host_spans.ENGINE) + ["graphmp.fetch"]
    for _ in range(40):
        lo, hi = 0, 1000
        ops = []
        for _ in range(rng.integers(0, 12)):
            s = int(rng.integers(-50, hi))
            ops.append((s, s + int(rng.integers(0, 120))))
        host = [("graphmp.sweep", lo, hi)]
        for _ in range(rng.integers(0, 16)):
            s = int(rng.integers(-50, hi))
            host.append((names[rng.integers(len(names))], s,
                         s + int(rng.integers(0, 150))))
        tr = xtrace.Trace(window=(float(lo), float(hi)),
                          device_ops={"/device:TPU:0": [
                              ("op", float(s), float(e)) for s, e in ops]},
                          device_modules={}, host=[
                              (n, float(s), float(e)) for n, s, e in host])

        def mark(pairs):
            m = np.zeros(hi - lo, bool)
            for s, e in pairs:
                m[max(s, lo):max(min(e, hi), lo)] = True
            return m

        idle = ~mark(ops)
        wait = mark([(s, e) for n, s, e in host if n in host_spans.WAIT])
        eng = mark([(s, e) for n, s, e in host if n in host_spans.ENGINE])
        r = _run(tr)
        p, e = _read("pipeline_idle_share", r), _read("engine_idle_share", r)
        assert p == pytest.approx(100 * np.mean(idle & wait), abs=1e-9)
        assert e == pytest.approx(100 * np.mean(idle & eng & ~wait),
                                  abs=1e-9)
        assert p + e <= _read("device_idle_share", r) + 1e-9


@pytest.mark.parametrize("name", IDLE)
def test_idle_readers_need_a_trace(name):
    assert _read(name, _run(None)) is None


@pytest.mark.parametrize("name", IDLE)
def test_idle_readers_raise_without_a_sweep_span(name):
    host = [("graphmp.wait", 3, 5), ("XlaLinearize", 6, 8)]
    with pytest.raises(ValueError, match="graphmp.sweep"):
        _read(name, _run(_synthetic_trace(host)))


@pytest.mark.parametrize("name", IDLE)
def test_idle_readers_read_nothing_from_a_program_without_spans(
        name, monkeypatch):
    monkeypatch.setattr(host_spans, "program_writes_spans", lambda: False)
    assert _read(name, _run(_synthetic_trace([("XlaLinearize", 6, 8)]))) \
        is None


def test_staging_counters_per_sweep():
    hist = [SimpleNamespace(stage_seconds=0.25, h2d_bytes=3_000_000_000),
            SimpleNamespace(stage_seconds=0.75, h2d_bytes=3_200_000_000)]
    r = _run(history=hist, sweeps=2)
    assert _read("pipeline_stage_s_per_sweep", r) == pytest.approx(0.5)
    assert _read("pipeline_h2d_gb_per_sweep", r) == pytest.approx(3.1)


@pytest.mark.parametrize("name", COUNTERS)
def test_staging_counters_read_nothing_from_a_program_without_them(name):
    old = SimpleNamespace(stall_seconds=1.0, fetch_seconds=2.0)
    assert _read(name, _run(history=[old])) is None


@pytest.mark.parametrize("name", IDLE + COUNTERS)
def test_new_metrics_are_reported_by_every_cell(name):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        assert name in run.cell_metrics(spec, w["name"], trace=True)
        assert name not in run.cell_metrics(spec, w["name"], trace=False)


def test_new_metrics_on_a_chip_trace():
    """A scale-14 out-of-core PageRank window traced on a v5e (prefetch
    depth 2, 17 shards, an 8 MiB edge cache): the span names the TPU
    runtime's profiler kept, and the four metrics as the chip run read
    them."""
    side = json.loads(CHIP.with_suffix("").with_suffix(".json").read_text())
    tr = xtrace.load(CHIP)
    hist = [SimpleNamespace(**h) for h in side["history"]]
    r = run.Run(setup_s=0.0, window_s=side["window_s"],
                sweeps=side["sweeps"], units=side["sweeps"],
                work_edges=side["num_edges"] * side["sweeps"], columns=1,
                num_edges=side["num_edges"],
                num_vertices=side["num_vertices"], edge_value=False,
                history=hist, cache_delta={},
                peaks=roofline.peaks(side["device"]["kind"]), trace=tr)
    names = {ev[0] for ev in tr.host}
    assert {host_spans.SWEEP, "graphmp.fetch", "graphmp.decode",
            "graphmp.stage"} | host_spans.WAIT | host_spans.ENGINE <= names
    got = {n: _read(n, r) for n in IDLE + COUNTERS + ("device_idle_share",)}
    for name, value in got.items():
        assert value == pytest.approx(side["metrics"][name], rel=1e-9)
    assert got["pipeline_idle_share"] > 0 and got["engine_idle_share"] > 0
    assert got["pipeline_idle_share"] + got["engine_idle_share"] <= \
        got["device_idle_share"]
    assert got["pipeline_h2d_gb_per_sweep"] == pytest.approx(
        side["h2d_bytes_per_sweep"] / 1e9, rel=1e-12)
    # the counter and the trace time the same blocks
    lo, hi = tr.window
    stage = [e - s for n, s, e in tr.host
             if n == "graphmp.stage" and lo <= s and e <= hi]
    assert len(stage) == sum(h.shards_processed for h in hist)
    assert got["pipeline_stage_s_per_sweep"] == pytest.approx(
        sum(stage) * 1e-9 / r.sweeps, abs=1e-3 + 20e-6 * len(stage))


def test_stepped_shards_on_a_chip_trace():
    """Every sweep of the traced chip window steps all 17 shards, each once,
    and its ``graphmp.step`` spans name them."""
    side = json.loads(CHIP.with_suffix("").with_suffix(".json").read_text())
    tr = xtrace.load(CHIP)
    groups = host_spans.stepped_shards(tr)
    assert [len(g) for g in groups] == \
        [h["shards_processed"] for h in side["history"]]
    assert all(sorted(g) == list(range(17)) for g in groups)
