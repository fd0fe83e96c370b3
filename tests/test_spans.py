"""Spans at the engine's layer boundaries (``repro.core.spans``).

A session run under ``jax.profiler.trace`` on the CPU writes every
``graphmp.*`` span into the profiler's host plane, with ``sweep`` and
``shard`` arguments; the counters the spans feed (``stall_seconds``,
``fetch_seconds``, ``stage_seconds``, ``decompress_seconds``, the sweep's
``seconds``) equal the spans' durations, and ``h2d_bytes`` counts the
bytes of every staged shard.
"""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.pipeline import ShardPipeline
from repro.core.shards import ELLShard, store_slices
from repro.core.spans import Counters, span

SPANS = ("graphmp.sweep", "graphmp.schedule", "graphmp.gather",
         "graphmp.wait", "graphmp.step", "graphmp.changed", "graphmp.fetch",
         "graphmp.read", "graphmp.decode", "graphmp.compress",
         "graphmp.stage")
SWEEPS = 3


@dataclasses.dataclass
class _Spans:
    """``graphmp.*`` events of one trace: name -> [(thread line, start_ns,
    end_ns, {arg: value})]."""
    by_name: dict

    def durations(self, name: str) -> float:
        return sum(e - s for _, s, e, _ in self.by_name[name]) * 1e-9


def _read_spans(trace_dir: Path) -> _Spans:
    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    by_name = defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("graphmp."):
                    by_name[ev.name].append(
                        ((plane.name, i), ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return _Spans(dict(by_name))


def _traced_run(graph_store, tmp_path, depth: int, **config):
    """PageRank for ``SWEEPS`` sweeps under the profiler, on an adaptive
    cache too small for a hot tier: every shard is read and compressed on
    the first sweep and decoded from the cold tier after."""
    from repro.session import GraphSession

    sess = GraphSession(graph_store, cache_mode="adaptive",
                        cache_budget_bytes=1 << 24, cache_hot_fraction=1e-6,
                        prefetch_depth=depth, **config)
    sess.run("pagerank", max_iters=1)  # compile outside the trace
    sess.cache.clear()
    trace_dir = tmp_path / f"trace{depth}"
    with jax.profiler.trace(str(trace_dir)):
        res = sess.run("pagerank", max_iters=SWEEPS)
    return sess, res, _read_spans(trace_dir)


@pytest.fixture(scope="module")
def traced(graph_store, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    return {d: _traced_run(graph_store, tmp, d) for d in (0, 2)}


# ---------------------------------------------------------------------------
# the helper
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Stats(Counters):
    seconds: float = 0.0


def test_span_feeds_its_counter_and_keeps_its_duration():
    stats = _Stats()
    with span("graphmp.test", stats, "seconds", sweep=1, shard=2) as s:
        time.sleep(0.01)
    assert s.seconds >= 0.01 and stats.seconds == s.seconds
    with span("graphmp.test") as bare:  # no counter: the duration alone
        pass
    assert bare.seconds >= 0.0 and stats.seconds == s.seconds


def test_span_feeds_its_counter_when_the_block_raises():
    stats = _Stats()
    with pytest.raises(KeyError):
        with span("graphmp.test", stats, "seconds"):
            raise KeyError("x")
    assert stats.seconds > 0.0


@dataclasses.dataclass
class _Shared(Counters):
    n: int = 0
    seconds: float = 0.0


def test_counters_lose_no_update_across_threads():
    """Producer and consumer threads charge one stats object: with a short
    switch interval, many threads' bumps all land."""
    import sys
    import threading

    stats = _Shared()
    threads_n, per = 16, 2000

    def work():
        for _ in range(per):
            stats.bump(n=1)
            with span("graphmp.test", stats, "seconds"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert stats.n == threads_n * per
    assert stats.seconds > 0.0


# ---------------------------------------------------------------------------
# a traced session
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [0, 2])
def test_traced_run_writes_every_span(traced, depth):
    _sess, res, spans = traced[depth]
    assert set(SPANS) <= set(spans.by_name)
    assert len(spans.by_name["graphmp.sweep"]) == res.iterations == SWEEPS
    sweeps = sorted(a["sweep"] for *_, a in spans.by_name["graphmp.sweep"])
    assert sweeps == list(range(SWEEPS))
    for name in ("graphmp.fetch", "graphmp.wait", "graphmp.step"):
        assert all("sweep" in a for *_, a in spans.by_name[name])


@pytest.mark.parametrize("depth", [0, 2])
def test_stage_and_decode_nest_inside_fetch(traced, depth):
    _sess, _res, spans = traced[depth]
    fetches = spans.by_name["graphmp.fetch"]
    for name in ("graphmp.stage", "graphmp.decode", "graphmp.read",
                 "graphmp.compress"):
        for line, s, e, args in spans.by_name[name]:
            assert any(fl == line and fs <= s and e <= fe
                       and fa["shard"] == args["shard"]
                       for fl, fs, fe, fa in fetches), (name, args)
    waits = spans.by_name["graphmp.wait"]
    on_engine = {line for line, *_ in waits}
    fetch_lines = {line for line, *_ in fetches}
    if depth == 0:  # the fetch runs inline, inside the engine's wait
        assert fetch_lines == on_engine
        for line, s, e, args in fetches:
            assert any(wl == line and ws <= s and e <= we
                       and wa.get("shard") == args["shard"]
                       for wl, ws, we, wa in waits)
    else:  # on the prefetch thread
        assert not fetch_lines & on_engine


@pytest.mark.parametrize("depth", [0, 2])
def test_fetch_once_per_scheduled_shard_per_sweep(traced, depth):
    sess, res, spans = traced[depth]
    per_sweep = defaultdict(list)
    for *_, args in spans.by_name["graphmp.fetch"]:
        per_sweep[args["sweep"]].append(args["shard"])
    P = sess.store.num_shards
    assert sorted(per_sweep) == [h.iteration for h in res.history]
    for h in res.history:
        assert h.shards_processed == P
        assert per_sweep[h.iteration] == list(range(P))  # schedule order


@pytest.mark.parametrize("depth", [0, 2])
def test_counters_equal_their_spans(traced, depth):
    """Each counter is the sum of its span's durations: stall is the wait
    on the queue (at depth 0 the whole inline fetch), fetch covers
    ``_produce``, stage the staging, decode the cold-tier codec calls."""
    sess, res, spans = traced[depth]
    hist = res.history
    n = sum(len(v) for v in spans.by_name.values())
    tol = 1e-3 + 20e-6 * n  # two clocks, each span read apart
    for field, name in (("stall_seconds", "graphmp.wait"),
                        ("fetch_seconds", "graphmp.fetch"),
                        ("stage_seconds", "graphmp.stage")):
        got = sum(getattr(h, field) for h in hist)
        assert got == pytest.approx(spans.durations(name), abs=tol), field
    assert sum(h.seconds for h in hist) == pytest.approx(
        spans.durations("graphmp.sweep"), abs=tol)
    assert sess.cache.stats.decompress_seconds == pytest.approx(
        spans.durations("graphmp.decode"), abs=tol)
    for h in hist:
        assert 0.0 < h.stage_seconds <= h.fetch_seconds
        assert h.fetch_seconds > 0.0
        if depth == 0:  # the consumer is stalled for the whole fetch
            assert h.stall_seconds >= h.fetch_seconds


class _SlowFetch:
    """A fetch that takes ``delay`` seconds per shard."""

    def __init__(self, delay: float):
        self.delay = delay

    def __call__(self, p: int) -> ELLShard:
        time.sleep(self.delay)
        cols = np.full((8, 128), -1, dtype=np.int32)
        return ELLShard(shard_id=p, start_vertex=0, end_vertex=8, nnz=0,
                        cols=cols, vals=np.zeros((8, 128), np.float32),
                        row_map=np.full(128, -1, np.int32),
                        slice_ptr=np.zeros(2, np.int32))


@pytest.mark.parametrize("depth", [0, 2])
def test_stall_and_fetch_keep_their_start_and_end(depth):
    """At depth 0 the consumer stalls for every fetch; with prefetch a
    consumer slower than the producer stalls only for the first shard."""
    delay, n = 0.05, 6
    pipe = ShardPipeline(_SlowFetch(delay), depth=depth,
                         stage=lambda s: s.cols, h2d=lambda c: c.nbytes)
    for _ in pipe.stream(list(range(n)), sweep=4):
        time.sleep(2 * delay)  # the consumer's own work
    st = pipe.stats
    assert st.fetch_seconds >= n * delay
    assert 0.0 <= st.stage_seconds < st.fetch_seconds
    assert st.h2d_bytes == n * 8 * 128 * 4
    if depth == 0:
        assert st.stall_seconds >= st.fetch_seconds
    else:
        assert st.stall_seconds < 2.5 * delay


# ---------------------------------------------------------------------------
# bytes staged to the device
# ---------------------------------------------------------------------------
def _shard_bytes(store, p: int) -> int:
    # cols + vals + the slice of each row group + row_map padded to the
    # store's slice count, and the two float32 dequantization parameters
    shard = store.read_shard(p)
    row_map = shard.staged_row_map(store_slices(store.properties["shards"]))
    return (shard.cols.nbytes + shard.vals.nbytes
            + shard.group_slices().nbytes + row_map.nbytes + 8)


@pytest.mark.parametrize("depth", [0, 2])
def test_h2d_bytes_count_every_staged_shard(traced, depth):
    sess, res, _spans = traced[depth]
    want = sum(_shard_bytes(sess.store, p)
               for p in range(sess.store.num_shards))
    for h in res.history:
        assert h.h2d_bytes == want


@pytest.mark.parametrize("depth", [0, 2])
def test_ell_slots_and_arcs_count_every_staged_shard(traced, depth):
    """Each sweep counts the ELL slots it staged and the edges they hold."""
    sess, res, _spans = traced[depth]
    shards = [sess.store.read_shard(p) for p in range(sess.store.num_shards)]
    for h in res.history:
        assert h.ell_slots == sum(s.cols.size for s in shards)
        assert h.ell_arcs == sum(s.nnz for s in shards) \
            == sess.store.num_edges
        assert h.ell_slots >= h.ell_arcs


def test_h2d_bytes_follow_the_schedule(graph_store, monkeypatch):
    """A sweep that schedules fewer shards stages fewer bytes: SSSP from
    one source runs selective sweeps."""
    from repro.core.engine import VSWEngine
    from repro.session import GraphSession

    schedules = []
    orig = VSWEngine._schedule

    def record(self, active_ids, active_ratio):
        keep, selective = orig(self, active_ids, active_ratio)
        schedules.append(keep)
        return keep, selective

    monkeypatch.setattr(VSWEngine, "_schedule", record)
    sess = GraphSession(graph_store, cache_mode=1, selective_threshold=0.5)
    res = sess.run("sssp", source=0, max_iters=4)
    sizes = [_shard_bytes(sess.store, p)
             for p in range(sess.store.num_shards)]
    assert any(h.shards_skipped for h in res.history)
    for h, keep in zip(res.history, schedules):
        assert h.h2d_bytes == sum(sizes[p] for p in keep)


def test_each_run_is_one_span_naming_its_sources(graph_store, tmp_path):
    """Searches through one shared engine: one ``graphmp.run`` span a
    search, naming its app and source and holding its sweeps; a batched
    run names all its sources."""
    from repro.session import GraphSession

    sess = GraphSession(graph_store)
    sess.run("sssp", source=0, max_iters=2)  # compile outside the trace
    sess.run_batch("sssp", sources=[0, 1], max_iters=2)
    sources = [1, 2, 3]
    with jax.profiler.trace(str(tmp_path / "t")):
        results = [sess.run("sssp", source=s) for s in sources]
        sess.run_batch("sssp", sources=[4, 5], max_iters=2)
    sess.close()
    spans = _read_spans(tmp_path / "t")
    runs = sorted(spans.by_name["graphmp.run"], key=lambda ev: ev[1])
    assert [(a["app"], a["sources"]) for *_, a in runs] == [
        ("sssp", 1), ("sssp", 2), ("sssp", 3), ("sssp_multi", "4 5")]
    sweeps = spans.by_name["graphmp.sweep"]
    for (line, s, e, _), res in zip(runs, results):
        inside = [sw for sw in sweeps if s <= sw[1] and sw[2] <= e]
        assert all(sw[0] == line for sw in inside)
        # the last sweep span of a converged run may find nothing to step
        assert res.iterations <= len(inside) <= res.iterations + 1


def test_attach_hub_exports_stage_seconds_and_h2d_bytes(graph_store):
    from repro.obs import MetricsHub
    from repro.session import GraphSession

    sess = GraphSession(graph_store, cache_mode=1, prefetch_depth=1)
    hub = sess.attach_hub(MetricsHub())
    res = sess.run("pagerank", max_iters=2)
    assert hub.counter("session.engine.h2d_bytes").value == sum(
        h.h2d_bytes for h in res.history) > 0
    assert hub.counter("session.engine.stage_seconds").value == \
        pytest.approx(sum(h.stage_seconds for h in res.history))


def test_sharded_engine_sums_h2d_bytes_over_its_lanes():
    """Two devices: each wave ships one [L, C] slice of every array to each
    device, the wave's largest shard setting L and C, the store's largest
    the slice count S."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = Path(__file__).resolve().parent.parent
    code = textwrap.dedent("""
        import tempfile
        from repro.graph.generate import rmat_edges, materialize
        from repro.graph.storage import write_edge_list
        from repro.graph.preprocess import preprocess_graph
        from repro.core.shards import GROUP_ROWS, store_slices
        from repro.session import GraphSession

        src, dst = materialize(rmat_edges(scale=9, edge_factor=8, seed=7))
        base = tempfile.mkdtemp()
        write_edge_list(base + "/el", [(src, dst)])
        preprocess_graph(base + "/el", base + "/store",
                         threshold_edge_num=2048, ell_max_width=256)
        D = 2
        with GraphSession(base + "/store", num_devices=D,
                          prefetch_depth=1) as s:
            res = s.run("pagerank", max_iters=2)
            eng = s.engine("pagerank")
            P = s.store.num_shards
            scheds = [[p for p in range(P) if eng._owner[p] == d]
                      for d in range(D)]
            want = 0
            for w in range(max(len(x) for x in scheds)):
                sh = [s.store.read_shard(x[w]) for x in scheds if w < len(x)]
                L = max(x.cols.shape[0] for x in sh)
                C = max(x.cols.shape[1] for x in sh)
                S = store_slices(s.store.properties["shards"])
                # cols, vals, slices, row_map, qparams, start, rows: per
                # device
                want += D * (L * C * 8 + L // GROUP_ROWS * 4 + S * C * 4
                             + 8 + 4 + 4)
            for h in res.history:
                assert h.h2d_bytes == want, (h.h2d_bytes, want)
                assert 0.0 < h.stage_seconds <= h.fetch_seconds
            print("ok", want)
    """)
    env = dict(os.environ, PYTHONPATH=str(repo / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    assert r.stdout.startswith("ok")
