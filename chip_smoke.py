#!/usr/bin/env python3
"""Run GraphMP's main path once on a TPU and check every answer.

    python chip_smoke.py              # one chip: scale-22 RMAT, edge factor 16
    python chip_smoke.py --chips 4    # only the multi-device path, 4 vs 1
    python chip_smoke.py --scale 18   # a smaller rehearsal

One process does everything, through the entry points a user calls:

1. generate a Graph500-style RMAT edge list from ``--seed`` (``rmat_edges``)
   and ``preprocess_graph`` it with the defaults (lane 128, ELL width 512,
   2^20 edges per shard) into a fresh temporary directory;
2. open ``GraphSession(path, prefetch_depth=2)`` with the default 1 GiB
   edge-cache budget — at scale 22 the shards outgrow it, so disk reads,
   the compressed cold tier and eviction all run;
3. run pagerank (10 iterations), sssp from the highest out-degree vertex,
   cc, and ``run_batch("sssp")`` over 16 landmarks, each checked against a
   plain NumPy reference built from the same edge list (pagerank to
   ``PR_RTOL``; distances and component ids exactly; every batched column
   exactly, the hub's column bitwise equal to the solo run);
4. serve 64 queries from 8 client threads through
   ``session.service(max_batch=16)``: 48 distinct (2-hop sssp and bfs, ppr)
   that coalesce into K=16 micro-batches, then 16 repeats answered from the
   service's memo — each answer checked the same way.

Every phase prints one ``phase {json}`` line (seconds, compile seconds,
edges/s, disk bytes, cache hit ratio, SpMV dispatch for K=1 and K=16).  The
last line is ``{"ok": true, "device": {...}}``.  Without a TPU, or when any
phase or check fails, the script exits non-zero and prints no such line.

``--chips 4`` runs only the multi-device path on the same graph:
``GraphSession(num_devices=4)`` for pagerank, sssp and a K=16 ``run_batch``,
each compared bitwise with the ``num_devices=1`` run in the same process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

# the program under test, from this checkout (needs the path above)
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.kernels.spmv.ops import describe_dispatch  # noqa: E402
from repro.session import GraphSession  # noqa: E402

EDGE_FACTOR = 16  # Graph500's
DAMPING = 0.85
PR_ITERS = 10
# Every service micro-batch is a K=16 sweep over all 67M edges, which the
# XLA element gather bounds at ~30 s on one v5e: 2 hops and 2 ppr steps keep
# the service to 6 such sweeps and the whole smoke well inside 1200 s.
PPR_ITERS = 2
SERVICE_HOPS = 2  # service sssp/bfs queries are 2-hop neighbourhoods
# f32 engine sums vs a float64 reference: hub vertices sum ~10^5 in-edge
# terms per iteration, so per-vertex relative error stays well under this
PR_RTOL = 1e-3
PPR_ATOL = 1e-7   # ppr mass far from the seed is ~1e-9; compare it absolutely
LANDMARKS = 16
SERVICE_DISTINCT = 48  # first wave: 16 sssp, 16 bfs, 16 ppr
SERVICE_REPEATS = 2    # per client: re-asks of answered queries (memo hits)
SERVICE_THREADS = 8


class SmokeFailure(AssertionError):
    """A phase produced a wrong answer."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# graph + reference
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class GraphFiles:
    graph_dir: str
    src: np.ndarray       # int32 edge sources, input order
    dst: np.ndarray       # int32 edge destinations
    n: int
    generate_s: float
    preprocess_s: float
    shard_bytes: int
    num_shards: int


def make_graph(workdir: str, scale: int, edge_factor: int,
               seed: int) -> GraphFiles:
    """Generate the RMAT edge list into ``workdir`` and preprocess it."""
    from repro.graph.generate import rmat_edges
    from repro.graph.preprocess import preprocess_graph
    from repro.graph.storage import write_edge_list

    t0 = time.perf_counter()
    chunks = [(s.astype(np.int32), d.astype(np.int32))
              for s, d in rmat_edges(scale, edge_factor, seed=seed)]
    edges_dir = str(Path(workdir) / "edges")
    write_edge_list(edges_dir, chunks, num_vertices=1 << scale)
    generate_s = time.perf_counter() - t0
    src = np.concatenate([c[0] for c in chunks])
    dst = np.concatenate([c[1] for c in chunks])
    del chunks

    t0 = time.perf_counter()
    graph_dir = str(Path(workdir) / "graph")
    store = preprocess_graph(edges_dir, graph_dir)
    preprocess_s = time.perf_counter() - t0
    shutil.rmtree(edges_dir)  # the session reads only the shards
    return GraphFiles(
        graph_dir=graph_dir, src=src, dst=dst, n=1 << scale,
        generate_s=generate_s, preprocess_s=preprocess_s,
        shard_bytes=sum(store.shard_nbytes(p) for p in range(store.num_shards)),
        num_shards=store.num_shards)


class Reference:
    """Plain NumPy answers computed from the edge list alone.

    Semantics match the engine's apps: pull along in-edges, dangling mass
    dropped (pagerank/ppr), unit edge weights (sssp = bfs hop levels), cc =
    the smallest vertex id that reaches each vertex along directed edges.
    Distances and labels pull over the edges sorted by destination: one
    ``reduceat`` per level, with up to 64 BFS sources packed into the bits
    of one uint64 per vertex.
    """

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        self.src, self.dst, self.n = src, dst, n
        self.out_deg = np.bincount(src, minlength=n)
        by_dst = np.argsort(dst, kind="stable")
        self.src_by_dst = src[by_dst]
        dst_sorted = dst[by_dst]
        self.heads = np.flatnonzero(
            np.r_[True, dst_sorted[1:] != dst_sorted[:-1]])
        self.owners = dst_sorted[self.heads]  # destination of each segment
        self._levels: dict[int, np.ndarray] = {}
        self._answers: dict[tuple, Future] = {}
        self._lock = threading.Lock()

    def _cached(self, key: tuple, compute):
        """compute() once per key; concurrent callers wait for the first
        (``warm`` runs the costly ones on a thread beside the engine)."""
        with self._lock:
            fut = self._answers.get(key)
            owner = fut is None
            if owner:
                fut = self._answers[key] = Future()
        if owner:
            try:
                fut.set_result(compute())
            except BaseException as exc:
                fut.set_exception(exc)
        return fut.result()

    def warm(self, pool, landmarks, ppr_seeds=()) -> None:
        """Start the landmark BFS, pagerank, cc and ppr columns on
        ``pool``, in the order the phases ask for them."""
        pool.submit(self.bfs_many, landmarks)
        pool.submit(self.pagerank)
        pool.submit(self.cc)
        for seed in ppr_seeds:
            pool.submit(self.ppr, seed)

    def _pull(self, values: np.ndarray, reduce, fill) -> np.ndarray:
        """reduce(values[u]) over the in-edges (u, v) of every v."""
        out = np.full(self.n, fill, dtype=values.dtype)
        out[self.owners] = reduce.reduceat(values[self.src_by_dst],
                                           self.heads)
        return out

    def _power(self, r: np.ndarray, reset, iters: int) -> np.ndarray:
        """iters x (r <- reset + d * sum over in-edges of r[u]/outdeg[u])."""
        inv = 1.0 / np.maximum(self.out_deg, 1)
        for _ in range(iters):
            r = reset + DAMPING * np.bincount(
                self.dst, weights=(r * inv)[self.src], minlength=self.n)
        return r

    def pagerank(self) -> np.ndarray:
        return self._cached(("pagerank",), lambda: self._power(
            np.full(self.n, 1.0 / self.n), (1 - DAMPING) / self.n, PR_ITERS))

    def ppr(self, seed: int) -> np.ndarray:
        def compute():
            reset = np.zeros(self.n)
            reset[seed] = 1 - DAMPING
            return self._power(np.eye(1, self.n, seed)[0], reset, PPR_ITERS)
        return self._cached(("ppr", seed), compute)

    def _bfs(self, sources: tuple) -> None:
        if len(sources) > 64:
            raise ValueError(f"one BFS packs <= 64 sources, got {len(sources)}")
        bit = np.uint64(1) << np.arange(len(sources), dtype=np.uint64)
        frontier = np.zeros(self.n, dtype=np.uint64)
        frontier[list(sources)] = bit
        seen = frontier.copy()
        levels = np.full((len(sources), self.n), np.inf, dtype=np.float32)
        levels[np.arange(len(sources)), list(sources)] = 0.0
        d = 0
        while frontier.any():
            d += 1
            frontier = self._pull(frontier, np.bitwise_or, 0) & ~seen
            seen |= frontier
            for k in range(len(sources)):
                levels[k, (frontier & bit[k]) != 0] = d
        with self._lock:
            self._levels.update(zip(sources, levels))

    def bfs_many(self, sources) -> None:
        """Hop levels from up to 64 distinct sources in one pull BFS."""
        sources = tuple(dict.fromkeys(int(s) for s in sources))
        self._cached(("bfs", sources), lambda: self._bfs(sources))

    def levels(self, source: int, max_hops: int | None = None) -> np.ndarray:
        with self._lock:
            batch = next((k[1] for k in self._answers
                          if k[0] == "bfs" and source in k[1]), (source,))
        self.bfs_many(batch)  # waits for a BFS already under way
        lv = self._levels[source]
        return lv if max_hops is None else np.where(lv <= max_hops, lv,
                                                    np.float32(np.inf))

    def cc(self) -> np.ndarray:
        def compute():
            label = np.arange(self.n, dtype=np.float32)
            while True:
                new = np.minimum(label, self._pull(label, np.minimum, np.inf))
                if np.array_equal(new, label):
                    return label
                label = new
        return self._cached(("cc",), compute)


def pick_landmarks(ref: Reference, k: int, seed: int) -> list[int]:
    """The highest out-degree vertex, then k-1 random ones with out-edges."""
    hub = int(np.argmax(ref.out_deg))
    rng = np.random.default_rng(seed)
    pool = np.flatnonzero(ref.out_deg > 0)
    pool = pool[pool != hub]
    return [hub] + [int(v) for v in rng.choice(pool, size=k - 1,
                                               replace=False)]


# ---------------------------------------------------------------------------
# phase accounting
# ---------------------------------------------------------------------------
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Phase:
    """Times one phase, reads what it cost from the session, and prints its
    ``phase {json}`` line on exit — a failed phase too, with ``"ok": false``.

    Compile seconds come from jax's own compile-duration events; edges from
    the session's per-iteration observer; disk bytes and hit ratio from the
    shared edge cache.
    """

    def __init__(self, name: str, session=None):
        self.name, self.session = name, session
        self.record: dict = {"phase": name}

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            with self._lock:
                self._compile_s += duration
                self._compiles += event == _COMPILE_EVENTS[-1]

    def _on_iteration(self, stats) -> None:
        with self._lock:
            self._edges += stats.edges_processed
            self._iterations += 1
            self._stall_s += stats.stall_seconds
            self._fetch_s += stats.fetch_seconds

    def __enter__(self) -> "Phase":
        self._lock = threading.Lock()
        self._compile_s, self._compiles = 0.0, 0
        self._edges = self._iterations = 0
        self._stall_s = self._fetch_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        if self.session is not None:
            st = self.session.stats
            self._marks = (st.disk_bytes, st.hits, st.misses)
            self.session.iteration_observers.append(self._on_iteration)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        rec = self.record
        rec.update(seconds=seconds, compile_s=self._compile_s,
                   compiles=self._compiles)
        if self.session is not None:
            self.session.iteration_observers.remove(self._on_iteration)
            st = self.session.stats
            disk0, hits0, misses0 = self._marks
            hits, misses = st.hits - hits0, st.misses - misses0
            use_pallas = self.session.config.use_pallas
            rec.update(iterations=self._iterations, edges=self._edges,
                       edges_per_s=self._edges / seconds,
                       stall_s=self._stall_s, fetch_s=self._fetch_s,
                       disk_bytes=st.disk_bytes - disk0,
                       cache_hit_ratio=hits / max(hits + misses, 1),
                       dispatch_k1=describe_dispatch(use_pallas, k=1),
                       dispatch_k16=describe_dispatch(use_pallas, k=16))
        rec["ok"] = exc[0] is None
        print("phase " + json.dumps(rec, default=float), flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_apps(session, ref: Reference, landmarks: list[int]) -> list[dict]:
    """pagerank, sssp from the hub, cc and a K=16 run_batch, all checked."""
    records = []
    hub = landmarks[0]
    with Phase("pagerank", session) as ph:
        got = session.run("pagerank", max_iters=PR_ITERS).values
        want = ref.pagerank()
        err = np.abs(got - want) / want
        ph.record["max_rel_err"] = float(err.max())
        _check(bool(err.max() <= PR_RTOL),
               f"pagerank max rel err {err.max():.3g} > {PR_RTOL}")
    records.append(ph.record)

    with Phase("sssp", session) as ph:
        solo = session.run("sssp", source=hub)
        ph.record["reached"] = int(np.isfinite(solo.values).sum())
        _check(solo.converged, "sssp did not converge")
        _check(np.array_equal(solo.values, ref.levels(hub)),
               "sssp distances differ from the reference BFS levels")
    records.append(ph.record)

    with Phase("cc", session) as ph:
        got = session.run("cc").values
        ph.record["components"] = int(np.unique(got).size)
        _check(np.array_equal(got, ref.cc()),
               "cc labels differ from the reference")
    records.append(ph.record)

    with Phase(f"run_batch_sssp_k{len(landmarks)}", session) as ph:
        cols = session.run_batch("sssp", sources=landmarks)
        for s, col in zip(landmarks, cols):
            _check(np.array_equal(col.values, ref.levels(s)),
                   f"run_batch column for source {s} differs from the "
                   "reference BFS levels")
        _check(cols[0].values.tobytes() == solo.values.tobytes(),
               "run_batch hub column is not bitwise equal to the solo run")
    records.append(ph.record)
    return records


def _service_queries(landmarks: list[int], count: int) -> list[tuple]:
    """Round-robin k-hop sssp and bfs (run_batch already converges K=16
    sssp) and ppr over two seeds (each ppr reference column costs PPR_ITERS
    passes over the edges)."""
    queries = []
    for i in range(count):
        src = landmarks[(i // 3) % len(landmarks)]
        if i % 3 < 2:
            queries.append(("sssp" if i % 3 == 0 else "bfs",
                            {"source": src, "max_iters": SERVICE_HOPS}))
        else:
            queries.append(("ppr", {"seed": landmarks[(i // 3) % 2],
                                    "max_iters": PPR_ITERS}))
    return queries


def _check_answer(ref: Reference, app: str, params: dict, result) -> None:
    if app == "ppr":
        want = ref.ppr(params["seed"])
        _check(bool(np.allclose(result.values, want, rtol=PR_RTOL,
                                atol=PPR_ATOL)),
               f"ppr seed {params['seed']} differs from the reference")
    else:
        want = ref.levels(params["source"], params.get("max_iters"))
        _check(np.array_equal(result.values, want),
               f"{app} {params} differs from the reference")


def phase_service(session, ref: Reference, landmarks: list[int]) -> dict:
    """Concurrent clients through GraphService; every answer checked.

    Each client submits its share of the distinct queries at once, checks
    every answer, then re-asks ``repeats`` of them: answered queries come
    back from the service's memo.
    """
    distinct, repeats = SERVICE_DISTINCT, SERVICE_REPEATS
    threads = SERVICE_THREADS
    todo = _service_queries(landmarks, distinct)
    with Phase("service", session) as ph:
        # a 50 ms straggler window lets each app's first-wave queries
        # coalesce into full K=16 micro-batches; the memo budget holds every
        # distinct answer (one float32 per vertex each)
        with session.service(max_batch=16, max_wait_ms=50,
                             memo_budget_bytes=distinct * 4 * ref.n) as svc:
            def client(part):
                futs = [(app, p, svc.submit(app, **p)) for app, p in part]
                for app, p, fut in futs:
                    _check_answer(ref, app, p, fut.result())
                for app, p, _ in futs[:repeats]:
                    _check_answer(ref, app, p, svc.submit(app, **p).result())
                return len(futs) + min(repeats, len(futs))

            with ThreadPoolExecutor(threads) as pool:
                done = sum(pool.map(client, [todo[i::threads]
                                             for i in range(threads)]))
            snap = svc.stats.snapshot()
        ph.record.update(queries=done, memo_hits=snap["memo_hits"],
                         p50_ms=snap["p50_ms"], p99_ms=snap["p99_ms"],
                         batch_occupancy=snap["batch_occupancy"])
        _check(snap["completed"] == done and snap["failed"] == 0,
               f"service completed {snap['completed']} of {done} queries")
    return ph.record


def phase_multi_device(graph_dir: str, ref: Reference, landmarks: list[int],
                       devices: int, **overrides) -> list[dict]:
    """pagerank, sssp and a K-landmark run_batch on ``devices`` devices,
    each bitwise equal to the same run on one device."""
    runs = {}
    records = []
    for d in (devices, 1):
        with GraphSession(graph_dir, prefetch_depth=2, num_devices=d,
                          **overrides) as session:
            with Phase(f"devices{d}", session) as ph:
                pr = session.run("pagerank", max_iters=PR_ITERS).values
                sp = session.run("sssp", source=landmarks[0]).values
                cols = session.run_batch("sssp", sources=landmarks)
                runs[d] = (pr, sp, np.stack([c.values for c in cols], 1))
            records.append(ph.record)
    pr1, sp1, _ = runs[1]
    with Phase(f"devices{devices}_vs_1") as ph:
        _check(bool(np.all(np.abs(pr1 - ref.pagerank()) / ref.pagerank()
                           <= PR_RTOL)),
               "one-device pagerank differs from the reference")
        _check(np.array_equal(sp1, ref.levels(landmarks[0])),
               "one-device sssp differs from the reference")
        for name, a, b in zip(("pagerank", "sssp", "run_batch"),
                              runs[devices], runs[1]):
            _check(a.tobytes() == b.tobytes(),
                   f"{devices}-device {name} is not bitwise equal to 1 device")
    records.append(ph.record)
    return records


def run_phases(graph_dir: str, ref: Reference, landmarks: list[int],
               chips: int = 1, **session_overrides) -> list[dict]:
    """Every engine phase for ``chips`` devices; returns the phase records.

    The reference answers compute on one host thread while the engine
    phases run; each check waits for the answer it needs.
    """
    with ThreadPoolExecutor(1) as ref_pool:
        ref.warm(ref_pool, landmarks, landmarks[:2] if chips == 1 else ())
        if chips > 1:
            return phase_multi_device(graph_dir, ref, landmarks, chips,
                                      **session_overrides)
        with GraphSession(graph_dir, prefetch_depth=2,
                          **session_overrides) as session:
            records = phase_apps(session, ref, landmarks)
            records.append(phase_service(session, ref, landmarks))
            print("cache " + json.dumps(session.cache_report(),
                                        default=float), flush=True)
        return records


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=int, default=22,
                    help="RMAT scale: 2^scale vertices (default 22)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-device path, 4 vs 1 device")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s), JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 2

    enable_compile_cache()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        with Phase("generate+preprocess") as ph:
            g = make_graph(tmp, args.scale, EDGE_FACTOR, args.seed)
            ph.record.update(vertices=g.n, edges=int(g.src.size),
                             generate_s=g.generate_s,
                             preprocess_s=g.preprocess_s,
                             shard_bytes=g.shard_bytes,
                             shards=g.num_shards)
        with Phase("reference"):
            ref = Reference(g.src, g.dst, g.n)
            landmarks = pick_landmarks(ref, LANDMARKS, args.seed)

        run_phases(g.graph_dir, ref, landmarks, args.chips)

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
