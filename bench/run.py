#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload g500-s22-ooc.pagerank --seed 7 \\
        --seconds 51 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<name>.json``: the graph, whether its edges carry weights,
how it is preprocessed, the session's settings) and a traffic mix
(``bench/mixes/<name>.json``: the application, its parameters, the warm-up,
the limits of the answer check).  The mix's ``app`` is a runner in
``bench/apps/<app>.py``; metrics are readers in
``bench/metrics/<name>.py``; peaks are in ``bench/peaks.json``.  A new
cell, configuration, mix, application or metric is new files and new
entries in ``BENCHMARK.json``; nothing here names one.

An application module defines ``App``, built as ``App(mix, session, seed,
out_deg)`` (``out_deg`` counted from the generated arcs), with:

- ``columns``: the vertex columns each engine sweep carries (K);
- ``edge_value``: whether its semiring reads the edges' values;
- ``call(units)``: run ``units`` units of work on the session, returning
  (the values to compare, units done, the engine's ``IterationStats`` of
  every sweep run, the seconds of each unit).  A unit is the
  application's own: one engine sweep, or one whole search;
- ``reference(graph, units, rounding=None)``: the same values from the
  plain NumPy reference over ``oracle.PullGraph`` for ``units`` units,
  with ``rounding`` applied to stored values (the lower-precision control);
- ``work_edges(graph, units)``: the edges those units count as done, the
  numerator of ``edges_per_s``.

One run:

1. set-up: generate the Graph500 graph (and its weights) on the device
   from ``--seed``, write it as the program's edge list,
   ``preprocess_graph`` it, open a ``GraphSession``, and run the cell's
   application for its warm-up units (compiling its shapes and filling
   the edge cache);
2. the window: one ``call`` of M units, M chosen from the median warm-up
   unit so that the call lasts about ``--seconds`` (``--trace 1`` records
   it with the profiler), while a thread samples the process's resident
   set and the edge cache's bytes.  The mix's ``warmup_units`` runs past
   the edge cache's fill, so that median is a unit of the steady state;
3. after the window: the device's peak memory, the session freed, the
   window's answer compared, every value, with the application's NumPy
   reference computed from the edge list alone, and the edge cache's peak
   bytes compared with the configuration's budget.

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
``checks`` last); the compared numbers with their limits are also the last
lines on stderr.  Without a TPU, or with fewer chips than the cell asks
for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs under /tmp unless told otherwise; a run writes only
# inside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import graph500  # noqa: E402
import oracle  # noqa: E402
import roofline  # noqa: E402
import xtrace  # noqa: E402

SPEC_FILE = ROOT / "BENCHMARK.json"
APPS = BENCH / "apps"  # one application runner a file, by the mix's app
CACHE = BENCH / ".cache"  # per-cell work directories (gitignored)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the spec and the data files it names
# ---------------------------------------------------------------------------
def load_cell(workload: str, spec_file: Path = SPEC_FILE):
    """(spec, cell, configuration, mix) for one workload name."""
    spec = json.loads(spec_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, mix


def cell_metrics(spec: dict, cell_name: str, trace: bool) -> list[str]:
    """The metric names this cell reports: its end-to-end metrics, or with a
    trace the per-layer metrics whose moved metric it reports."""
    def applies(m):
        return cell_name in m.get("workloads", [cell_name])

    e2e = [m["name"] for m in spec["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    return [m["name"] for m in spec["per_layer"]
            if applies(m) and m["moves"] in e2e]


def _module(kind: str, path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} {path.stem!r}: {path} does not "
                                f"exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _module("metric", BENCH / "metrics" / f"{name}.py").read


def app_runner(name: str):
    """The ``App`` class of the application a mix names."""
    return _module("app", APPS / f"{name}.py").App


# ---------------------------------------------------------------------------
# what the metric readers see
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    setup_s: float
    window_s: float
    sweeps: int              # engine sweeps in the window (len(history))
    units: int               # the application's units of work completed
    work_edges: int          # the edges those units count as done
    columns: int
    num_edges: int           # the generated graph's |E|, in arcs
    num_vertices: int
    edge_value: bool
    history: list            # IterationStats of the window's sweeps
    cache_delta: dict        # numeric cache counters, window delta
    peaks: dict
    trace: xtrace.Trace | None = None
    shard_vertices: list | None = None  # vertices of each shard's interval


def rss_bytes() -> int:
    """The process's resident set now, from ``/proc/self/statm``."""
    with open("/proc/self/statm", "rb") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


class PeakSampler:
    """Peaks while active of the process's resident set and of the edge
    cache's bytes (``cache.cached_bytes``), sampled every ``interval``
    seconds on a thread of its own (the kernel's high-water mark cannot be
    reset, or read, everywhere the benchmark runs)."""

    def __init__(self, cache, interval: float = 0.005):
        self.cache = cache
        self.interval = interval
        self.peak_bytes = 0
        self.cache_peak_bytes = 0
        self._stop = threading.Event()

    def _sample(self) -> None:
        self.peak_bytes = max(self.peak_bytes, rss_bytes())
        self.cache_peak_bytes = max(self.cache_peak_bytes,
                                    int(self.cache.cached_bytes))

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            self._sample()

    def __enter__(self):
        self._sample()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self._sample()


class CompileCounter:
    """Backend compiles seen while active (jax's own monitoring events)."""

    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def _on(self, event: str, _duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def _numeric(report: dict) -> dict:
    return {k: v for k, v in report.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def device_info(chips: int, require_tpu: bool) -> tuple[list, dict]:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {platform} device(s)")
    return devices[:chips], {"platform": platform,
                             "kind": devices[0].device_kind,
                             "count": len(devices)}


def use_compile_cache() -> None:
    """The program's fixed persistent compile cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one), holding every program
    the run compiles, however quick."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _emit(tag: str, payload) -> None:
    print(f"bench {tag} " + json.dumps(payload, default=float), flush=True)


# ---------------------------------------------------------------------------
def make_graph(config: dict, seed: int, workdir: Path,
               parts: dict) -> tuple[Path, Path, int, int, np.ndarray]:
    """Generate, write and preprocess the configuration's graph; returns
    (edge list dir, graph dir, |V|, |E| in arcs, out-degrees) and records
    the seconds of each step in ``parts``."""
    from repro.graph.preprocess import preprocess_graph

    t = time.perf_counter()
    n = 1 << config["scale"]
    src, dst, weights = graph500.config_arcs(config, seed)
    parts["generate_s"] = time.perf_counter() - t
    edges = workdir / "edges"
    oracle.write_edge_list(edges, src, dst, n, weights)
    m = int(src.size)
    out_deg = np.bincount(src, minlength=n)
    del src, dst, weights
    parts["edge_list_s"] = time.perf_counter() - t - parts["generate_s"]
    t = time.perf_counter()
    graph = workdir / "graph"
    preprocess_graph(str(edges), str(graph), **config["preprocess"])
    parts["preprocess_s"] = time.perf_counter() - t
    return edges, graph, n, m, out_deg


def run_cell(spec: dict, cell: dict, config: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, require_tpu: bool = True,
             control: str | None = None, peaks: dict | None = None,
             workdir: Path | None = None) -> dict:
    """One run of one cell; returns the result object (``checks`` last).

    ``require_tpu=False`` lets a test drive the run on the CPU;
    ``control="bf16"`` puts the reference computed in bfloat16 in the
    program's place (``bench/control.py``)."""
    import jax

    from repro.kernels.spmv.ops import describe_dispatch
    from repro.session import GraphSession

    devices, device = device_info(cell["chips"], require_tpu)
    peaks = peaks if peaks is not None else roofline.peaks(device["kind"])
    use_compile_cache()
    rss_base = rss_bytes()  # the TPU runtime is up; nothing generated yet
    workdir = workdir or CACHE / cell["name"]
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    try:
        parts = {"imports_s": time.time() - T_START}
        with CompileCounter() as setup_compiles:
            edges, graph, n, m, out_deg = make_graph(config, seed, workdir,
                                                     parts)
            t = time.perf_counter()
            session = GraphSession(str(graph), **config["session"])
            app = app_runner(mix["app"])(mix, session, seed, out_deg)
            del out_deg
            _v, _u, _h, warm_s = app.call(int(mix["warmup_units"]))
            del _v, _h
            parts["warmup_s"] = time.perf_counter() - t
        units = max(1, round(seconds / statistics.median(warm_s)))

        trace_dir = workdir / "trace"
        cache0 = _numeric(session.cache_report())
        gc.collect()
        with CompileCounter() as compiles, \
                PeakSampler(session.cache) as sampled:
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
            t0 = time.perf_counter()
            setup_s = time.time() - T_START
            with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
                values, done, history, unit_s = app.call(units)
            window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        window_compiles = compiles.count
        device["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices)
        report = session.cache_report()
        cache_delta = {k: v - cache0[k] for k, v in _numeric(report).items()
                       if k in cache0}
        shards = session.store.properties["shards"]
        shard_vertices = np.diff(session.store.properties["intervals"])
        slots = sum(s["rows"] * s["width"] for s in shards)
        use_pallas = session.config.use_pallas
        arcs = sum(h.ell_arcs for h in history)
        skipped = sum(h.shards_skipped for h in history)
        scheduled = skipped + sum(h.shards_processed for h in history)
        _emit("cache", report)
        _emit("window", {
            "units": done, "units_asked": units, "sweeps": len(history),
            "seconds": window_s, "warmup_unit_s": warm_s, "unit_s": unit_s,
            "sweep_s": [h.seconds for h in history],
            "compiles": window_compiles,
            "setup_compiles": setup_compiles.count, "setup_parts": parts,
            "selective_sweeps": sum(h.selective_enabled for h in history),
            "shards_skipped_share": skipped / max(1, scheduled),
            "arcs_skipped_share": 1.0 - arcs / (m * max(1, len(history))),
            "dispatch": describe_dispatch(use_pallas, k=app.columns),
            "shards": len(shards), "padded_slots_per_edge": slots / m,
            "rss_base_gb": rss_base / 1e9,
            "rss_peak_gb": sampled.peak_bytes / 1e9,
            "cache_peak_bytes": sampled.cache_peak_bytes})
        session.close()
        del session
        gc.collect()

        t_ref = time.perf_counter()
        ref_graph = oracle.PullGraph(*oracle.read_edge_list(edges))
        want = app.reference(ref_graph, done)
        got = values
        if control == "bf16":
            got = app.reference(ref_graph, done, oracle.bf16_rounding)
        elif control is not None:
            raise ValueError(f"unknown control {control!r}")
        work_edges = app.work_edges(ref_graph, done)
        del ref_graph
        t_ref = time.perf_counter() - t_ref
        err = oracle.rel_err(got, want)
        limit = mix["limits"]["max_rel_err"]
        budget = int(config["session"]["cache_budget_bytes"])
        checks = {"max_rel_err": {"value": float(err.max()), "limit": limit},
                  "cache_peak_bytes": {"value": sampled.cache_peak_bytes,
                                       "limit": budget}}
        failed = int(np.count_nonzero(err > limit))
        correct = failed == 0 and sampled.cache_peak_bytes <= budget

        run = Run(setup_s=setup_s, window_s=window_s, sweeps=len(history),
                  units=done, work_edges=work_edges,
                  columns=app.columns, num_edges=m, num_vertices=n,
                  edge_value=app.edge_value, history=history,
                  cache_delta=cache_delta, peaks=peaks,
                  shard_vertices=shard_vertices.tolist())
        result = {"correct": correct, "attempted": int(err.size),
                  "failed": failed}
        t_trace = time.perf_counter()
        if trace:
            run.trace = xtrace.load(xtrace.find_xplane(trace_dir))
            device["busy_s"] = xtrace.busy_seconds(run.trace)
            device["window_s"] = run.trace.window_s
        metrics = {}
        unit_of = {mt["name"]: mt["unit"]
                   for mt in spec["end_to_end"] + spec["per_layer"]}
        for name in cell_metrics(spec, cell["name"], trace):
            value = metric_reader(name)(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit_of[name]}
        result.update(metrics=metrics, device=device)
        if trace:
            result["breakdown"] = {"device_ops": xtrace.top_ops(run.trace),
                                   "idle_gaps": xtrace.idle_gaps(run.trace)}
        _emit("after", {"reference_s": t_ref,
                        "trace_s": time.perf_counter() - t_trace})
        result["checks"] = checks
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, config, mix = load_cell(args.workload)
    try:
        result = run_cell(spec, cell, config, mix, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace))
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
