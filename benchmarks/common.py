"""Shared benchmark fixtures: a synthetic power-law graph preprocessed once,
sized so the suite finishes on this CPU container but still exercises real
disk I/O through every code path."""
from __future__ import annotations

import os
import tempfile
from pathlib import Path

from repro.compile_cache import enable_compile_cache
from repro.graph.generate import rmat_edges, materialize
from repro.graph.preprocess import preprocess_graph
from repro.graph.storage import GraphStore, write_edge_list

BENCH_DIR = Path(os.environ.get("BENCH_DIR", tempfile.gettempdir())) / "repro_bench"
SCALE = int(os.environ.get("BENCH_SCALE", "16"))          # 2^16 = 65k vertices
EDGE_FACTOR = int(os.environ.get("BENCH_EDGE_FACTOR", "16"))  # ~1M edges

# persistent jit cache: shard-step compiles amortize across bench runs
enable_compile_cache()


def get_graph():
    """(src, dst, n) for the benchmark RMAT graph (cached per process)."""
    src, dst = materialize(rmat_edges(scale=SCALE, edge_factor=EDGE_FACTOR, seed=11))
    return src, dst, 1 << SCALE


def get_store(threshold_edge_num: int = 1 << 16) -> GraphStore:
    tag = f"v3_s{SCALE}_e{EDGE_FACTOR}_t{threshold_edge_num}"
    out = BENCH_DIR / f"store_{tag}"
    if (out / "property.json").exists():
        return GraphStore(out)
    src, dst, n = get_graph()
    el = BENCH_DIR / f"el_{tag}"
    if not (el / "meta.json").exists():
        write_edge_list(el, [(src, dst)], num_vertices=n)
    # lane=16: CPU-friendly vector width for the benches (TPU default is 128;
    # the layout algebra is identical — see core/shards.py)
    return preprocess_graph(str(el), str(out), threshold_edge_num=threshold_edge_num,
                            lane=16)


def row(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.1f},{derived}"
