"""The ``ell_slots_per_arc`` reader: ELL slots staged over the arcs they
hold, summed over the window's sweeps.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_ell_slots_per_arc.py
"""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402


def _run(history) -> "run.Run":
    return run.Run(setup_s=1.0, window_s=0.01, sweeps=len(history),
                   units=len(history), work_edges=1000 * len(history),
                   columns=1,
                   num_edges=1000, num_vertices=100, edge_value=False,
                   history=list(history), cache_delta={},
                   peaks={"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12})


def test_ell_slots_per_arc_reads_the_counters():
    """Slots over arcs of the window's sweeps; None for a program whose
    IterationStats lack the counters."""
    read = run.metric_reader("ell_slots_per_arc")
    assert read(_run([SimpleNamespace(ell_slots=300, ell_arcs=100),
                      SimpleNamespace(ell_slots=100, ell_arcs=100)])
                ) == pytest.approx(2.0)
    assert read(_run([SimpleNamespace(h2d_bytes=8)])) is None
    assert read(_run([])) is None
