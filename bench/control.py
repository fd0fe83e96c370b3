#!/usr/bin/env python3
"""Readings of the answer check's control at a cell's own size.

    python3 bench/control.py --workload g500-s21-mem.ppr-k16 --units 5 \\
        --seeds 11 12 13

The control is the application's reference (``bench/apps``) put in the
program's place and computed one precision below the configuration's
float32: vertex values stored in bfloat16 after every step.  For each seed
this generates the cell's graph (and weights) on the device, as a run
does, and prints the check's number for the control over ``--units``
units of work (which must exceed the limit) beside the limit.  Benchmark
runs do not run it; ``run.run_cell(..., control="bf16")`` drives a whole
run with it (``tests/bench``).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import graph500  # noqa: E402
import oracle  # noqa: E402
import run  # noqa: E402


def reading(config: dict, mix: dict, seed: int, units: int) -> float:
    n = 1 << config["scale"]
    src, dst, weights = graph500.config_arcs(config, seed)
    app = run.app_runner(mix["app"])(mix, None, seed,
                                     np.bincount(src, minlength=n))
    graph = oracle.PullGraph(src, dst, n, weights)
    want = app.reference(graph, units)
    got = app.reference(graph, units, oracle.bf16_rounding)
    return float(oracle.rel_err(got, want).max())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--units", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _spec, cell, config, mix = run.load_cell(args.workload)
    limit = mix["limits"]["max_rel_err"]
    for seed in args.seeds:
        value = reading(config, mix, seed, args.units)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "units": args.units, "control": "bf16",
                          "max_rel_err": value, "limit": limit,
                          "fails": value > limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
