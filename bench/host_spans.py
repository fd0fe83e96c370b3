"""Device idle time put down to the program's own host spans.

The engine writes ``graphmp.*`` spans (``repro.core.spans``) into the
profiler's host plane, on the clock of the device planes.  The device's
idle time inside the traced window, taken on the first device plane as
``xtrace.idle_gaps`` takes it, is split between two span families of the
engine thread:

* ``WAIT`` (``graphmp.wait``): the engine blocked on the shard queue, so
  the device idled because no shard was ready;
* ``ENGINE`` (schedule, gather dispatch, shard-step dispatch,
  changed-mask pull): the engine's own host work.

Each family's intervals are merged first, so nothing counts twice, and
idle time under both counts as waiting; the two shares therefore never
sum past the device's idle share.
"""
from __future__ import annotations

import importlib.util

import numpy as np

import xtrace

SWEEP = "graphmp.sweep"
STEP = "graphmp.step"  # one shard step, with its ``sweep`` and ``shard``
WAIT = frozenset({"graphmp.wait"})
ENGINE = frozenset({"graphmp.schedule", "graphmp.gather", "graphmp.step",
                    "graphmp.changed"})


def program_writes_spans() -> bool:
    """Whether the program under test has the span helper (a program from
    before it writes no ``graphmp.*`` span, and reads as no metric)."""
    return importlib.util.find_spec("repro.core.spans") is not None


def _idle_under(busy: np.ndarray, spans: np.ndarray) -> float:
    """Nanoseconds of the merged ``spans`` not covered by the merged
    ``busy`` intervals."""
    if not spans.size:
        return 0.0
    total = float(np.sum(spans[:, 1] - spans[:, 0]))
    if not busy.size:
        return total
    # cumulative busy time as a piecewise-linear function of time
    lengths = busy[:, 1] - busy[:, 0]
    before = np.concatenate([[0.0], np.cumsum(lengths)[:-1]])
    xs = busy.ravel()
    ys = np.stack([before, before + lengths], axis=1).ravel()
    covered = np.interp(spans[:, 1], xs, ys) - np.interp(spans[:, 0], xs, ys)
    return total - float(np.sum(covered))


def idle_shares(trace: xtrace.Trace) -> tuple[float, float] | None:
    """(pipeline, engine) idle shares of the window in %, or None for a
    program that writes no spans.  A traced window without a
    ``graphmp.sweep`` span from a program that writes them is an error,
    so a renamed span cannot drop the metrics unseen."""
    if not any(ev[0] == SWEEP for ev in trace.host):
        if not program_writes_spans():
            return None
        raise ValueError(f"no {SWEEP!r} span in the traced window")
    plane = sorted(trace.device_ops)[0]
    busy = xtrace.busy_intervals(trace.device_ops[plane], trace.window)

    def family(names):
        return xtrace.busy_intervals(
            [ev for ev in trace.host if ev[0] in names], trace.window)

    wait = _idle_under(busy, family(WAIT))
    both = _idle_under(busy, family(WAIT | ENGINE))
    window_ns = trace.window[1] - trace.window[0]
    return 100.0 * wait / window_ns, 100.0 * (both - wait) / window_ns



def stepped_shards(trace: xtrace.Trace) -> list[list[int]]:
    """The shards stepped by each sweep of the traced window that stepped
    any, in time order: the ``shard`` argument of the ``graphmp.step``
    spans inside each ``graphmp.sweep`` span (a step span that names no
    shard, as a multi-device wave's does, is left out)."""
    lo, hi = trace.window
    sweeps = sorted(s for name, s, e in trace.host
                    if name == SWEEP and s >= lo and e <= hi)
    groups: list[list[int]] = [[] for _ in sweeps]
    for (name, s, e), args in zip(trace.host, trace.host_args):
        if name == STEP and "shard" in args and lo <= s and e <= hi:
            i = int(np.searchsorted(sweeps, s, side="right")) - 1
            if i >= 0:
                groups[i].append(int(args["shard"]))
    return [g for g in groups if g]
