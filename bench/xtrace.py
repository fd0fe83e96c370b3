"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Everything is read with ``jax.profiler.ProfileData`` alone:

* device planes are those named ``/device:<KIND>:<n>``; their ``XLA Ops``
  line holds one event per operation run, their ``XLA Modules`` line one per
  compiled program run;
* host planes (``/host:...``) hold the threads' spans, the benchmark's own
  ``bench.window`` annotation among them, which bounds the traced window;
  the arguments of the program's own spans (``graphmp.*``) are kept.

From these: the seconds the device ran any operation (the union of the op
intervals inside the window), the device seconds of modules whose name
matches a pattern, the operations that took most time, and the longest
idle gaps named by the host span that covers most of each.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench.window"
PROGRAM_SPANS = "graphmp."  # the prefix of the program's span names
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_KIND = re.compile(r"kind=(k\w+)")
_MODULE = re.compile(r"^jit_|\(\d+\)$")
# idle gaps attributed one by one, longest first (the rest are short)
_GAPS_NAMED = 300


@dataclasses.dataclass
class Trace:
    """Events as ``(name, start_ns, end_ns)`` tuples, per line."""
    window: tuple[float, float]
    device_ops: dict[str, list]      # device plane -> op events
    device_modules: dict[str, list]  # device plane -> module events
    host: list                       # every host event but the window span
    # the arguments of each ``host`` event, in the same order: those of the
    # program's own spans (``PROGRAM_SPANS``), {} for any other; empty for a
    # trace built without them
    host_args: list = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str | Path) -> Trace:
    """Read one ``.xplane.pb`` into a ``Trace``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    ops, modules, host, host_args = {}, {}, [], []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, float(e.start_ns),
                          float(e.start_ns + e.duration_ns))
                    if ev[0] == WINDOW_SPAN:
                        window = (ev[1], ev[2])
                    else:
                        host.append(ev)
                        host_args.append(
                            dict(e.stats)
                            if e.name.startswith(PROGRAM_SPANS) else {})
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    if not ops:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} line")
    return Trace(window, ops, modules, host, host_args)


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(found)}")
    return found[0]


def busy_intervals(events: list, window: tuple[float, float]) -> np.ndarray:
    """Merged ``[start, end]`` intervals of ``events`` clipped to the window."""
    lo, hi = window
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in events
                if e > lo and s < hi)
    merged: list[list[float]] = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return np.asarray(merged, dtype=np.float64).reshape(-1, 2)


def busy_seconds(trace: Trace) -> float:
    """Seconds in which any operation ran, averaged over the device planes."""
    per_device = [float(np.sum(b[:, 1] - b[:, 0])) * 1e-9 for b in
                  (busy_intervals(ev, trace.window)
                   for ev in trace.device_ops.values())]
    return sum(per_device) / len(per_device)


def module_seconds(trace: Trace, pattern: str) -> float | None:
    """Device seconds of the modules whose name matches ``pattern`` inside
    the window, summed over devices; None when no module matches."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    total, matched = 0.0, False
    for events in trace.device_modules.values():
        for name, s, e in events:
            if rx.search(name) and e > lo and s < hi:
                matched = True
                total += min(e, hi) - max(s, lo)
    return total * 1e-9 if matched else None


def op_label(hlo: str) -> str:
    """``name opcode [fusion kind]`` of one HLO instruction's text, e.g.
    ``fusion fusion kCustom`` for ``%fusion = f32[8]{0} fusion(...),
    kind=kCustom, ...``; shapes are left out, so every shard bucket of one
    instruction shares a label."""
    name, sep, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name
    if rest.startswith("("):  # a tuple type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    label = f"{name} {rest.strip().partition('(')[0]}"
    kind = _KIND.search(rest)
    return f"{label} {kind.group(1)}" if kind else label


def _module_of(starts: np.ndarray, modules: list, t: float) -> str:
    i = int(np.searchsorted(starts, t, side="right")) - 1
    if i >= 0 and modules[i][1] <= t < modules[i][2]:
        return _MODULE.sub("", modules[i][0])
    return "?"


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """``[[module/op, seconds], ...]``: device time per instruction of each
    module (``op_label``), the ``k`` largest, averaged over devices."""
    lo, hi = trace.window
    acc: dict[str, float] = defaultdict(float)
    for plane, events in trace.device_ops.items():
        modules = sorted(trace.device_modules.get(plane, []),
                         key=lambda ev: ev[1])
        starts = np.array([ev[1] for ev in modules])
        for name, s, e in events:
            if e > lo and s < hi:
                label = f"{_module_of(starts, modules, s)}/{op_label(name)}"
                acc[label] += min(e, hi) - max(s, lo)
    ndev = len(trace.device_ops)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9 / ndev] for name, ns in ranked]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """``[[host span, seconds], ...]``: the device's idle time inside the
    window, each gap named by the host span overlapping it most (the
    shortest such span on a tie), summed per name, the ``k`` largest.
    Taken on the first device plane."""
    plane = sorted(trace.device_ops)[0]
    busy = busy_intervals(trace.device_ops[plane], trace.window)
    lo, hi = trace.window
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])][:_GAPS_NAMED]
    if trace.host:
        names = np.array([ev[0] for ev in trace.host], dtype=object)
        hs = np.array([ev[1] for ev in trace.host])
        he = np.array([ev[2] for ev in trace.host])
    acc: dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        name = "(no host span)"
        if trace.host:
            overlap = np.minimum(he, g1) - np.maximum(hs, g0)
            best = np.flatnonzero(overlap > 0)
            if best.size:
                # most overlap first, then the innermost (shortest) span
                order = np.lexsort((he[best] - hs[best], -overlap[best]))
                name = names[best[order[0]]]
        acc[name] += g1 - g0
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, float(ns) * 1e-9] for name, ns in ranked]
