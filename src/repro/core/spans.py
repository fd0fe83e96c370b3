"""Named spans at the engine's layer boundaries, and the counters they feed.

``span`` wraps one block of host work.  It enters a
``jax.profiler.TraceAnnotation``, so the block lands in the profiler's host
plane, on the clock of the device planes, whenever a profiler session is
active (and costs under a microsecond when none is).  It also times the
block with ``perf_counter`` and, given a ``Counters`` object and a field
name, adds the seconds to that field, so each boundary is measured once
and feeds both the trace and the lifetime counters::

    with span("graphmp.decode", self.stats, "decompress_seconds", shard=p):
        shard = unpack(decompress(blob))

Keyword arguments become the span's arguments in the trace (``sweep``,
``shard``).  There is no switch: the profiler being active is the switch.

The spans, by thread (the engine thread consumes shards, the prefetch
thread produces them; see ``repro.core.pipeline``), with the counter each
feeds:

* engine: ``graphmp.run`` (one whole ``iter_run``, with its ``app`` and
  ``sources``), and inside it ``graphmp.sweep`` (one iteration;
  ``IterationStats.seconds``), and inside it ``graphmp.schedule`` (shard
  schedule and frontier ids), ``graphmp.gather`` (the gather dispatch),
  ``graphmp.wait`` (blocked on the next shard; ``stall_seconds``),
  ``graphmp.step`` (one shard step dispatch), ``graphmp.changed`` (the
  changed-mask pull);
* prefetch: ``graphmp.fetch`` (one shard fetched and staged;
  ``fetch_seconds``), and inside it ``graphmp.read`` (the store read on a
  cache miss), ``graphmp.decode`` (a cold-tier decompress;
  ``decompress_seconds``), ``graphmp.compress`` (admission or demotion;
  ``compress_seconds``), ``graphmp.stage`` (host->device staging;
  ``stage_seconds``).

At prefetch depth 0 the prefetch spans run on the engine thread, inside
``graphmp.wait``.
"""
from __future__ import annotations

import threading
from time import perf_counter

from jax.profiler import TraceAnnotation


class Counters:
    """Base of the dataclasses of lifetime counters: ``bump`` adds under a
    lock, so a producer and a consumer thread can both charge one object."""

    def __post_init__(self):
        self.lock = threading.Lock()

    def bump(self, **deltas) -> None:
        with self.lock:
            for field, delta in deltas.items():
                setattr(self, field, getattr(self, field) + delta)


class span:
    """``with span(name, counters=None, field=None, **args) as s:`` — see the
    module docstring; ``s.seconds`` holds the block's duration on exit."""

    __slots__ = ("_ann", "_counters", "_field", "_t0", "seconds")

    def __init__(self, name: str, counters: Counters | None = None,
                 field: str | None = None, **args):
        self._ann = TraceAnnotation(name, **args)
        self._counters = counters
        self._field = field
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._field is not None:
            self._counters.bump(**{self._field: self.seconds})
