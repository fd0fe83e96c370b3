"""Seconds the prefetch thread spent staging shards to the device (sum of
``IterationStats.stage_seconds``, the ``graphmp.stage`` spans), per sweep.
None for a program without the counter."""


def read(run):
    stage = [getattr(h, "stage_seconds", None) for h in run.history]
    if not stage or None in stage:
        return None
    return sum(stage) / run.sweeps
