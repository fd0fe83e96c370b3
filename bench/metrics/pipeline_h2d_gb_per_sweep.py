"""Gigabytes staged to the device (sum of ``IterationStats.h2d_bytes``:
the ELL columns, values, row map and dequantization parameters of every
scheduled shard), per sweep.  It depends only on the graph, its layout
and the schedule.  None for a program without the counter."""


def read(run):
    h2d = [getattr(h, "h2d_bytes", None) for h in run.history]
    if not h2d or None in h2d:
        return None
    return sum(h2d) / 1e9 / run.sweeps
