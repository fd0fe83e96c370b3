"""The edges the window's units of work count as done (``Run.work_edges``,
the application's own count: for PageRank the generated arcs x columns x
sweeps), per second of the window.

The count comes from the generated graph, never the engine's own count,
so a schedule that skips shards reads as faster, not as less work."""


def read(run):
    return run.work_edges / run.window_s
