"""Input edges x columns x sweeps completed in the window, per second of it.

The edge count is the generated graph's |E|, never the engine's own count,
so a schedule that skips shards reads as faster, not as less work."""


def read(run):
    return run.num_edges * run.columns * run.sweeps / run.window_s
