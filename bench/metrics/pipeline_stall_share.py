"""Share of the window the engine waited on the shard pipeline
(sum of ``IterationStats.stall_seconds``), in %."""


def read(run):
    return 100.0 * sum(h.stall_seconds for h in run.history) / run.window_s
