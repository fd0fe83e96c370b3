"""Share of the shard steps the window's sweeps scheduled that selective
scheduling skipped (``IterationStats.shards_skipped`` over
``shards_skipped + shards_processed``), in %.  None for a window of no
sweep."""


def read(run):
    skipped = sum(h.shards_skipped for h in run.history)
    scheduled = skipped + sum(h.shards_processed for h in run.history)
    return 100.0 * skipped / scheduled if scheduled else None
