"""Seconds the prefetch thread spent fetching, decoding and staging shards
(sum of ``IterationStats.fetch_seconds``), per sweep."""


def read(run):
    return sum(h.fetch_seconds for h in run.history) / run.sweeps
