"""Seconds the edge cache spent decompressing cold-tier shards over the
window (delta of the cache's ``decompress_seconds``), per sweep."""


def read(run):
    decode = run.cache_delta.get("decompress_seconds")
    return None if decode is None else decode / run.sweeps
