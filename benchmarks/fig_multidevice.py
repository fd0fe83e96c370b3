"""Sharded VSW: edges/sec and per-lane stall vs device count.

The claim under measurement: routing one VSW iteration through
``ShardedVSWEngine`` folds N shards per wave across N devices while keeping
results bitwise-identical and disk accounting canonical — so edges/sec
should hold or rise with the device count and the summed per-lane stall
should not blow up, while disk bytes stay EXACTLY constant across device
counts (same schedule, same shards, split across cache partitions).

Every device count runs in this process, over the devices ``jax.devices()``
offers (counts above that are skipped).  To emulate devices on a CPU, launch
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``: the N
"devices" then share cores, so the numbers measure the sharded path's
overhead and accounting, not real scaling.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import get_store, row
from repro.session import GraphSession

DEVICE_COUNTS = (1, 2, 4, 8)
MAX_ITERS = 8


def _measure(path: str, devices: int) -> dict:
    with GraphSession(path, num_devices=devices, prefetch_depth=2) as sess:
        sess.run("pagerank", max_iters=1)  # warm the jit caches (not measured)
        disk0 = sess.stats.disk_bytes
        res = sess.run("pagerank", max_iters=MAX_ITERS)
        return {
            "eps": res.edges_per_second(),
            "disk": sess.stats.disk_bytes - disk0,
            "stall": sum(h.stall_seconds for h in res.history),
            "fetch": sum(h.fetch_seconds for h in res.history),
            "secs": res.total_seconds,
            "checksum": float(np.asarray(res.values).sum()),
        }


def run() -> list[str]:
    out = []
    path = str(get_store().path)
    disk_seen, checksums = set(), set()
    for d in (c for c in DEVICE_COUNTS if c <= len(jax.devices())):
        m = _measure(path, d)
        disk_seen.add(m["disk"])
        checksums.add(m["checksum"])
        out.append(row(
            f"fig_multidevice_pagerank_dev{d}", m["secs"] * 1e6,
            f"edges_per_s={m['eps']:.3g};stall_s={m['stall']:.3f};"
            f"fetch_s={m['fetch']:.3f};disk_MB={m['disk']/1e6:.1f}"))
    # same schedule + shards at every device count: canonical disk bytes and
    # the result itself must not drift
    out.append(row(
        "fig_multidevice_disk_invariant", 0.0,
        f"identical={'yes' if len(disk_seen) == 1 else 'NO'}"))
    out.append(row(
        "fig_multidevice_result_invariant", 0.0,
        f"identical={'yes' if len(checksums) == 1 else 'NO'}"))
    return out
