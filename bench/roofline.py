"""The bytes and operations an SpMV sweep needs, and the least time for them.

Counted from the algorithm, never from the layout that implements it: per
edge the source id, the edge value where the semiring reads one, and the K
gathered source values; per destination vertex K values read and K
written.  Padded ELL slots are not counted, so the count is the same for
any layout or kernel that does the same work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def spmv_bytes(nnz: int, n: int, k: int, *, edge_value: bool = False,
               id_bytes: int = 4, value_bytes: int = 4) -> int:
    """Bytes one sweep over ``nnz`` edges into ``n`` vertices needs."""
    per_edge = id_bytes + (value_bytes if edge_value else 0) + k * value_bytes
    return nnz * per_edge + n * 2 * k * value_bytes


def spmv_ops(nnz: int, k: int, *, edge_value: bool = False) -> int:
    """Operations of one sweep: an add per edge and column (and a multiply
    where the semiring combines an edge value)."""
    return nnz * k * (2 if edge_value else 1)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(nbytes: float, ops: float, peak: dict) -> tuple[float, str]:
    """(the least time the chip could take, which bound sets it)."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
