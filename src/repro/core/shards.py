"""Graph sharding: vertex intervals (Algorithm 1), CSR shards, sliced ELL.

Faithful to the paper's §2.2:
  * vertices are split into P disjoint intervals; shard(i) holds every edge
    whose *destination* lies in interval i (pull-mode, single writer);
  * Algorithm 1 greedily cuts intervals so each shard holds at most
    ``threshold_edge_num`` edges (paper default: 20M edges ≈ 80MB);
  * edges inside a shard are grouped by destination and stored in CSR.

TPU adaptation: CSR rows are re-laid out as a **sliced ELL** (SELL-C-σ)
with C = 128 rows along the lanes (:class:`ELLShard`).  Empty rows get no
slot; rows longer than ``max_width`` wrap onto several virtual rows mapped
to the same destination (``row_map``), which absorbs power-law skew; the
virtual rows are sorted by length, so each slice of 128 is only as deep as
its longest row and padding stays a few percent of the edges.  The reduce
over a destination's virtual rows re-applies the semiring, preserving exact
results for +, min, max.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar, Iterator

import numpy as np

LANE = 128  # TPU lane width: the virtual rows one ELL slice holds.
SUBLANE = 8  # TPU sublane.
# Slice depths are multiples of GROUP_ROWS: the fold reduces each lane
# GROUP_ROWS rows at a time, and a group never straddles two slices.
GROUP_ROWS = 2
# Row counts are multiples of ROW_ALIGN: whole int8 (32, 128) tiles, and
# whole (8, 128) tiles of the fold's group partials.
ROW_ALIGN = max(32, SUBLANE * GROUP_ROWS)

# Edge-value storage dtypes (GRAPHMP_EDGE_DTYPE / preprocess val_dtype).
# float32 is the exact baseline; float16/int8 trade bounded error for halved/
# quartered edge-value bytes on disk, in cache, AND over the HBM read the
# SpMV kernel performs (dequantization happens inside the kernel).
EDGE_VAL_DTYPES = ("float32", "float16", "int8")


# --------------------------------------------------------------------------
# edge-value quantization (per-shard affine scheme)
# --------------------------------------------------------------------------
def quantize_edge_vals(vals: np.ndarray, dtype: str) -> tuple[np.ndarray, float, float]:
    """Quantize a float32 edge-value array -> (q, scale, zero).

    Dequantization is the single affine formula used everywhere (kernel,
    jnp fallback, delta re-layout)::

        v_hat = (q.astype(float32) - zero) * scale

    * float32 — identity (scale=1, zero=0).
    * float16 — plain downcast (scale=1, zero=0); error <= 2^-11 * |v|.
    * int8    — affine over [vmin, vmax] widened to include 0, with the
      zero point rounded to an *integer* so v=0 (and therefore padded
      slots) quantizes to q=zero and dequantizes to exactly 0.0:
      scale=(vmax-vmin)/255, zero=rint(-128-vmin/scale),
      q=clip(rint(v/scale+zero)).  Max abs error stays scale/2: rounding
      the zero point shifts the whole grid by delta in [-1/2, 1/2] steps,
      and a range endpoint pushed past +-128 clips back by that same
      delta.  An all-zero array quantizes exactly (scale=1, zero=-128).

    scale/zero are rounded to float32 so every consumer (device kernels
    included) dequantizes with bit-identical parameters.
    """
    dt = np.dtype(dtype)
    if dt == np.float32:
        return vals.astype(np.float32), 1.0, 0.0
    if dt == np.float16:
        return vals.astype(np.float16), 1.0, 0.0
    if dt != np.int8:
        raise ValueError(f"unsupported edge-value dtype {dtype!r}; "
                         f"choose from {EDGE_VAL_DTYPES}")
    v = np.asarray(vals, dtype=np.float32)
    vmin = min(float(v.min(initial=0.0)), 0.0)
    vmax = max(float(v.max(initial=0.0)), 0.0)
    scale = (vmax - vmin) / 255.0
    if scale == 0.0:
        scale = 1.0
    scale = float(np.float32(scale))
    # Integer zero point: 0 lies in [vmin, vmax] by construction, so zero
    # lands in [-128, 127] and rint keeps it there — exact in float32.
    zero = float(np.float32(np.rint(-128.0 - vmin / scale)))
    q = np.clip(np.rint(v / np.float32(scale) + np.float32(zero)),
                -128, 127).astype(np.int8)
    return q, scale, zero


def dequantize_edge_vals(vals: np.ndarray, scale: float = 1.0,
                         zero: float = 0.0) -> np.ndarray:
    """Invert :func:`quantize_edge_vals` (float32 passes through untouched)."""
    if vals.dtype == np.float32:
        return vals
    return ((vals.astype(np.float32) - np.float32(zero))
            * np.float32(scale)).astype(np.float32)


# --------------------------------------------------------------------------
# Algorithm 1: compute vertex intervals
# --------------------------------------------------------------------------
def compute_intervals(in_degrees: np.ndarray, threshold_edge_num: int) -> np.ndarray:
    """Greedy interval cut, exactly Algorithm 1.

    Returns ``starts`` of shape [P+1]: shard p owns vertices
    [starts[p], starts[p+1]).  A single vertex whose in-degree exceeds the
    threshold gets its own interval (the paper requires the threshold to be
    no smaller than the max in-degree; we relax that by allowing singleton
    intervals, which the ELL row-wrapping then handles).
    """
    n = int(in_degrees.shape[0])
    if n == 0:
        return np.array([0], dtype=np.int64)
    csum = np.concatenate([[0], np.cumsum(in_degrees.astype(np.int64))])
    starts = [0]
    v = 0
    while v < n:
        # Largest end such that csum[end] - csum[v] <= threshold, end > v.
        end = int(np.searchsorted(csum, csum[v] + threshold_edge_num, side="right")) - 1
        end = max(end, v + 1)  # always make progress (singleton heavy vertex)
        end = min(end, n)
        starts.append(end)
        v = end
    return np.asarray(starts, dtype=np.int64)


# --------------------------------------------------------------------------
# CSR shard
# --------------------------------------------------------------------------
@dataclasses.dataclass
class CSRShard:
    """One destination-interval shard in CSR (paper's on-disk format)."""

    shard_id: int
    start_vertex: int  # first destination vertex id owned by this shard
    end_vertex: int    # one past the last destination vertex id
    row: np.ndarray    # [rows+1] int64 — CSR row pointers (rows = end-start)
    col: np.ndarray    # [nnz] int32/int64 — source vertex ids
    val: np.ndarray | None  # [nnz] float32 — edge weights (None ⇒ unweighted)

    @property
    def num_rows(self) -> int:
        return self.end_vertex - self.start_vertex

    @property
    def nnz(self) -> int:
        return int(self.col.shape[0])

    def source_vertices(self) -> np.ndarray:
        return np.unique(self.col)


def build_csr_shards(
    src: np.ndarray,
    dst: np.ndarray,
    num_vertices: int,
    threshold_edge_num: int,
    val: np.ndarray | None = None,
) -> list[CSRShard]:
    """Preprocessing steps 2+3 (in memory): bucket edges by destination
    interval, sort/group by destination, emit CSR per shard."""
    in_deg = np.bincount(dst, minlength=num_vertices).astype(np.int64)
    starts = compute_intervals(in_deg, threshold_edge_num)
    order = np.argsort(dst, kind="stable")
    dst_sorted = dst[order]
    src_sorted = src[order]
    val_sorted = val[order] if val is not None else None
    # row pointer over *all* vertices, then slice per shard
    row_all = np.concatenate([[0], np.cumsum(in_deg)])
    shards = []
    for p in range(len(starts) - 1):
        lo, hi = int(starts[p]), int(starts[p + 1])
        e_lo, e_hi = int(row_all[lo]), int(row_all[hi])
        shards.append(
            CSRShard(
                shard_id=p,
                start_vertex=lo,
                end_vertex=hi,
                row=(row_all[lo : hi + 1] - row_all[lo]).astype(np.int64),
                col=src_sorted[e_lo:e_hi].astype(np.int32),
                val=None if val_sorted is None else val_sorted[e_lo:e_hi].astype(np.float32),
            )
        )
    return shards


# --------------------------------------------------------------------------
# Sliced ELL shard (TPU layout)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ELLShard:
    """TPU-native shard: a sliced ELL (SELL-C-σ) with C rows along the lanes.

    Each non-empty destination row becomes one or more *virtual rows* of at
    most ``max_width`` edges (a hub wraps onto several); ``row_map[v]`` is
    the local destination (0-based within the interval) of virtual row v.
    Virtual rows are sorted longest first and cut into slices of C = lane
    consecutive virtual rows, one per lane: slice s holds virtual rows
    [s*C, (s+1)*C) and occupies rows [slice_ptr[s], slice_ptr[s+1]) of
    ``cols``/``vals``, as deep as its longest virtual row rounded up to
    ``GROUP_ROWS``; lane j of those rows holds virtual row s*C + j, top
    down, sentinel col = -1 below its end.  Rows past ``slice_ptr[-1]`` and
    slices past the last virtual row are padding (row_map -1); an empty
    destination row owns no slot.
    """

    shard_id: int
    start_vertex: int
    end_vertex: int
    cols: np.ndarray       # [L, C] int32, sentinel -1
    vals: np.ndarray       # [L, C] float32 | float16 | int8 (see val_scale)
    row_map: np.ndarray    # [S*C] int32 — local destination per virtual row
    slice_ptr: np.ndarray  # [S+1] int32 — first row of each slice
    nnz: int
    # Affine dequantization parameters for non-float32 ``vals`` (identity for
    # float32): true value = (vals.astype(f32) - val_zero) * val_scale.
    val_scale: float = 1.0
    val_zero: float = 0.0
    # the layout's arrays, as the storage backends persist them
    ARRAYS: ClassVar[tuple[str, ...]] = ("cols", "vals", "row_map",
                                         "slice_ptr")

    @property
    def shape(self) -> tuple[int, int]:
        return self.cols.shape  # (L, C)

    @property
    def num_slices(self) -> int:
        return int(self.slice_ptr.shape[0]) - 1

    @property
    def quantized(self) -> bool:
        return self.vals.dtype != np.float32

    def vals_f32(self) -> np.ndarray:
        """Edge values dequantized to float32 (host-side consumers)."""
        return dequantize_edge_vals(self.vals, self.val_scale, self.val_zero)

    def padded_bytes(self) -> int:
        return self.cols.nbytes + self.vals.nbytes

    def decoded_nbytes(self) -> int:
        """Host bytes of the decoded shard (cols + vals + row_map +
        slice_ptr) — the one definition shared by cache hot-tier accounting
        and pipeline staged-bytes accounting."""
        return (self.padded_bytes() + self.row_map.nbytes
                + self.slice_ptr.nbytes)

    def group_slices(self) -> np.ndarray:
        """[L / GROUP_ROWS] int32: the slice each group of GROUP_ROWS rows
        belongs to, ascending; padding rows get S, which the fold drops."""
        depth = np.diff(self.slice_ptr) // GROUP_ROWS
        ids = np.full(self.cols.shape[0] // GROUP_ROWS, self.num_slices,
                      dtype=np.int32)
        ids[: int(depth.sum())] = np.repeat(
            np.arange(self.num_slices, dtype=np.int32), depth)
        return ids

    def staged_row_map(self, num_slices: int) -> np.ndarray:
        """``row_map`` padded with -1 (no destination) to ``num_slices``
        slices, when that is more than the shard's own."""
        extra = num_slices * self.cols.shape[1] - self.row_map.size
        if extra <= 0:
            return self.row_map
        return np.concatenate([self.row_map,
                               np.full(extra, -1, dtype=np.int32)])

    def _slots(self):
        """Row, lane and virtual row of every edge slot, in CSR order
        (destination, then the edge's place in its row)."""
        r_idx, c_idx = np.nonzero(self.cols >= 0)
        s = np.searchsorted(self.slice_ptr, r_idx, side="right") - 1
        vrow = s * self.cols.shape[1] + c_idx
        # a destination's wrapped virtual rows keep their order in the
        # stable longest-first sort, the full ones ahead of the remainder
        order = np.lexsort((r_idx, vrow, self.row_map[vrow]))
        return r_idx[order], c_idx[order], vrow[order]

    def edges(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(local destination int64, source int64, value float32) of every
        edge in CSR order — the inverse of :func:`csr_to_ell`."""
        r_idx, c_idx, vrow = self._slots()
        return (self.row_map[vrow].astype(np.int64),
                self.cols[r_idx, c_idx].astype(np.int64),
                self.vals_f32()[r_idx, c_idx].astype(np.float32))

    def neighbors(self, local: int) -> np.ndarray:
        """Sources of destination row ``local``, in CSR order."""
        C = self.cols.shape[1]
        out = []
        for v in np.flatnonzero(self.row_map == local):
            s, lane = divmod(int(v), C)
            col = self.cols[self.slice_ptr[s]: self.slice_ptr[s + 1], lane]
            out.append(col[col >= 0])
        return np.concatenate(out) if out else np.zeros(0, np.int32)

    def source_vertices(self) -> np.ndarray:
        c = self.cols[self.cols >= 0]
        return np.unique(c)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _bucket_quarter_pow2(x: int, floor: int) -> int:
    """Round up to a quarter-power-of-two bucket (…, 1024, 1280, 1536, 1792,
    2048, …): ≤4 shapes per octave keeps jit compiles bounded while wasting
    ≤25% (vs ≤100% for pure pow2)."""
    n = max(x, floor)
    p = max(1 << max((n - 1).bit_length() - 2, 0), floor)
    return -(-n // p) * p


def _bucket_rows(x: int) -> int:
    """Round a shard's ELL row count up to a 1/32-octave step (at least
    ROW_ALIGN): shards of about ``threshold_edge_num`` edges share one or
    two shapes, and the padding stays under 1/32 of the slots."""
    n = max(x, ROW_ALIGN)
    step = max(1 << max((n - 1).bit_length() - 5, 0), ROW_ALIGN)
    return -(-n // step) * step


def store_slices(shard_meta) -> int:
    """The slice count a shard step's row map is padded to: the most of any
    shard in the store (0 where the metadata lacks ``slices``), so that a
    store's shards differ in shape by their row count alone and compile
    once per row count."""
    return max((int(m.get("slices", 0)) for m in shard_meta), default=0)


def segment_rows(intervals) -> int:
    """The destination rows one shard step covers: the longest interval,
    bucketed to a quarter power of two — the static length of every shard's
    partial array and of the vertex slice it updates."""
    lengths = np.diff(np.asarray(intervals, dtype=np.int64))
    return _bucket_quarter_pow2(int(lengths.max(initial=1)), SUBLANE)


def csr_to_ell(shard: CSRShard, max_width: int = 512, lane: int = LANE) -> ELLShard:
    """Re-lay a CSR shard as a sliced ELL (see :class:`ELLShard`).

    ``max_width`` caps a virtual row; longer rows wrap.  ``lane`` is C, the
    virtual rows a slice holds (the hardware vector width, 128 on TPU).
    The layout is a function of the shard's own degrees alone: its slots
    number the edges plus each slice's tail below its longest row (sorting
    keeps rows of a slice alike) and the rounding of the row count.
    """
    cap = max(int(max_width), 1)
    deg = np.diff(shard.row)
    nz = np.flatnonzero(deg)
    reps = -(-deg[nz] // cap)
    vdst = np.repeat(nz, reps)
    first = np.cumsum(reps) - reps              # first virtual row per dst
    occ = np.arange(vdst.size) - np.repeat(first, reps)
    vstart = shard.row[vdst] + occ * cap        # first edge of a virtual row
    vlen = np.minimum(deg[vdst] - occ * cap, cap)
    order = np.argsort(-vlen, kind="stable")    # longest first, stable
    vdst, vstart, vlen = vdst[order], vstart[order], vlen[order]
    V = int(vdst.size)
    S = _bucket_quarter_pow2(-(-V // lane), 1)
    depth = np.zeros(S, dtype=np.int64)
    depth[: -(-V // lane)] = -(-vlen[::lane] // GROUP_ROWS) * GROUP_ROWS
    slice_ptr = np.concatenate([[0], np.cumsum(depth)]).astype(np.int32)
    L = _bucket_rows(int(slice_ptr[-1]))
    # one slot per edge: virtual row v's k-th edge sits at
    # (slice_ptr[v // C] + k, v % C)
    v = np.repeat(np.arange(V), vlen)
    k = np.arange(v.size) - np.repeat(np.cumsum(vlen) - vlen, vlen)
    slot = (slice_ptr[v // lane] + k) * lane + v % lane
    edge = vstart[v] + k
    cols = np.full(L * lane, -1, dtype=np.int32)
    vals = np.zeros(L * lane, dtype=np.float32)
    cols[slot] = shard.col[edge]
    vals[slot] = 1.0 if shard.val is None else shard.val[edge]
    row_map = np.full(S * lane, -1, dtype=np.int32)
    row_map[:V] = vdst
    return ELLShard(
        shard_id=shard.shard_id,
        start_vertex=shard.start_vertex,
        end_vertex=shard.end_vertex,
        cols=cols.reshape(L, lane),
        vals=vals.reshape(L, lane),
        row_map=row_map,
        slice_ptr=slice_ptr,
        nnz=shard.nnz,
    )


def quantize_shard(shard: ELLShard, dtype: str) -> ELLShard:
    """Return ``shard`` with edge values stored as ``dtype`` (see
    :func:`quantize_edge_vals`).  float32 (or already-matching dtype) is a
    no-op returning the same object."""
    if np.dtype(dtype) == shard.vals.dtype:
        return shard
    if shard.quantized:  # re-quantizing: recover float32 first
        shard = dataclasses.replace(shard, vals=shard.vals_f32(),
                                    val_scale=1.0, val_zero=0.0)
    if np.dtype(dtype) == np.float32:
        return shard
    q, scale, zero = quantize_edge_vals(shard.vals, dtype)
    return dataclasses.replace(shard, vals=q, val_scale=scale, val_zero=zero)


def iter_edges(shard: CSRShard) -> Iterator[tuple[int, int, float]]:
    """Debug helper: yield (src, dst, val) triples of a CSR shard."""
    for local in range(shard.num_rows):
        lo, hi = int(shard.row[local]), int(shard.row[local + 1])
        for e in range(lo, hi):
            v = 1.0 if shard.val is None else float(shard.val[e])
            yield int(shard.col[e]), shard.start_vertex + local, v
