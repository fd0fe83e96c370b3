"""Algorithm 1 + CSR/sliced-ELL layout properties (hypothesis)."""
import numpy as np

from tests._hypo import given, settings, st

from repro.core.shards import (GROUP_ROWS, ROW_ALIGN, CSRShard,
                               build_csr_shards, compute_intervals, csr_to_ell,
                               iter_edges, segment_rows, store_slices)
from repro.graph.generate import rmat_edges


@given(st.lists(st.integers(0, 50), min_size=1, max_size=200),
       st.integers(1, 64))
@settings(max_examples=50, deadline=None)
def test_intervals_partition_and_respect_threshold(degs, threshold):
    deg = np.asarray(degs, dtype=np.int64)
    starts = compute_intervals(deg, threshold)
    # partition: consecutive, covering, disjoint
    assert starts[0] == 0 and starts[-1] == len(deg)
    assert (np.diff(starts) >= 1).all()
    # threshold respected except for unavoidable singleton heavy vertices
    csum = np.concatenate([[0], np.cumsum(deg)])
    for a, b in zip(starts[:-1], starts[1:]):
        edges = csum[b] - csum[a]
        assert edges <= threshold or b - a == 1


@given(st.integers(1, 6), st.integers(0, 400), st.integers(2, 5),
       st.integers(1, 40), st.sampled_from([8, 128]))
@settings(max_examples=30, deadline=None)
def test_csr_ell_roundtrip_preserves_edges(seed, n_edges, logn, cap, lane):
    """Every arc lands in exactly one slot of its destination's shard, and
    decoding the slots gives the CSR edges back in CSR order."""
    n = 1 << logn
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, n_edges)
    dst = rng.integers(0, n, n_edges)
    val = rng.random(n_edges).astype(np.float32)
    shards = build_csr_shards(src, dst, n, threshold_edge_num=64, val=val)
    seen = []
    for sh in shards:
        for s, d, v in iter_edges(sh):
            assert sh.start_vertex <= d < sh.end_vertex
            seen.append((s, d, np.float32(v)))
        ell = csr_to_ell(sh, max_width=cap, lane=lane)
        L, C = ell.shape
        assert C == lane and L % ROW_ALIGN == 0
        assert int((ell.cols >= 0).sum()) == sh.nnz == ell.nnz
        local, s_, v_ = ell.edges()
        assert np.array_equal(local, np.repeat(np.arange(sh.num_rows),
                                               np.diff(sh.row)))
        assert np.array_equal(s_, sh.col) and np.array_equal(v_, sh.val)
        got = [(int(s), sh.start_vertex + int(d), np.float32(v))
               for d, s, v in zip(local, s_, v_)]
        assert sorted(got) == sorted(
            e for e in seen if sh.start_vertex <= e[1] < sh.end_vertex)
        seen = [e for e in seen if not (sh.start_vertex <= e[1] < sh.end_vertex)]
    assert not seen or len(shards) == 0


def _virtual_rows(ell):
    """(destination, length) of every virtual row, slice by slice."""
    C = ell.shape[1]
    rows = []
    for s in range(ell.num_slices):
        block = ell.cols[ell.slice_ptr[s]: ell.slice_ptr[s + 1]]
        for j in range(C):
            dst = int(ell.row_map[s * C + j])
            length = int((block[:, j] >= 0).sum())
            assert (block[:length, j] >= 0).all()  # top down, no holes
            rows.append((dst, length))
    return rows


def test_heavy_vertex_row_wrapping():
    """A vertex whose in-degree exceeds the cap wraps onto virtual rows of
    exactly the cap, the remainder last, all mapped to that vertex."""
    n = 16
    src = np.arange(1000) % n
    dst = np.zeros(1000, dtype=np.int64)  # all edges into vertex 0
    shards = build_csr_shards(src, dst, n, threshold_edge_num=1 << 20)
    ell = csr_to_ell(shards[0], max_width=128)
    assert (ell.cols >= 0).sum() == 1000
    rows = [(d, k) for d, k in _virtual_rows(ell) if d >= 0]
    assert rows == [(0, 128)] * 7 + [(0, 1000 - 7 * 128)]
    assert np.array_equal(ell.neighbors(0), shards[0].col)


def test_empty_rows_get_no_slot():
    """Destinations without in-edges own no virtual row and no slot: a
    shard whose interval is mostly empty costs only its edges' slices."""
    n = 4096
    dst = np.arange(0, n, 64)          # 64 destinations of 4096 have edges
    src = (dst * 7) % n
    (sh,) = build_csr_shards(src, dst, n, threshold_edge_num=1 << 20)
    ell = csr_to_ell(sh)
    assert set(int(d) for d in ell.row_map if d >= 0) == set(dst.tolist())
    assert int((ell.row_map >= 0).sum()) == dst.size
    # one slice of depth GROUP_ROWS; the row count is the ROW_ALIGN floor
    assert ell.num_slices == 1
    assert ell.shape[0] == ROW_ALIGN and int(ell.slice_ptr[1]) == GROUP_ROWS
    empty = CSRShard(0, 0, 100, np.zeros(101, np.int64),
                     np.zeros(0, np.int32), None)
    e = csr_to_ell(empty)
    assert (e.cols < 0).all() and (e.row_map < 0).all()
    assert int(e.slice_ptr[-1]) == 0


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_slices_sorted_and_as_deep_as_longest_row(seed):
    """Virtual rows run longest first; each slice is exactly as deep as its
    longest row, rounded up to GROUP_ROWS; slices start where the last
    ends, and the rows past the last slice hold no edge."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 2000))
    m = int(rng.integers(0, 20_000))
    src = rng.integers(0, n, m)
    dst = (rng.pareto(1.2, m) * n / 50).astype(np.int64) % n
    (sh,) = build_csr_shards(src, dst, n, threshold_edge_num=1 << 30)
    ell = csr_to_ell(sh, max_width=int(rng.integers(1, 600)),
                     lane=int(rng.choice([8, 128])))
    C = ell.shape[1]
    lengths = [k for d, k in _virtual_rows(ell) if d >= 0]
    assert lengths == sorted(lengths, reverse=True)
    depth = np.diff(ell.slice_ptr)
    assert (depth % GROUP_ROWS == 0).all()
    for s, dp in enumerate(depth):
        longest = max([k for d, k in _virtual_rows(ell)[s * C:(s + 1) * C]
                       if d >= 0], default=0)
        assert dp == -(-longest // GROUP_ROWS) * GROUP_ROWS
    assert (ell.cols[int(ell.slice_ptr[-1]):] < 0).all()
    g = ell.group_slices()
    assert g.shape == (ell.shape[0] // GROUP_ROWS,)
    assert (np.diff(g) >= 0).all() and int(g[-1]) <= ell.num_slices


def test_kronecker_slots_per_arc():
    """On an undirected Kronecker graph (Graph500's parameters) the layout
    spends at most 1.2 slots an arc, padding included."""
    scale = 14
    src, dst = next(rmat_edges(scale, 16, seed=3))
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    shards = build_csr_shards(src, dst, 1 << scale, threshold_edge_num=1 << 20)
    slots = sum(csr_to_ell(sh).cols.size for sh in shards)
    assert slots / src.size <= 1.2


def test_segment_rows_covers_every_interval():
    """One static slice length serves every shard step: the longest
    interval, bucketed."""
    intervals = [0, 5, 300, 301, 1000]
    assert segment_rows(intervals) >= 699
    assert segment_rows([0, 8]) == 8


def test_store_slices_give_one_row_map_shape(graph_store):
    """The store records each shard's slice count; staged row maps are
    padded with -1 to the store's largest, keeping each shard's own."""
    meta = graph_store.properties["shards"]
    shards = [graph_store.read_shard(p)
              for p in range(graph_store.num_shards)]
    assert [m["slices"] for m in meta] == [s.num_slices for s in shards]
    S = store_slices(meta)
    assert S == max(s.num_slices for s in shards)
    for sh in shards:
        rm = sh.staged_row_map(S)
        assert rm.shape == (S * sh.shape[1],)
        assert np.array_equal(rm[:sh.row_map.size], sh.row_map)
        assert (rm[sh.row_map.size:] == -1).all()
    assert store_slices([{"rows": 32, "width": 128}]) == 0
    assert shards[0].staged_row_map(0) is shards[0].row_map
