"""Share of the SpMV roofline: the least time the bytes (or operations) of
the shards the window stepped need at the chip's published peak, over the
device time of the engine's shard-step modules in the trace, in %.

Only the shards actually stepped are counted: their arcs (the sum of
``IterationStats.ell_arcs``: edges, not ELL slots) and their destination
vertices (every vertex in a sweep over every shard; in a sweep that skipped
shards, the intervals of those its ``graphmp.step`` spans name).  None
without a trace; a trace with no shard-step module, or a sweep that
skipped shards without step spans naming the shards it stepped, is an
error, so a renamed kernel or span cannot drop or skew the metric unseen."""
import host_spans
import roofline
import xtrace

MODULES = r"shard_step"


def stepped_vertices(run) -> int:
    """The destination vertices of the shards the window's sweeps stepped,
    summed over its sweeps."""
    if not any(h.shards_skipped for h in run.history):
        return run.num_vertices * len(run.history)
    groups = host_spans.stepped_shards(run.trace)
    if [len(g) for g in groups] != [h.shards_processed for h in run.history]:
        raise ValueError(f"the traced window's {host_spans.STEP!r} spans do "
                         f"not name the shards its sweeps stepped")
    return sum(int(run.shard_vertices[p]) for g in groups for p in g)


def read(run):
    if run.trace is None:
        return None
    device_s = xtrace.module_seconds(run.trace, MODULES)
    if not device_s:
        raise ValueError(f"no device module matching {MODULES!r} in the "
                         f"traced window")
    arcs = sum(h.ell_arcs for h in run.history)
    nbytes = roofline.spmv_bytes(arcs, stepped_vertices(run), run.columns,
                                 edge_value=run.edge_value)
    ops = roofline.spmv_ops(arcs, run.columns, edge_value=run.edge_value)
    least, _bound = roofline.least_seconds(nbytes, ops, run.peaks)
    return 100.0 * least / device_s
