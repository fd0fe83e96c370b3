"""Share of the SpMV roofline: the least time the sweeps' bytes (or
operations) need at the chip's published peak, over the device time of the
engine's shard-step modules in the trace, in %.  None without a trace; a
trace with no such module is an error, so a renamed kernel cannot drop the
metric unseen."""
import roofline
import xtrace

MODULES = r"shard_step"


def read(run):
    if run.trace is None:
        return None
    device_s = xtrace.module_seconds(run.trace, MODULES)
    if not device_s:
        raise ValueError(f"no device module matching {MODULES!r} in the "
                         f"traced window")
    nbytes = roofline.spmv_bytes(run.num_edges, run.num_vertices, run.columns,
                                 edge_value=run.edge_value) * run.sweeps
    ops = roofline.spmv_ops(run.num_edges, run.columns,
                            edge_value=run.edge_value) * run.sweeps
    least, _bound = roofline.least_seconds(nbytes, ops, run.peaks)
    return 100.0 * least / device_s
