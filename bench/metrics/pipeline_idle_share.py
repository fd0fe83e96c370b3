"""Share of the traced window in which the device ran no operation while
the engine thread waited on the shard queue (``graphmp.wait``): the device
idled because no shard was ready, in %.  None without a trace; a traced
window without a ``graphmp.sweep`` span is an error (``host_spans``)."""
import host_spans


def read(run):
    if run.trace is None:
        return None
    shares = host_spans.idle_shares(run.trace)
    return None if shares is None else shares[0]
