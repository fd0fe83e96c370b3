"""Share of the traced window in which the device ran no operation while
the engine thread did its own host work (``graphmp.schedule``,
``graphmp.gather``, ``graphmp.step``, ``graphmp.changed``) and was not
waiting on the shard queue, in %.  None without a trace; a traced window
without a ``graphmp.sweep`` span is an error (``host_spans``)."""
import host_spans


def read(run):
    if run.trace is None:
        return None
    shares = host_spans.idle_shares(run.trace)
    return None if shares is None else shares[1]
