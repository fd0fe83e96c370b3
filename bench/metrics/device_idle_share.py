"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, in %."""
import xtrace


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - xtrace.busy_seconds(run.trace) / run.trace.window_s)
