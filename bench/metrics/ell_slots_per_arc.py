"""ELL slots staged to the device per edge they hold, over the window's
sweeps (``IterationStats.ell_slots`` over ``ell_arcs``): 1 is a layout with
no padding.  None for a program without the counters."""


def read(run):
    slots = [getattr(h, "ell_slots", None) for h in run.history]
    arcs = [getattr(h, "ell_arcs", None) for h in run.history]
    if not slots or None in slots or None in arcs or not sum(arcs):
        return None
    return sum(slots) / sum(arcs)
