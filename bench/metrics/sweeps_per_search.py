"""Engine sweeps per unit of work (``Run.sweeps`` over ``Run.units``), for
an application whose unit is a whole search run to convergence (``sssp``):
the window's sweeps over its searches.  None for a window of no unit."""


def read(run):
    return run.sweeps / run.units if run.units else None
