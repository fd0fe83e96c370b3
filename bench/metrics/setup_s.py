"""Seconds from the start of ``bench/run.py`` to the start of the window:
generation, edge list, preprocessing, session open, compile and warm-up."""


def read(run):
    return run.setup_s
