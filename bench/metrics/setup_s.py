"""Seconds from the start of the process to the start of the window:
imports, weights, compilation or cache reads, and the warm-up work."""


def read(run):
    return run.setup_s
