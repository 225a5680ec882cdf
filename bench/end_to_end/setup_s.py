"""Seconds from process start to the window's start: imports, weights
from the seed, the Engine, and the compile (or cache load) and warm-up
of every program shape the cell's traffic reaches."""


def read(run):
    return run.setup_s
