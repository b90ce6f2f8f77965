"""Job-stage placements decided per second of the window: scenarios x
jobs x stages of every query, over the window."""
from bench.readers import rate


def read(run):
    return rate(run, "stages")
