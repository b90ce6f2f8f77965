"""Bytes per query sent to the device and copied back (the program's own
`h2d_bytes` and `d2h_bytes` counters of `vectorsim._dispatch`), in MiB."""
from bench.readers import stat_mean


def read(run):
    h2d = stat_mean(run, "run", "h2d_bytes")
    d2h = stat_mean(run, "run", "d2h_bytes")
    return None if h2d is None or d2h is None else (h2d + d2h) / 2 ** 20
