"""95th percentile of the per-query wall latency over every query of the
window (host clock, query sent to result on the host)."""
import numpy as np


def read(run):
    lat = [r["latency_s"] for r in run.records]
    return float(np.percentile(lat, 95)) if lat else None
