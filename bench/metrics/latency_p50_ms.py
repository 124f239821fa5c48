"""Median latency of the depth maps emitted in the window: from the due
time of the last event a segment votes with to its map on the host."""
import numpy as np


def read(run):
    lat = run.latencies_s
    return float(np.percentile(lat, 50) * 1e3) if lat else None
