"""95th percentile of the latencies of every depth map emitted in the
window (see latency_p50_ms)."""
import numpy as np


def read(run):
    lat = run.latencies_s
    return float(np.percentile(lat, 95) * 1e3) if lat else None
