"""How late packets due in the window were pushed, 95th percentile, ms."""
import numpy as np


def read(run):
    return float(np.percentile(run.lags, 95) * 1e3) if run.lags else None
