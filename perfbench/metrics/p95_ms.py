"""95th percentile latency of every request due in the window, each timed
from when it was due to when its answer came back (host clock)."""
import numpy as np


def read(run):
    lat = run.window.latencies_s
    return float(np.quantile(lat, 0.95)) * 1e3 if lat else None
