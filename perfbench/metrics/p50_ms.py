"""Median latency of every request due in the window, each timed from when
it was due (host clock)."""
import numpy as np


def read(run):
    lat = run.window.latencies_s
    return float(np.quantile(lat, 0.50)) * 1e3 if lat else None
