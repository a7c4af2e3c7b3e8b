"""Scheduler: median queue wait of the requests dispatched in the window,
from the engine's own per-request samples (submit to dispatch)."""
import numpy as np


def read(run):
    qw = run.window.queue_wait_s
    return float(np.median(qw)) * 1e3 if qw else None
