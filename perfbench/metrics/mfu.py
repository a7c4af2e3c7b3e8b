"""Whole served product: useful FLOPs completed in the window (2 * nnz per
retired column) over the window's seconds times the chip's peak."""
from perfbench import work


def read(run):
    w = run.window
    if run.peak is None or w.seconds <= 0:
        return None
    return 100.0 * work.flops(run.op, w.cols) / (
        w.seconds * run.peak.flops_per_s)
