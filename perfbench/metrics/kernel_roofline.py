"""Kernels: the least time the window's products could take on the chip
(``work.roofline_s`` of each retired wave) over the device time of the
Pallas kernels that ran them, from the profiler trace."""
from perfbench import work


def read(run):
    t = run.trace
    if t is None or run.peak is None or t.kernel_s <= 0:
        return None
    least = sum(work.roofline_s(run.op, c, run.peak)
                for c in run.window.inside())
    return 100.0 * least / t.kernel_s
