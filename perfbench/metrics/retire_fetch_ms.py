"""Engine host path: mean milliseconds of the engine's ``spmm.retire.fetch``
span (``np.asarray`` of a wave's output once the device is done: the
device-to-host copy) per wave retired in the window, from the program's
spans in the profiler trace."""
from perfbench import spans


def read(run):
    return spans.mean_ms(run, "spmm.retire.fetch")
