"""Engine host path: mean milliseconds of the engine's ``spmm.retire.copy``
span (each request's panel cut from the wave's output and copied into its
answer, in its own dtype) per wave retired in the window, from the
program's spans in the profiler trace."""
from perfbench import spans


def read(run):
    return spans.mean_ms(run, "spmm.retire.copy")
