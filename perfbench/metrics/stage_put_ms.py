"""Engine host path: mean milliseconds of the engine's ``spmm.stage.put``
span (``jnp.asarray`` of a wave's concatenated right-hand side: the
host-to-device transfer) per wave staged in the window, from the program's
spans in the profiler trace."""
from perfbench import spans


def read(run):
    return spans.mean_ms(run, "spmm.stage.put")
