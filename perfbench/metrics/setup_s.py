"""Process start to the first timed request: operand, prep, warm-up."""


def read(run):
    return run.setup_s
