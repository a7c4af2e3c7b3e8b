"""Engine host path: the engine's host prep seconds of each wave (dtype
promotion, column concatenation, transfer), per wave staged in the
window."""


def read(run):
    w = run.window
    return w.stage_s / w.stage_waves * 1e3 if w.stage_waves else None
