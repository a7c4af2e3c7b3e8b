"""Scheduler: columns the engine's waves carried inside the window, as a
share of what waves of ``max_wave_cols`` could have carried (engine
counters ``cols`` and ``waves``)."""


def read(run):
    waves = run.window.inside()
    if not waves:
        return None
    return 100.0 * sum(waves) / (len(waves) * run.window.max_wave_cols)
