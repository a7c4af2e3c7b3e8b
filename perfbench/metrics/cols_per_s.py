"""Right-hand-side columns whose output panels were retired inside the
window, over the window's seconds (host clock). Parts of a request split
across waves count when they retire."""


def read(run):
    w = run.window
    return w.cols / w.seconds if w.seconds > 0 else None
