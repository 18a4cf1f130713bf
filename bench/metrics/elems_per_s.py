"""Input elements (batch x n per call) of every call completed in the
window, over the window's whole wall time on the host clock."""


def read(run):
    return run.elems / run.window_s
