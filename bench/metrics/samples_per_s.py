"""Samples trained per second: every sample of the window over the
window's wall time (host clock), stalls included."""


def read(run):
    return run.samples / run.window_s
