"""Samples consumed by the steps completed in the window, on all chips,
over the window's seconds (host clock, tracing off)."""


def read(run):
    return run.samples / run.window_s
