"""95th percentile, over every step of the window, of the wall time between
one step's completion and the next (host clock, tracing off)."""

import numpy as np


def read(run):
    return 1000.0 * float(np.percentile(run.intervals_s, 95))
