"""User + system CPU time over the window of the trainer process and of
every process the cell started, per sample (host clock, tracing off)."""


def read(run):
    return 1e6 * run.cpu_s / run.samples
