"""Process start to the first timed step: dataset, params, compile or its
cache, the warm-up steps (kernel's process clock)."""


def read(run):
    return run.setup_s
