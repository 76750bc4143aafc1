"""Device layer: 1 - (union of device op intervals / traced window), mean
over the cell's chips (profiler trace)."""


def read(run):
    t = run.trace_summary
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
