"""Consumer step: device time of the benchmark's named step program per
step of the traced window, mean over the chips (profiler trace)."""


def read(run):
    t = run.trace_summary
    if not t or not t["steps"]:
        return None
    return 1000.0 * t["step_device_s"] / t["steps"]
