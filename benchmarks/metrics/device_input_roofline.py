"""Device input work's share of its roofline: the least time its bytes need
at the chip's HBM bandwidth (read the staged uint8 batch once, write the
cropped batch once, from shapes) over the device time of every program
other than the consumer step, from the trace. Bandwidth bounds it: the
work has almost no arithmetic."""


def read(run):
    t = run.trace_summary
    if not run.input_bytes or not t or t["input_device_s"] <= 0:
        return None
    least = run.input_bytes * t["steps"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t["input_device_s"]
