"""Consumer step: the step's forward + backward operations (from shapes,
``step_flops`` of the configuration) times steps, over the traced window
times chips times the chip's bf16 peak."""


def read(run):
    if not run.trace_summary:
        return None
    return 100.0 * run.step_flops * run.steps / (
        run.window_s * run.chips * run.peaks["bf16_flops_per_s"])
