"""Loader layer: the program's ``loader.wait`` stage (the consumer blocked
on the host queue) per batch over the traced window (``stall_s`` of the
loader's diagnostics): the stall timed inside the loader, which
``loader.wait_ms`` times from outside, with staging."""


def read(run):
    if not run.diag.get("batches"):
        return None
    return 1000.0 * run.diag["stall_s"] / run.diag["batches"]
