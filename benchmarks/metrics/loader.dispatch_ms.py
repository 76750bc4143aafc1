"""Loader layer: the loader's ``device_dispatch_s`` (host staging,
``device_put`` and the device stage's dispatch) per batch."""


def read(run):
    if not run.diag.get("batches"):
        return None
    return 1000.0 * run.diag["device_dispatch_s"] / run.diag["batches"]
