"""Reader layer: the share of the reader's threads the cell keeps busy,
(read + decode + transform) thread time over (reader workers x window)
of the traced window; 100 over it is the reader's headroom."""


def read(run):
    workers = run.sizes.get("reader_workers")
    if not workers or not run.diag.get("reader_row_groups"):
        return None
    busy = sum(run.diag[f"reader_{stage}_s"]
               for stage in ("read", "decode", "transform"))
    return 100.0 * busy / (workers * run.window_s)
