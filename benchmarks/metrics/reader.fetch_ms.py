"""Reader layer: the loader's ``producer_decode_s`` (reader pull, decode,
collate) per batch over the traced window."""


def read(run):
    if not run.diag.get("batches"):
        return None
    return 1000.0 * run.diag["producer_decode_s"] / run.diag["batches"]
