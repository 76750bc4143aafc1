"""Reader layer: the reader workers' ``reader.decode`` stage (every codec
column of a row group) per row group, thread time over the traced window
(``reader_decode_s`` / ``reader_row_groups`` of the loader's diagnostics)."""


def read(run):
    if not run.diag.get("reader_row_groups"):
        return None
    return 1000.0 * run.diag["reader_decode_s"] / run.diag["reader_row_groups"]
