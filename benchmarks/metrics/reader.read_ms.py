"""Reader layer: the reader workers' ``reader.read`` stage (Parquet read,
predicate filter, row-drop partition) per row group, thread time over the
traced window (``reader_read_s`` / ``reader_row_groups`` of the loader's
diagnostics: the ``petastorm_reader_stage_seconds`` series)."""


def read(run):
    if not run.diag.get("reader_row_groups"):
        return None
    return 1000.0 * run.diag["reader_read_s"] / run.diag["reader_row_groups"]
