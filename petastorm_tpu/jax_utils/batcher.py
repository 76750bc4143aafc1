"""Collation: reader output → fixed-size numpy batches.

The reference leaves fixed-size batching to the frameworks (``tf.data.batch``,
torch collate — ``petastorm/pytorch.py::decimal_friendly_collate``). For SPMD
consumers batch cardinality is correctness, not convenience: every host must
dispatch the same number of steps per epoch or the pjit program deadlocks
(SURVEY.md §7 hard-part #2). So the batcher makes the last-batch policy
explicit:

- ``last_batch="drop"`` — drop the final partial batch (default; matches what
  ``tf.data`` calls ``drop_remainder=True``);
- ``last_batch="pad"`` — wrap-pad the final partial batch to full size and
  attach a boolean ``PAD_MASK_KEY`` column (True = real row) so losses can be
  masked;
- ``last_batch="keep"`` — yield the ragged final batch (non-SPMD use only).

Rows arrive either as schema namedtuples (``make_reader``), NGram dicts
``{offset: namedtuple}`` (collated to ``[B, T, ...]``), or column-batch
namedtuples of record-batch length (``make_batch_reader`` — re-sliced to the
requested batch size).
"""

from __future__ import annotations

import numpy as np

from petastorm_tpu.telemetry import tracing

#: Name of the boolean mask column attached when ``last_batch="pad"``.
PAD_MASK_KEY = "__pad_mask__"

_LAST_BATCH_POLICIES = ("drop", "pad", "keep")


def _stack_column(values):
    """Stack per-row values into one [B, ...] numpy array.

    Numeric/array values stack densely; strings/Decimals/objects — and
    nullable columns where any row is None — become an object array (the
    loader keeps those host-side).
    """
    first = values[0]
    if isinstance(first, np.ndarray) and first.dtype != object:
        # Dense only when every row is a same-shaped array (a nullable field
        # can mix ndarrays with None).
        if all(isinstance(v, np.ndarray) and v.shape == first.shape
               and v.dtype == first.dtype for v in values):
            return np.stack(values)
    elif isinstance(first, (int, float, bool, np.generic)) and \
            all(v is not None for v in values):
        return np.asarray(values)
    out = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        out[i] = v
    return out


def collate_rows(rows, fields=None):
    """Collate a list of namedtuple/dict rows into ``{field: [B, ...]}``."""
    if not rows:
        return {}
    first = rows[0]
    if isinstance(first, dict):
        names = fields or list(first)
        get = lambda row, name: row[name]  # noqa: E731
    else:
        names = fields or list(first._fields)
        get = getattr
    return {name: _stack_column([get(row, name) for row in rows])
            for name in names}


def collate_ngram_rows(rows):
    """Collate NGram rows ``{offset: namedtuple}`` into ``[B, T, ...]`` arrays.

    Offsets are sorted to form the time axis. A field present at *every*
    timestep becomes ``{name: [B, T, ...]}``; a field present at only some
    timesteps keeps per-step identity as ``{f"{name}@{offset}": [B, ...]}``
    (NGram field sets may legitimately differ per offset — reference
    ``petastorm/ngram.py`` semantics, SURVEY.md §2.1).
    """
    if not rows:
        return {}
    offsets = sorted(rows[0])
    fields_at = {off: set(rows[0][off]._fields) for off in offsets}
    common = set.intersection(*fields_at.values()) if offsets else set()

    out = {}
    for name in sorted(common):
        # [B, T, ...]: stack rows then timesteps.
        per_row = [
            np.stack([np.asarray(getattr(row[off], name)) for off in offsets])
            for row in rows
        ]
        out[name] = _stack_column(per_row)
    for off in offsets:
        for name in sorted(fields_at[off] - common):
            out[f"{name}@{off}"] = _stack_column(
                [np.asarray(getattr(row[off], name)) for row in rows])
    return out


def _pad_batch(batch, batch_size):
    """Wrap-pad every column to ``batch_size`` rows and attach PAD_MASK_KEY."""
    short = next(iter(batch.values())).shape[0] if batch else 0
    reps = -(-batch_size // max(short, 1))
    padded = {}
    for name, col in batch.items():
        tiled = np.concatenate([col] * reps)[:batch_size]
        padded[name] = tiled
    mask = np.zeros(batch_size, dtype=bool)
    mask[:short] = True
    padded[PAD_MASK_KEY] = mask
    return padded


def batch_iterator(reader, batch_size, last_batch="drop", max_batches=None,
                   shuffle_buffer_size=0, shuffle_seed=None):
    """Yield ``{field: [batch_size, ...]}`` dicts from a Reader.

    Handles all three reader output shapes (rows, NGram windows, column
    batches). ``max_batches`` truncates the stream (used by the loader's
    equal-step coordination and by benchmarks). ``shuffle_buffer_size`` > 0
    decorrelates rows within row groups through a ``RandomShufflingBuffer``
    (the reference's ``shuffling_queue_capacity`` — row readers only).
    """
    if last_batch not in _LAST_BATCH_POLICIES:
        raise ValueError(
            f"last_batch must be one of {_LAST_BATCH_POLICIES}, "
            f"got {last_batch!r}")
    if batch_size <= 0:
        raise ValueError("batch_size must be positive")

    produced = 0
    if getattr(reader, "batched_output", False):
        if shuffle_buffer_size:
            raise ValueError(
                "shuffle_buffer_size requires a row reader (make_reader); "
                "column-batch readers shuffle at row-group granularity via "
                "shuffle_row_groups")
        source = _rebatch_column_batches(reader, batch_size)
    else:
        source = _batch_rows(reader, batch_size, shuffle_buffer_size,
                             shuffle_seed)

    # The limit check precedes the source pull: pulling first would decode a
    # full batch past the limit only to discard it (and with max_batches=0 —
    # the empty-shard lockstep case — would decode a batch before yielding
    # nothing at all).
    while max_batches is None or produced < max_batches:
        try:
            batch, full = next(source)
        except StopIteration:
            return
        if not full:
            if last_batch == "drop":
                return
            if last_batch == "pad":
                batch = _pad_batch(batch, batch_size)
        produced += 1
        yield batch


def _timed_collate(collate, *args):
    """``collate(*args)`` inside the ``loader.collate`` span."""
    with tracing.span("loader.collate"):
        return collate(*args)


def _batch_rows(reader, batch_size, shuffle_buffer_size=0, shuffle_seed=None):
    """Row reader → (collated batch dict, is_full) pairs."""
    buf = []
    ngram = getattr(reader, "ngram", None) is not None
    collate = collate_ngram_rows if ngram else collate_rows

    if shuffle_buffer_size:
        from petastorm_tpu.reader_impl.shuffling_buffer import (
            RandomShufflingBuffer,
        )

        sbuf = RandomShufflingBuffer(
            shuffle_buffer_size,
            min_after_retrieve=shuffle_buffer_size // 2,
            extra_capacity=max(shuffle_buffer_size, 1000),
            random_seed=shuffle_seed)

        def rows():
            for row in reader:
                sbuf.add_many([row])
                while not sbuf.can_add() and sbuf.can_retrieve():
                    yield sbuf.retrieve()
            sbuf.finish()
            while sbuf.can_retrieve():
                yield sbuf.retrieve()

        source = rows()
    else:
        source = reader

    for row in source:
        buf.append(row)
        if len(buf) == batch_size:
            yield _timed_collate(collate, buf), True
            buf = []
    if buf:
        yield _timed_collate(collate, buf), False


def _rebatch_column_batches(reader, batch_size):
    """Column-batch reader → fixed-size (batch dict, is_full) pairs.

    Record batches arrive at row-group/record-batch granularity; slice and
    stitch them into exact ``batch_size`` chunks, carrying remainders across
    input batches.
    """
    pending = {}   # field -> list of leftover column chunks
    pending_rows = 0
    names = None

    def emit(n):
        nonlocal pending, pending_rows
        out, rest = {}, {}
        for name in names:
            joined = (pending[name][0] if len(pending[name]) == 1
                      else np.concatenate(pending[name]))
            out[name] = joined[:n]
            rest[name] = [joined[n:]] if joined.shape[0] > n else []
        pending = rest
        pending_rows -= n
        return out

    for col_batch in reader:
        batch_dict = col_batch._asdict() if hasattr(col_batch, "_asdict") \
            else dict(col_batch)
        if names is None:
            names = list(batch_dict)
            pending = {name: [] for name in names}
        rows_in = len(next(iter(batch_dict.values())))
        for name in names:
            pending[name].append(np.asarray(batch_dict[name]))
        pending_rows += rows_in
        while pending_rows >= batch_size:
            yield _timed_collate(emit, batch_size), True
    if pending_rows:
        yield _timed_collate(emit, pending_rows), False
