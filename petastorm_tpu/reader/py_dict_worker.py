"""Row-decoding worker: one row group → decoded row dicts (or NGram windows).

Reference parity: ``petastorm/py_dict_reader_worker.py`` (``PyDictReaderWorker``,
``PyDictReaderWorkerResultsQueueReader``) — SURVEY.md §2.1, hot path §3.2.

Per ventilated item the worker: reads the row group's needed columns (two-phase
when a predicate is present: predicate columns → boolean mask → remaining
columns for surviving rows), applies ``shuffle_row_drop_partitions``
subsampling, decodes codecs per row (``decode_row`` — the cv2/np.load hot
loop), assembles NGram windows, applies the TransformSpec, and publishes the
row list. The pyarrow column read and cv2 decode both release the GIL, which
is what makes the thread pool effective here.
"""

from __future__ import annotations

from collections import deque

from petastorm_tpu.reader_impl.delivery_tracker import (
    FusedPiecePayload,
    PiecePayload,
    item_key,
)
from petastorm_tpu.schema.transform import transform_schema
from petastorm_tpu.telemetry import tracing
from petastorm_tpu.telemetry.metrics import (
    READER_READ_BYTES,
    READER_STAGE_SECONDS,
)
from petastorm_tpu.utils import decode_row, decode_table
from petastorm_tpu.workers_pool.worker_base import WorkerBase


class PyDictReaderWorker(WorkerBase):
    def __init__(self, worker_id, publish_func, args):
        super().__init__(worker_id, publish_func, args)
        (self._filesystem, self._pieces, self._schema, self._read_schema,
         self._ngram, self._cache, self._transform_spec) = args
        # Schema the *consumer* sees (post-transform); field decode uses the
        # pre-transform read schema.
        self._result_schema = (
            transform_schema(self._read_schema, self._transform_spec)
            if self._transform_spec else self._read_schema
        )

    def process(self, piece_index, worker_predicate=None,
                shuffle_row_drop_partition=(0, 1)):
        piece = self._pieces[piece_index]
        cache_key = self._cache_key(piece, worker_predicate,
                                    shuffle_row_drop_partition)
        key = item_key(piece_index, shuffle_row_drop_partition[0])
        rows = self._cache.get(
            cache_key,
            lambda: self._load_rows(piece, worker_predicate,
                                    shuffle_row_drop_partition, key),
        )
        if rows:
            self.publish_func(PiecePayload(key, rows))

    def _cache_key(self, piece, worker_predicate, shuffle_row_drop_partition):
        # Cached rows are POST-transform: the transform repr must be in the
        # key or a persistent cache serves rows transformed by a stale func.
        fields = sorted(self._read_schema.fields)
        return (piece.path, piece.row_group, repr(worker_predicate),
                tuple(fields), shuffle_row_drop_partition,
                repr(self._transform_spec))

    def _load_rows(self, piece, worker_predicate, shuffle_row_drop_partition,
                   bid=None):
        this_partition, num_partitions = shuffle_row_drop_partition
        with tracing.span("reader.read", bid=bid,
                          hist=READER_STAGE_SECONDS.labels("read")) as span:
            if worker_predicate is not None:
                storage, read_bytes = self._read_with_predicate(
                    piece, worker_predicate)
            else:
                storage = piece.read(self._filesystem,
                                     columns=self._needed_columns())
                read_bytes = storage.nbytes
            if isinstance(storage, list):
                # Per-row predicate fallback: rows are already python dicts.
                storage = self._drop_partition(storage,
                                               shuffle_row_drop_partition)
                rows = len(storage)
            else:
                # Survivors stayed Arrow all the way — column-wise decode,
                # no to_pylist on scalar fields.
                if num_partitions > 1:
                    import numpy as np

                    storage = storage.take(
                        np.arange(this_partition, storage.num_rows,
                                  num_partitions))
                rows = storage.num_rows
            span.args.update(rows=rows, bytes=read_bytes)
        READER_READ_BYTES.inc(read_bytes)
        with tracing.span("reader.decode", bid=bid,
                          hist=READER_STAGE_SECONDS.labels("decode")):
            if isinstance(storage, list):
                decoded = [decode_row(row, self._read_schema)
                           for row in storage]
            else:
                decoded = decode_table(storage, self._read_schema)

        if self._ngram is not None:
            windows = self._ngram.form_ngram(decoded, self._read_schema)
            if self._transform_spec and self._transform_spec.func:
                with tracing.span(
                        "reader.transform", bid=bid,
                        hist=READER_STAGE_SECONDS.labels("transform")):
                    windows = [
                        {offset: self._transform_spec.func(dict(ts_row))
                         for offset, ts_row in window.items()}
                        for window in windows
                    ]
            return windows

        if not self._transform_spec:
            return decoded
        if not self._transform_spec.func:
            return [self._apply_transform(row) for row in decoded]
        with tracing.span("reader.transform", bid=bid,
                          hist=READER_STAGE_SECONDS.labels("transform")):
            return [self._apply_transform(row) for row in decoded]

    def _needed_columns(self):
        if self._ngram is not None:
            return self._ngram.get_field_names_at_all_timesteps()
        return sorted(self._read_schema.fields)

    def _read_with_predicate(self, piece, predicate):
        """Two-phase read: predicate columns first, the rest only for survivors.
        Returns ``(survivors, encoded bytes read)``.

        The mask is computed **vectorized** when the predicate exposes a
        column-level form (``pa_mask`` — pyarrow compute on the raw table —
        or ``do_include_vectorized``) and every predicate field is a
        scalar-codec column (stored values ARE the decoded values); the
        per-row ``decode_row`` + ``do_include`` loop remains the fallback,
        unchanged. On the vectorized path the survivors stay Arrow end to
        end: both column reads are ``Table.filter``-ed and returned as ONE
        combined ``pa.Table`` for column-wise decode — no ``to_pylist``
        ever runs. The fallback path (rows already materialized for the
        mask) still returns merged python row dicts."""
        import numpy as np
        import pyarrow as pa

        predicate_fields = sorted(predicate.get_fields())
        unknown = [f for f in predicate_fields if f not in self._schema.fields]
        if unknown:
            raise ValueError(f"Predicate fields not in schema: {unknown}")
        predicate_view = self._schema.create_schema_view(
            [self._schema.fields[f] for f in predicate_fields]
        )
        predicate_table = piece.read(self._filesystem, columns=predicate_fields)
        read_bytes = predicate_table.nbytes
        mask = self._vectorized_predicate_mask(predicate, predicate_view,
                                               predicate_table)
        predicate_rows = None
        if mask is None:
            # Per-row fallback: decode each predicate row, ask do_include.
            # The materialized rows double as the survivor list — no
            # second to_pylist of the predicate columns.
            all_rows = predicate_table.to_pylist()
            mask = np.empty(len(all_rows), dtype=bool)
            for i, row in enumerate(all_rows):
                decoded = decode_row(row, predicate_view)
                mask[i] = bool(predicate.do_include(decoded))
            predicate_rows = [row for row, kept in zip(all_rows, mask)
                              if kept]
        if not mask.any():
            return [], read_bytes
        keep = pa.array(mask)
        # Predicate fields that belong in the output (the rest were read
        # only to compute the mask).
        kept_fields = [
            name for name in predicate_fields
            if name in self._read_schema.fields or (
                self._ngram is not None
                and name in self._ngram.get_field_names_at_all_timesteps())]
        other_columns = [c for c in self._needed_columns()
                         if c not in predicate_fields]
        if predicate_rows is None:
            # Vectorized mask: survivors never become python rows at all —
            # combine the filtered column reads into one Arrow table and
            # let the caller decode column-wise.
            data = {}
            if other_columns:
                other_table = piece.read(self._filesystem,
                                         columns=other_columns)
                read_bytes += other_table.nbytes
                other_table = other_table.filter(keep)
                for name in other_columns:
                    data[name] = other_table.column(name)
            filtered = predicate_table.filter(keep)
            for name in kept_fields:
                data[name] = filtered.column(name)
            return pa.table(data), read_bytes
        # Per-row mask fallback: the predicate rows are already python
        # dicts (the mask needed them) — merge row-wise as before.
        if other_columns:
            other_table = piece.read(self._filesystem, columns=other_columns)
            read_bytes += other_table.nbytes
            other_rows = other_table.filter(keep).to_pylist()
        else:
            other_rows = [{} for _ in predicate_rows]
        result = []
        for pred_row, other_row in zip(predicate_rows, other_rows):
            merged = dict(other_row)
            for name in kept_fields:
                merged[name] = pred_row[name]
            result.append(merged)
        return result, read_bytes

    def _vectorized_predicate_mask(self, predicate, predicate_view, table):
        """Column-level mask, or ``None`` to use the per-row path.

        Only scalar-codec fields of NUMERIC/BOOL dtype qualify: for them
        the stored column value compares exactly as the value
        ``decode_row`` would hand ``do_include``, so the column forms are
        bit-equivalent. Decimal (stored as Arrow strings — lexicographic
        comparison diverges), datetimes, and strings stay on the per-row
        decode path. Prefers ``pa_mask`` (pyarrow compute, zero
        Python-object materialization), then the numpy
        ``do_include_vectorized``."""
        import numpy as np

        for field in predicate_view.fields.values():
            codec_name = type(field.codec).__name__ \
                if field.codec is not None else None
            if field.shape not in ((), None) or codec_name not in (
                    None, "ScalarCodec"):
                return None
            try:
                kind = np.dtype(field.numpy_dtype).kind
            except TypeError:  # Decimal and friends: no numpy dtype
                return None
            if kind not in "biuf":
                return None
        pa_mask = getattr(predicate, "pa_mask", None)
        if pa_mask is not None:
            return np.asarray(pa_mask(table), dtype=bool)
        columns = {name: table.column(name).to_numpy(zero_copy_only=False)
                   for name in table.column_names}
        mask = predicate.do_include_vectorized(columns, table.num_rows)
        return np.asarray(mask, dtype=bool) if mask is not None else None

    def _drop_partition(self, rows, shuffle_row_drop_partition):
        this_partition, num_partitions = shuffle_row_drop_partition
        if num_partitions <= 1:
            return rows
        return rows[this_partition::num_partitions]

    def _apply_transform(self, row):
        if self._transform_spec.func:
            row = self._transform_spec.func(dict(row))
        # enforce the post-transform field set
        return {name: row[name] for name in self._result_schema.fields
                if name in row}

    @property
    def result_schema(self):
        return self._result_schema


class PyDictResultsQueueReader:
    """Consumer-side: turns published row lists into single namedtuple rows."""

    def __init__(self):
        self._buffer = deque()
        self.delivery_tracker = None  # set by Reader for resumable iteration
        self._pending_item = None  # (item_key, num_rows) awaiting last row
        #: Work-item tag of the payload the returned row came from — rows of
        #: one payload drain contiguously (the buffer refills only when
        #: empty), so the tag is valid for every row until the next refill.
        self.last_item_key = None

    @property
    def batched_output(self):
        return False

    def read_next(self, pool, schema, ngram, timeout=None):
        kwargs = {} if timeout is None else {"timeout": timeout}
        while not self._buffer:
            with tracing.span("reader.wait",
                              hist=READER_STAGE_SECONDS.labels("wait")) as span:
                # raises EmptyResultError at end
                rows = pool.get_results(**kwargs)
                span.bid = getattr(rows, "item_key", None)
            if isinstance(rows, FusedPiecePayload):
                # A fused pool task already collated + serialized the whole
                # piece: hand the payload through UNSPLIT (the engine
                # routes it), record delivery now — nothing of it is
                # buffered here. Delivery is counted in ROWS (the payload
                # holds batches), matching the unfused branch.
                self.last_item_key = rows.item_key
                self._pending_item = None
                if self.delivery_tracker is not None:
                    self.delivery_tracker.record(
                        rows.item_key,
                        sum(fb.rows for fb in rows.payload))
                return rows
            if isinstance(rows, PiecePayload):
                # Delivery is recorded only when the payload's LAST row is
                # handed out (bottom of this method): rows still buffered at
                # checkpoint time must be re-read on resume (at-least-once).
                self._pending_item = (rows.item_key, len(rows.payload))
                self.last_item_key = rows.item_key
                rows = rows.payload
            else:
                self._pending_item = None
                self.last_item_key = None
            # Convert the whole delivered row-group at once: namedtuple
            # construction via map(row.get, fields) is the consumer's hot
            # loop and caps pool throughput (it is serial no matter how many
            # workers feed it).
            if ngram is not None:
                self._buffer.extend(
                    ngram.make_namedtuple(schema, row) for row in rows)
            else:
                self._buffer.extend(schema.make_namedtuples(rows))
        row = self._buffer.popleft()
        if not self._buffer and self._pending_item is not None:
            if self.delivery_tracker is not None:
                self.delivery_tracker.record(*self._pending_item)
            self._pending_item = None
        return row
