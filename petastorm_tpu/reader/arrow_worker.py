"""Batch worker: one row group → one ``pa.Table`` (columnar, no per-row decode).

Reference parity: ``petastorm/arrow_reader_worker.py`` (``ArrowReaderWorker``,
``ArrowReaderWorkerResultsQueueReader``) — SURVEY.md §2.1, §3.2 batch variant.

The ``make_batch_reader`` path for plain Parquet: columns stay columnar end to
end (predicate via pandas mask, TransformSpec on a pandas DataFrame, Arrow-IPC
across the process boundary), and the consumer receives namedtuples of numpy
*column batches* — the shape the JAX collator likes, since batching to
fixed-size device arrays is a pure slice/concat over these.
"""

from __future__ import annotations

from collections import deque

import numpy as np
import pyarrow as pa

from petastorm_tpu.reader_impl.delivery_tracker import (item_key,
                                                        read_table_tag,
                                                        tag_table)
from petastorm_tpu.schema.transform import transform_schema
from petastorm_tpu.schema.unischema import Unischema
from petastorm_tpu.telemetry import tracing
from petastorm_tpu.telemetry.metrics import (
    READER_READ_BYTES,
    READER_STAGE_SECONDS,
)
from petastorm_tpu.workers_pool.worker_base import WorkerBase


class ArrowReaderWorker(WorkerBase):
    def __init__(self, worker_id, publish_func, args):
        super().__init__(worker_id, publish_func, args)
        (self._filesystem, self._pieces, self._schema, self._read_schema,
         self._ngram, self._cache, self._transform_spec) = args
        if self._ngram is not None:
            raise NotImplementedError(
                "NGram is not supported by make_batch_reader (reference parity)"
            )

    def process(self, piece_index, worker_predicate=None,
                shuffle_row_drop_partition=(0, 1)):
        piece = self._pieces[piece_index]
        # Transform repr included: cached tables are post-transform (see
        # py_dict_worker._cache_key).
        cache_key = (piece.path, piece.row_group, repr(worker_predicate),
                     tuple(sorted(self._read_schema.fields)),
                     shuffle_row_drop_partition, repr(self._transform_spec))
        key = item_key(piece_index, shuffle_row_drop_partition[0])
        table = self._cache.get(
            cache_key,
            lambda: self._load_table(piece, worker_predicate,
                                     shuffle_row_drop_partition, key),
        )
        if table is not None and table.num_rows > 0:
            # Tag rides in schema metadata (not a wrapper object) so the
            # Arrow-IPC serializer keeps transporting plain tables.
            self.publish_func(tag_table(table, key))

    def _load_table(self, piece, worker_predicate, shuffle_row_drop_partition,
                    bid=None):
        columns = sorted(self._read_schema.fields)
        with tracing.span("reader.read", bid=bid,
                          hist=READER_STAGE_SECONDS.labels("read")) as span:
            if worker_predicate is not None:
                predicate_fields = sorted(worker_predicate.get_fields())
                all_columns = sorted(set(columns) | set(predicate_fields))
                table = piece.read(self._filesystem, columns=all_columns)
                read_bytes = table.nbytes
                frame = table.to_pandas()
                values = {f: frame[f] for f in predicate_fields}
                mask = _vectorized_mask(worker_predicate, values, len(frame))
                frame = frame[mask]
                frame = frame[[c for c in columns]]
                table = pa.Table.from_pandas(frame, preserve_index=False)
            else:
                table = piece.read(self._filesystem, columns=columns)
                read_bytes = table.nbytes
            table = self._drop_partition(table, shuffle_row_drop_partition)
            span.args.update(rows=table.num_rows, bytes=read_bytes)
        READER_READ_BYTES.inc(read_bytes)

        if self._transform_spec is not None:
            frame = table.to_pandas()
            if self._transform_spec.func:
                with tracing.span(
                        "reader.transform", bid=bid,
                        hist=READER_STAGE_SECONDS.labels("transform")):
                    frame = self._transform_spec.func(frame)
            result_schema = transform_schema(self._read_schema, self._transform_spec)
            missing = [c for c in result_schema.fields if c not in frame.columns]
            if missing:
                raise ValueError(
                    f"TransformSpec output is missing declared fields: {missing}"
                )
            frame = frame[[c for c in result_schema.fields]]
            table = pa.Table.from_pandas(frame, preserve_index=False)
        return table

    def _drop_partition(self, table, shuffle_row_drop_partition):
        this_partition, num_partitions = shuffle_row_drop_partition
        if num_partitions <= 1:
            return table
        indices = np.arange(this_partition, table.num_rows, num_partitions)
        return table.take(pa.array(indices))


def _vectorized_mask(predicate, column_values, num_rows):
    """Evaluate a row predicate over pandas columns → bool mask (shared
    engine: ``predicates.evaluate_predicate_mask``)."""
    from petastorm_tpu.predicates import evaluate_predicate_mask

    columns = {n: (c.to_numpy() if hasattr(c, "to_numpy") else np.asarray(c))
               for n, c in column_values.items()}
    return evaluate_predicate_mask(predicate, columns, num_rows)


class ArrowResultsQueueReader:
    """Consumer-side: ``pa.Table`` → namedtuple of numpy column arrays."""

    def __init__(self):
        self._buffer = deque()
        self.delivery_tracker = None  # set by Reader for resumable iteration
        #: Work-item tag of the most recently returned output (``"piece:
        #: drop_partition"``) — consumers that attribute outputs per piece
        #: (the streaming piece engine) read it right after ``read_next``.
        self.last_item_key = None

    @property
    def batched_output(self):
        return True

    def read_next(self, pool, schema, ngram, timeout=None):
        kwargs = {} if timeout is None else {"timeout": timeout}
        with tracing.span("reader.wait",
                          hist=READER_STAGE_SECONDS.labels("wait")) as span:
            # raises EmptyResultError at end
            table = pool.get_results(**kwargs)
            key = span.bid = read_table_tag(table)
        self.last_item_key = key
        if self.delivery_tracker is not None and key is not None:
            self.delivery_tracker.record(key, table.num_rows)
        return table_to_batch(table, schema)


def table_to_batch(table, schema):
    """Convert an arrow table into the reader's batch namedtuple."""
    columns = {}
    for name in schema.fields:
        if name not in table.column_names:
            continue
        column = table.column(name)
        field = schema.fields[name]
        columns[name] = _column_to_numpy(column, field)
    return schema.make_namedtuple(**columns)


def _column_to_numpy(column, field):
    values = column.to_numpy(zero_copy_only=False)
    if field.shape and values.dtype == object:
        # codec-less list columns: stack into [batch, *shape]
        try:
            return np.stack([np.asarray(v, dtype=np.dtype(field.numpy_dtype))
                             for v in values])
        except (ValueError, TypeError):
            return values  # ragged; leave as object array
    return values
