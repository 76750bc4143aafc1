"""Stage spans of the local path: ``tracing.span`` and the reader's spans.

One ``tracing.span`` call feeds the collector (Chrome trace JSON), a
histogram and a ``jax.profiler.TraceAnnotation``; the reader's workers and
the loader time every stage through it, so a local-path trace holds
``reader.read`` / ``reader.decode`` per row group on the worker threads and
``reader.wait`` / ``loader.collate`` inside the producer's ``loader.decode``.
"""

import glob
import json
import os
import sys

import numpy as np
import pytest

from petastorm_tpu.telemetry import tracing

ROWS_PER_GROUP = 8
GROUPS = 4


@pytest.fixture(scope="module")
def jpeg_dataset(tmp_path_factory):
    """A small seeded JPEG dataset: ``GROUPS`` row groups of
    ``ROWS_PER_GROUP`` rows, a 48x64 JPEG image and an int64 id."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.etl.metadata import materialize_dataset
    from petastorm_tpu.schema.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.schema.unischema import Unischema, UnischemaField

    path = str(tmp_path_factory.mktemp("data") / "jpeg_ds")
    url = f"file://{path}"
    schema = Unischema("JpegSpans", [
        UnischemaField("image", np.uint8, (48, 64, 3),
                       CompressedImageCodec("jpeg", 90), False),
        UnischemaField("id", np.int64, (), ScalarCodec(), False),
    ])
    field = schema.fields["image"]
    rng = np.random.default_rng(23)
    arrow_schema = schema.as_arrow_schema()
    with materialize_dataset(None, url, schema):
        os.makedirs(path, exist_ok=True)
        with pq.ParquetWriter(os.path.join(path, "part-00000.parquet"),
                              arrow_schema, compression="none") as writer:
            for g in range(GROUPS):
                ids = range(g * ROWS_PER_GROUP, (g + 1) * ROWS_PER_GROUP)
                images = [field.codec.encode(field, rng.integers(
                    0, 255, (48, 64, 3), dtype=np.uint8)) for _ in ids]
                writer.write_table(pa.Table.from_arrays(
                    [pa.array(images, pa.binary()),
                     pa.array(list(ids), pa.int64())],
                    schema=arrow_schema), row_group_size=ROWS_PER_GROUP)
    return url


def _load(url, trace_path=None):
    """Every batch of one epoch through the columnar reader (two threads)
    and the numpy-only loader, one row group a batch; and the loader's
    diagnostics."""
    from petastorm_tpu import make_columnar_reader
    from petastorm_tpu.jax_utils import make_jax_dataloader

    reader = make_columnar_reader(url, reader_pool_type="thread",
                                  workers_count=2, num_epochs=1,
                                  shuffle_row_groups=False)
    loader = make_jax_dataloader(reader, ROWS_PER_GROUP,
                                 stage_to_device=False,
                                 trace_path=trace_path)
    with loader:
        batches = [dict(b) for b in loader]
        diagnostics = loader.diagnostics
    return batches, diagnostics


def _by_first_id(batches):
    return {int(b["id"][0]): b for b in batches}


def _spans(trace_path):
    from petastorm_tpu.telemetry.critical_path import pair_spans

    with open(trace_path) as f:
        return pair_spans(json.load(f)["traceEvents"])


def _inside(inner, outer):
    return (inner["tid"] == outer["tid"] and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


@pytest.fixture(scope="module")
def traced(jpeg_dataset, tmp_path_factory):
    trace_path = str(tmp_path_factory.mktemp("trace") / "trace.json")
    try:
        batches, diagnostics = _load(jpeg_dataset, trace_path)
    finally:
        tracing.COLLECTOR.disable()
    return batches, diagnostics, _spans(trace_path)


# -- (a) the span call ---------------------------------------------------


def test_span_feeds_collector_and_histogram_once(monkeypatch):
    from petastorm_tpu.telemetry.registry import MetricsRegistry

    hist = MetricsRegistry().histogram("span_test_seconds", "test").labels()
    collector = tracing.COLLECTOR
    collector.acquire()
    try:
        with tracing.span("reader.read", bid="3:0", hist=hist) as span:
            span.args["rows"] = 5
        events = [e for e in collector.events() if e["name"] == "reader.read"]
    finally:
        collector.release()
    assert [e["ph"] for e in events] == ["B", "E"]
    assert events[0]["args"] == {"rows": 5, "bid": "3:0"}
    assert hist.count == 1
    # The same duration: timestamps are wall-anchored microseconds, so
    # their difference carries the anchor's rounding (under a microsecond).
    assert (events[1]["ts"] - events[0]["ts"]) / 1e6 == pytest.approx(
        hist.sum, abs=1e-6)

    # Collector off: the histogram still observes, no event is recorded.
    collector.clear()
    with tracing.span("reader.read", hist=hist):
        pass
    assert hist.count == 2
    assert collector.events() == []

    # A block that raises records nothing.
    with pytest.raises(ValueError):
        with tracing.span("reader.read", hist=hist):
            raise ValueError("stage failed")
    assert hist.count == 2

    # Without jax loaded there is no annotation to make.
    monkeypatch.setitem(sys.modules, "jax", None)
    assert tracing._annotation("reader.read", None) is None
    with tracing.span("reader.read", hist=hist):
        pass
    assert hist.count == 3


# -- (b) the local path's collector trace --------------------------------


def test_local_trace_has_reader_stages_per_row_group(jpeg_dataset, traced):
    batches, _, spans = traced
    untraced, _ = _load(jpeg_dataset)
    got, want = _by_first_id(batches), _by_first_id(untraced)
    assert sorted(got) == sorted(want)
    for key, batch in want.items():
        assert sorted(got[key]) == sorted(batch)
        for name, col in batch.items():
            np.testing.assert_array_equal(got[key][name], col)

    for stage in ("reader.read", "reader.decode"):
        of_stage = [s for s in spans if s["name"] == stage]
        assert sorted(s["bid"] for s in of_stage) == \
            [f"{g}:0" for g in range(GROUPS)], stage
    for span in (s for s in spans if s["name"] == "reader.read"):
        assert span["args"]["rows"] == ROWS_PER_GROUP
        assert span["args"]["bytes"] > 0

    decodes = [s for s in spans if s["name"] == "loader.decode"]
    for stage in ("reader.wait", "loader.collate"):
        of_stage = [s for s in spans if s["name"] == stage]
        assert of_stage, stage
        assert all(any(_inside(s, d) for d in decodes) for s in of_stage), \
            f"{stage} outside loader.decode"
    waits = [s for s in spans if s["name"] == "reader.wait"]
    assert sorted(s["bid"] for s in waits) == \
        [f"{g}:0" for g in range(GROUPS)]
    # The workers' spans run on their own threads, not the producer's.
    producer = {d["tid"] for d in decodes}
    assert not producer & {s["tid"] for s in spans
                           if s["name"] == "reader.decode"}


# -- (c) the same stages in the profiler's trace ---------------------------


def test_profiler_trace_holds_reader_and_loader_spans(jpeg_dataset,
                                                      tmp_path):
    import jax
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path / "profile")
    with jax.profiler.trace(trace_dir):
        batches, _ = _load(jpeg_dataset)
    assert len(batches) == GROUPS
    xplane, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    profile = ProfileData.from_file(xplane)
    lines = [{e.name for e in line.events}
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    decode_lines = [names for names in lines
                    if "petastorm_tpu.reader.decode" in names]
    wait_lines = [names for names in lines
                  if "petastorm_tpu.loader.wait" in names]
    assert decode_lines and wait_lines
    # A worker thread decodes; the consumer waits on its own line.
    assert all("petastorm_tpu.loader.wait" not in names
               for names in decode_lines)


# -- (d) stall attribution reaches the reader -----------------------------


def test_critical_path_charges_loader_wait_to_reader(traced):
    from petastorm_tpu.telemetry import critical_path

    spans = traced[2]
    events = []
    for s in spans:
        events.append({"name": s["name"], "ph": "B", "ts": s["ts"],
                       "pid": s["pid"], "tid": s["tid"], "args": s["args"]})
        events.append({"name": s["name"], "ph": "E",
                       "ts": s["ts"] + s["dur"], "pid": s["pid"],
                       "tid": s["tid"]})
    report = critical_path.diagnose(events)
    reader = [row for row in report["bottlenecks"]
              if row["stage"].startswith("reader.")]
    assert reader and sum(row["self_us"] for row in reader) > 0


# -- (e) the stage histogram counts row groups -----------------------------


def test_reader_stage_histogram_counts_row_groups(jpeg_dataset):
    from petastorm_tpu.telemetry.metrics import (
        READER_READ_BYTES,
        READER_STAGE_SECONDS,
    )

    decode = READER_STAGE_SECONDS.labels("decode")
    count0, bytes0 = decode.count, READER_READ_BYTES.value
    _, diagnostics = _load(jpeg_dataset)
    assert decode.count - count0 == GROUPS
    assert diagnostics["reader_row_groups"] == GROUPS
    assert diagnostics["reader_read_bytes"] == READER_READ_BYTES.value - bytes0
    assert diagnostics["reader_read_bytes"] > 0
    assert diagnostics["reader_decode_s"] > 0
    assert diagnostics["reader_transform_s"] == 0
