"""``make_jax_dataloader`` — batches from a Reader into TPU HBM.

Pipeline (SURVEY.md §7 stage 5, hard-part #6 "pipelined host→HBM staging"):

    Reader (its own worker pool)            ← Parquet read + decode
      → producer thread: collate to fixed-size numpy batches (batcher.py)
      → bounded host queue (backpressure)
      → consumer: async ``jax.device_put`` kept ``device_prefetch`` batches
        ahead (double buffering — H2D DMA overlaps the caller's compute)
      → yields jax.Array batches (or globally-sharded arrays when a
        ``sharding`` is given — per-shard direct-to-device placement when
        every device is addressable, ``make_array_from_process_local_data``
        on a pod)

With a :class:`~petastorm_tpu.jax_utils.DeviceStage` armed
(``device_stage=``), image fields are staged as RAW uint8 bytes and a
fused JIT kernel performs cast/normalize/crop/flip on the accelerator —
H2D moves bytes, not float32 pixels (``docs/guides/device_decode.md``).

Input-stall instrumentation is built in: time the consumer blocks waiting on
the host queue is "stall", measured against wall time between yields —
``loader.diagnostics['input_stall_pct']`` is the north-star metric
(BASELINE.md: ≤5% stall at v5e-64).

Non-tensor columns (strings, Decimals — object-dtype after collation) cannot
live in HBM; the ``non_tensor_policy`` knob keeps them host-side ("host",
default), drops them ("drop"), or rejects them ("error").
"""

from __future__ import annotations

import itertools
import queue
import threading
import time

import numpy as np

from petastorm_tpu.jax_utils.batcher import PAD_MASK_KEY, batch_iterator
from petastorm_tpu.utils import resize_bounded_queue
from petastorm_tpu.telemetry import tracing
from petastorm_tpu.telemetry.metrics import (
    LOADER_BATCHES,
    LOADER_DISPATCH_OVERLAP,
    LOADER_ROWS,
    LOADER_STAGE_SECONDS,
    READER_READ_BYTES,
    READER_STAGE_SECONDS,
)

_SENTINEL = object()

#: Loader pipeline stages, as histogram label values: ``decode`` (reader
#: pull + collation), ``queue_wait`` (producer blocked on a full host
#: queue), ``wait`` (consumer blocked on input — the stall), ``raw_stage``
#: (staging the raw uint8 bytes batch for the device decode stage),
#: ``device_decode`` (the fused on-device decode/augment kernel dispatch),
#: ``shard_put`` (each per-shard device_put inside a sharded delivery),
#: ``device_put`` (H2D dispatch of ordinary tensors), ``consumer`` (the
#: training step between yields).
_STAGES = ("decode", "queue_wait", "wait", "raw_stage", "device_decode",
           "shard_put", "device_put", "consumer")

#: Stages that are device-dispatch work (the ledger ``device_dispatch_s``
#: sums and the overlap gauge measures). ``shard_put`` is excluded: its
#: observations happen INSIDE the raw_stage/device_put windows (one per
#: target device) — summing it too would double-count.
_DISPATCH_STAGES = ("raw_stage", "device_decode", "device_put")

#: The in-process reader's stages (``petastorm_reader_stage_seconds``),
#: reported per iteration as ``reader_<stage>_s``.
_READER_STAGES = ("read", "decode", "transform", "wait")

#: Per-process loader instance ids — the ``loader`` label value, so each
#: loader's series are separable in a scrape and the legacy per-iteration
#: diagnostics can be re-derived as (current - iteration-start baseline).
#: Ids are RECYCLED: a garbage-collected loader's series are removed from
#: the registry and its id returns to the pool (weakref.finalize), so a
#: trainer constructing loaders in a loop does not grow the registry —
#: live cardinality stays at the number of live loaders.
_LOADER_IDS = itertools.count()
_LOADER_ID_POOL = []


def _acquire_loader_id():
    try:
        return _LOADER_ID_POOL.pop()
    except IndexError:
        return str(next(_LOADER_IDS))


def _release_loader_metrics(loader_id):
    """weakref.finalize callback: retire a dead loader's series."""
    LOADER_BATCHES.remove(loader_id)
    LOADER_ROWS.remove(loader_id)
    LOADER_DISPATCH_OVERLAP.remove(loader_id)
    for stage in _STAGES:
        LOADER_STAGE_SECONDS.remove(loader_id, stage)
    _LOADER_ID_POOL.append(loader_id)


def make_jax_dataloader(reader, batch_size,
                        last_batch="drop",
                        max_batches=None,
                        device=None,
                        sharding=None,
                        host_prefetch=4,
                        device_prefetch=2,
                        non_tensor_policy="host",
                        stage_to_device=True,
                        shuffle_buffer_size=0,
                        shuffle_seed=None,
                        stage_in_producer=False,
                        trace_path=None,
                        batch_cache=None,
                        device_stage=None,
                        cache_resume=None,
                        autotune=None):
    """Create a :class:`JaxDataLoader` over ``reader``.

    :param reader: a ``make_reader``/``make_batch_reader`` Reader (row, NGram,
        or column-batch output all supported).
    :param batch_size: rows per emitted batch. With ``sharding``, this is the
        *per-host* batch size; the global array's batch dim is
        ``batch_size * jax.process_count()``.
    :param last_batch: "drop" | "pad" | "keep" (see batcher.py; "pad" adds a
        boolean ``__pad_mask__`` column).
    :param max_batches: stop after N batches (equal-step coordination: pass
        the pre-agreed per-host step count).
    :param device: target ``jax.Device`` (default: first local device).
        Mutually exclusive with ``sharding``.
    :param sharding: a ``jax.sharding.Sharding``; batches are emitted as
        globally-sharded ``jax.Array`` s via
        ``make_array_from_process_local_data``.
    :param host_prefetch: bounded host-queue depth (collated numpy batches).
    :param device_prefetch: how many batches to keep in-flight on device
        (≥2 ⇒ double buffering). HBM cost: every in-flight batch is
        device-resident, so deep prefetch holds up to
        ``device_prefetch × batch_bytes`` of HBM beyond the model's
        working set (2× that under ``stage_in_producer``, which adds a
        device-resident queue of the same depth) — the loader drops its
        own references the moment a batch is consumed, so this bound is
        tight: raise it for jitter absorption only as HBM allows.
    :param non_tensor_policy: "host" | "drop" | "error" for object-dtype
        columns.
    :param stage_to_device: False ⇒ yield plain numpy dicts (no JAX import;
        useful for tests and host-only consumers).
    :param shuffle_buffer_size: > 0 adds a row-level RandomShufflingBuffer on
        top of row-group shuffling (reference ``shuffling_queue_capacity``
        semantics; row readers only).
    :param shuffle_seed: seed for the shuffle buffer.
    :param stage_in_producer: run ``device_put`` dispatch off the consumer's
        critical path, on a dedicated STAGING thread fed by the decode
        thread: decode and H2D dispatch overlap (both release the GIL), so
        the pipeline's per-batch cost is max(decode, dispatch) instead of
        their sum, and the consumer's per-step input cost shrinks to a
        queue get. Best when steps are long enough to hide the slower of
        the two; not supported with ``sharding``. In this mode the device
        queue's depth is bounded by ``device_prefetch`` (not
        ``host_prefetch``): total in-flight device batches stay ≤
        2·``device_prefetch`` + 1 — raise ``device_prefetch`` for deeper
        jitter absorption (decoded host batches additionally buffer up to
        ``host_prefetch`` between the two threads).
    :param trace_path: write a Perfetto-loadable Chrome ``trace_event``
        JSON of per-batch pipeline spans here at the end of each iteration
        (arms the process trace collector; see
        ``docs/guides/diagnostics.md#metrics-and-tracing``). ``None`` (the
        default) records nothing.
    :param batch_cache: a :class:`~petastorm_tpu.cache_impl.BatchCache`
        (or ``None``). The producer consults it before pulling the reader:
        on a hit the whole epoch's collated batch sequence is served from
        cache (the reader — and the Parquet read + decode behind it — is
        not touched, so iterating the loader again replays the epoch even
        though the underlying ``num_epochs=1`` reader is exhausted); on a
        miss the decoded sequence is written through as it streams.
        Shuffle-compatible: with shuffling requested (``shuffle_seed``, a
        shuffle buffer, or a ``shuffle_row_groups`` reader) the entry
        stays canonical and each pass is served through a fresh seed-tree
        batch permutation — order changes per epoch, bytes don't; note
        the row-level shuffle buffer is superseded by batch-granularity
        permutation while the cache is armed, and the shuffled fill pass
        buffers the epoch before its first yield
        (``docs/guides/caching.md#shuffle-compatible-serving``).
    :param cache_resume: a prior ``state_dict()`` of kind
        ``"cache_replay"`` — resumes a shuffled cached pass at its exact
        permuted batch position (requires ``batch_cache`` and the same
        reader construction).
    :param device_stage: a :class:`~petastorm_tpu.jax_utils.DeviceStage`
        (or ``None``). When armed, the loader stages each batch's raw
        uint8 image fields AS BYTES (4x fewer H2D bytes than float32
        pixels) and a fused JIT kernel performs cast/normalize/crop/flip
        ON the device, with the raw buffer donated to the kernel on
        TPU/GPU so in-flight HBM stays bounded. With ``sharding``, the raw
        batch is delivered shard-by-shard directly onto each target device
        and decoded as one global array (``docs/guides/device_decode.md``).
        Requires ``stage_to_device=True``.
    :param autotune: arm the profile-driven online autotuner
        (``docs/guides/pipeline.md``): the loader's pipeline is described
        as an explicit stage graph and a controller thread periodically
        re-plans the runtime knobs — reader-pool ``workers_count``,
        ``host_prefetch``/``device_prefetch``, and (with a
        ``ServiceBatchSource``) ``credits``/``ready_queue_depth``/
        ``transform_placement`` — within declared bounds, from measured
        per-stage profiles. ``True`` uses defaults; a dict may set
        ``interval_s``, ``bounds`` (``{knob: (lo, hi)}``),
        ``hysteresis``, ``placement_hysteresis``, ``tolerance``. The
        default ``None`` builds no graph and starts no thread — static
        behavior is bit-for-bit unchanged.
    """
    return JaxDataLoader(reader, batch_size, last_batch=last_batch,
                         max_batches=max_batches, device=device,
                         sharding=sharding, host_prefetch=host_prefetch,
                         device_prefetch=device_prefetch,
                         non_tensor_policy=non_tensor_policy,
                         stage_to_device=stage_to_device,
                         shuffle_buffer_size=shuffle_buffer_size,
                         shuffle_seed=shuffle_seed,
                         stage_in_producer=stage_in_producer,
                         trace_path=trace_path,
                         batch_cache=batch_cache,
                         device_stage=device_stage,
                         cache_resume=cache_resume,
                         autotune=autotune)


class JaxDataLoader:
    """Iterable/context-manager yielding ``{field: array}`` batches."""

    def __init__(self, reader, batch_size, last_batch="drop", max_batches=None,
                 device=None, sharding=None, host_prefetch=4,
                 device_prefetch=2, non_tensor_policy="host",
                 stage_to_device=True, shuffle_buffer_size=0,
                 shuffle_seed=None, stage_in_producer=False,
                 batch_source=None, trace_path=None, batch_cache=None,
                 device_stage=None, cache_resume=None, autotune=None):
        if device is not None and sharding is not None:
            raise ValueError("device and sharding are mutually exclusive")
        if device_stage is not None and not stage_to_device:
            raise ValueError(
                "device_stage decodes ON the device; it cannot run with "
                "stage_to_device=False (the numpy-only path never touches "
                "a device) — drop the stage or enable device staging")
        if stage_in_producer and sharding is not None:
            raise ValueError(
                "stage_in_producer is not supported with a global sharding "
                "(make_array_from_process_local_data must run on the thread "
                "driving the pjit steps)")
        if non_tensor_policy not in ("host", "drop", "error"):
            raise ValueError("non_tensor_policy must be host|drop|error")
        if device_prefetch < 1:
            raise ValueError("device_prefetch must be >= 1")
        if batch_source is not None:
            if shuffle_buffer_size or shuffle_seed is not None \
                    or last_batch != "drop":
                raise ValueError(
                    "shuffle_buffer_size/shuffle_seed/last_batch are row-"
                    "batching knobs the custom batch_source path does not "
                    "consume; shuffle and shape batches inside the source "
                    "(silently ignoring them would change training data "
                    "order/shape with no error)")
            if sharding is not None and max_batches is None:
                raise ValueError(
                    "a custom batch_source with a global sharding requires "
                    "an explicit max_batches: source batch counts are data-"
                    "dependent per host, so without an agreed step count "
                    "pjit deadlocks the pod (agree via "
                    "jax_utils.sharding.agree_max_batches)")
        if batch_cache is not None and batch_source is not None:
            raise ValueError(
                "batch_cache is the local-reader decode bypass; the "
                "data service's workers own caching on the remote path "
                "(BatchWorker(batch_cache=...)) — arming both here "
                "would cache an opaque stream under a key that cannot "
                "see the remote plan")
        if cache_resume is not None:
            if batch_cache is None:
                raise ValueError(
                    "cache_resume is a batch_cache replay position; it "
                    "needs batch_cache armed (and the same cache "
                    "key ingredients the snapshot was taken under)")
            if cache_resume.get("kind") != "cache_replay":
                raise ValueError(
                    f"cache_resume must be a state_dict() of kind "
                    f"'cache_replay', got {cache_resume.get('kind')!r}")
            ventilator = getattr(reader, "_ventilator", None)
            if getattr(ventilator, "_randomize_item_order", False) \
                    and getattr(reader, "_shard_seed", None) is None:
                raise ValueError(
                    "cache_resume with a shuffle_row_groups reader "
                    "requires shard_seed: without one the fill order is "
                    "not reproducible, so a cold-cache resume would "
                    "refill the entry in a different canonical order and "
                    "then seek the resume position into the WRONG "
                    "sequence (silent duplicate and lost samples)")
        self.reader = reader
        self._batch_size = batch_size
        self._last_batch = last_batch
        self._max_batches = max_batches
        self._device = device
        self._sharding = sharding
        self._host_prefetch = max(1, host_prefetch)
        self._device_prefetch = device_prefetch
        self._non_tensor_policy = non_tensor_policy
        self._stage_to_device = stage_to_device
        self._stage_in_producer = stage_in_producer and stage_to_device
        self._shuffle_buffer_size = shuffle_buffer_size
        self._shuffle_seed = shuffle_seed
        # Custom host-batch pipeline (e.g. sequence packing): a zero-arg
        # callable returning an iterator of {field: ndarray} batches. The
        # staging/prefetch/diagnostics machinery is reused unchanged; the
        # row-batching knobs (batch_size/last_batch/shuffle buffer) are the
        # source's concern, not this class's.
        self._batch_source = batch_source
        self._batch_cache = batch_cache
        self._device_stage = device_stage
        # Production ordinal of the next staged batch — the device stage's
        # augment seed. Monotonic across iterations (epoch 2 draws fresh
        # augments) and assigned in production order on whichever thread
        # stages, so the augment sequence is reproducible across runs and
        # invariant to device_prefetch depth / stage_in_producer placement.
        self._stage_step = 0
        # Cumulative H2D payload bytes this loader staged (raw bytes + ordinary
        # tensors); the per-iteration diagnostics view re-bases like the
        # registry-backed stages.
        self._h2d_bytes = 0
        # A cache fill is valid ONLY from the reader's start position —
        # i.e. the first pass this loader ever pulls from it. Set when
        # that pass begins and never cleared: any later cache miss
        # (abandoned fill, evicted entry, an entry that never fit the
        # memory budget) finds the reader mid-stream or exhausted, and
        # filling from there would commit a truncated/shifted/empty
        # sequence under the full-epoch key. Once set, misses stream
        # uncached (correct, just not accelerated).
        self._cache_fill_attempted = False
        # Shuffle-compatible replay: each iteration of a cache-armed
        # loader is one "cache epoch"; shuffled serves permute the
        # canonical entry by fold_in(seed, cache-epoch) so the order
        # changes per pass while the cached bytes don't. cache_resume
        # re-enters a permuted pass at a batch position.
        self._cache_epoch = 0
        self._cache_skip = 0
        self._cache_pass = None   # live pass info state_dict() snapshots
        self._cache_resume_seed = None
        self._cache_resume_has_seed = False
        if cache_resume is not None:
            self._cache_epoch = int(cache_resume["cache_epoch"])
            self._cache_skip = max(0, int(
                cache_resume.get("batches_yielded", 0)))
            # Checked against the effective permutation seed at serve
            # time: resuming under a different seed would skip a prefix
            # of the WRONG permutation (silent duplicate/lost samples).
            self._cache_resume_seed = cache_resume.get("shuffle_seed")
            self._cache_resume_has_seed = "shuffle_seed" in cache_resume
        if sharding is not None and max_batches is None \
                and batch_source is None:
            # (With a custom batch_source the reader-metadata derivation
            # below would count ROW batches, not source batches — the source
            # owns step agreement; see make_packed_jax_dataloader docs.)
            # SPMD lockstep: under a global sharding every host must dispatch
            # the same number of steps or pjit deadlocks the pod. Derive the
            # global-min batch count from the reader's shard metadata (each
            # host computes the same number locally — no collective).
            from petastorm_tpu.jax_utils.sharding import (
                derive_equal_step_max_batches,
            )

            derived = derive_equal_step_max_batches(reader, batch_size,
                                                    last_batch)
            if derived is not None:
                self._max_batches = derived

        self._queue = None
        self._host_queue = None
        self._producer = None
        self._stager = None
        self._producer_error = None
        self._source_iter = None   # batch_source() iterator for _produce
        self._direct_iter = None   # prefetched source consumed sans producer
        self._stop = threading.Event()
        self._total_rows_yielded = 0  # cumulative, pad-aware (resume support)
        self._yield_count_tracker = None  # tracker the count is relative to
        # Typed metrics behind the diagnostics dict: per-instance children
        # of the registry families (telemetry.metrics), labeled by a
        # process-unique loader id. The legacy per-iteration dict is
        # RE-DERIVED from these on every `diagnostics` read — current
        # child value minus the iteration-start baseline — so a
        # monitoring thread polling mid-epoch sees live numbers (wall_s
        # and input_stall_pct included) while a scraper sees the same
        # series monotonic.
        self._loader_id = _acquire_loader_id()
        self._m_batches = LOADER_BATCHES.labels(self._loader_id)
        self._m_rows = LOADER_ROWS.labels(self._loader_id)
        self._m_stage = {stage: LOADER_STAGE_SECONDS.labels(self._loader_id,
                                                            stage)
                         for stage in _STAGES}
        self._m_overlap = LOADER_DISPATCH_OVERLAP.labels(self._loader_id)
        self._m_reader = {stage: READER_STAGE_SECONDS.labels(stage)
                          for stage in _READER_STAGES}
        import weakref

        self._metrics_finalizer = weakref.finalize(
            self, _release_loader_metrics, self._loader_id)
        # Cleanup matters for long-lived processes, not interpreter exit
        # (module globals may already be torn down there).
        self._metrics_finalizer.atexit = False
        self._trace_path = trace_path
        self._iter_start = None   # perf_counter at iteration start
        self._iter_end = None     # set when the iteration finishes
        self._source_diag = None  # batch_source diagnostics snapshot
        self._base = self._metric_baseline()
        # Online autotuner (docs/guides/pipeline.md): the stage graph and
        # controller are built lazily at the first __iter__ so they bind
        # the source/reader objects as iterated. The default (None) builds
        # nothing — static behavior is bit-for-bit today's.
        if autotune is None or autotune is False:
            self._autotune_config = None
        elif autotune is True:
            self._autotune_config = {}
        elif isinstance(autotune, dict):
            allowed = {"interval_s", "bounds", "hysteresis",
                       "placement_hysteresis", "tolerance", "probe_defer",
                       "classify_kwargs", "rewrite_hysteresis", "rewrites",
                       "rewrite_thresholds"}
            unknown = set(autotune) - allowed
            if unknown:
                # A misspelled key would otherwise silently fall back to
                # the default — the user believes they tuned something.
                raise ValueError(
                    f"unknown autotune config key(s) {sorted(unknown)}; "
                    f"allowed: {sorted(allowed)}")
            self._autotune_config = dict(autotune)
        else:
            raise ValueError(
                "autotune must be None, True, or a config dict "
                "(interval_s/bounds/hysteresis/placement_hysteresis/"
                "tolerance/probe_defer/classify_kwargs/"
                "rewrite_hysteresis/rewrites/rewrite_thresholds)")
        self.autotune = None  # the AutotuneController once armed

    # -- diagnostics (derived from the metrics registry) -------------------

    def _metric_baseline(self):
        """Current registry child values — subtracted on read so the
        diagnostics dict stays per-iteration while the registry series
        stay monotonic for scrapers."""
        return {
            "batches": self._m_batches.value,
            "rows": self._m_rows.value,
            "h2d_bytes": self._h2d_bytes,
            "stage": {stage: child.sum
                      for stage, child in self._m_stage.items()},
            "reader": {stage: (child.sum, child.count)
                       for stage, child in self._m_reader.items()},
            "reader_bytes": READER_READ_BYTES.value,
        }

    @property
    def diagnostics(self):
        """Per-iteration pipeline counters, derived live from the metrics
        registry (``docs/guides/diagnostics.md``): ``batches``/``rows``
        yielded, the per-stage time breakdown (``producer_decode_s``,
        ``producer_queue_wait_s``, ``device_dispatch_s`` with its
        device-stage components ``raw_stage_s``/``device_decode_s``/
        ``shard_put_s``, ``stall_s``, ``consumer_s``), the dispatch
        ledger's ``dispatch_overlap_pct`` and staged ``h2d_bytes``, the
        in-process reader's worker stages (``reader_read_s``,
        ``reader_decode_s``, ``reader_transform_s``, ``reader_wait_s``,
        ``reader_row_groups``, ``reader_read_bytes`` — process-wide
        series, so every thread-pool reader in the process counts), and
        ``wall_s`` / ``input_stall_pct`` — the
        north-star metric — computed **at read time**, so a monitoring
        thread polling mid-epoch sees this epoch's live stall percentage,
        not the previous iteration's frozen one. ``source`` carries the
        batch_source's own diagnostics when one is plugged in."""
        now = time.perf_counter()
        start, end = self._iter_start, self._iter_end
        wall = 0.0 if start is None else max(0.0, (now if end is None
                                                   else end) - start)
        base = self._base
        stage = {name: max(0.0, child.sum - base["stage"][name])
                 for name, child in self._m_stage.items()}
        stall = stage["wait"]
        # Dispatch ledger: every device-dispatch stage (plain device_put,
        # raw-bytes staging, the fused on-device decode). The overlap gauge
        # reports how much of it rode inside the pipeline's OTHER work —
        # the producer's decode windows or the consumer's step window
        # (stage_in_producer dispatches inside the step wait) — instead of
        # extending the wall; 100 means dispatch is fully hidden. Crediting
        # only decode would misread the paced stage_in_producer regime as
        # 0% overlap while input_stall_pct ≈ 0 shows dispatch extended
        # nothing.
        dispatch = sum(stage[name] for name in _DISPATCH_STAGES)
        overlap_pct = (
            round(100.0 * max(0.0, min(1.0, (stage["decode"]
                                             + stage["consumer"] + dispatch
                                             - wall) / dispatch)), 2)
            if dispatch > 0 else 100.0)
        self._m_overlap.set(overlap_pct)
        out = {
            "batches": int(self._m_batches.value - base["batches"]),
            "rows": int(self._m_rows.value - base["rows"]),
            "stall_s": stall,
            "wall_s": wall,
            "input_stall_pct": (round(100.0 * stall / wall, 2)
                                if wall > 0 else 0.0),
            "max_batches": self._max_batches,
            # per-stage breakdown (stall root-causing):
            "producer_decode_s": stage["decode"],   # reader pull + collation
            "producer_queue_wait_s": stage["queue_wait"],
            "device_dispatch_s": dispatch,
            "raw_stage_s": stage["raw_stage"],
            "device_decode_s": stage["device_decode"],
            "shard_put_s": stage["shard_put"],
            "dispatch_overlap_pct": overlap_pct,
            # H2D payload bytes staged this iteration (raw uint8 bytes when
            # a device stage is armed — the uint8-vs-float32 ledger).
            "h2d_bytes": int(self._h2d_bytes - base["h2d_bytes"]),
            # Time the CONSUMER spends between taking a batch and asking
            # for the next (its step dispatch + device wait) — the other
            # side of the ledger from stall_s: wall ≈ stall_s + consumer_s
            # + loader bookkeeping. Lets a training loop reconcile "low
            # stall but below the step bound" by naming the consumer-side
            # residual instead of leaving it unattributed.
            "consumer_s": stage["consumer"],
            "reader_row_groups": int(self._m_reader["read"].count
                                     - base["reader"]["read"][1]),
            "reader_read_bytes": int(READER_READ_BYTES.value
                                     - base["reader_bytes"]),
        }
        for name, child in self._m_reader.items():
            out[f"reader_{name}_s"] = max(
                0.0, child.sum - base["reader"][name][0])
        if self._source_diag is not None:
            out["source"] = dict(self._source_diag)
        return out

    def exclude_stall_so_far(self):
        """Zero the per-iteration stall accounting up to this call — e.g.
        to exclude the pipeline-fill stall of the first batch, which every
        architecture pays once (``bench.py``'s realistic-step leg). The
        registry histogram keeps the full history; only the derived
        per-iteration view re-bases."""
        self._base["stage"]["wait"] = self._m_stage["wait"].sum

    def stage_quantiles(self, quantiles=(0.5, 0.99)):
        """Approximate per-batch latency quantiles for each pipeline stage,
        estimated from this loader's registry histograms (lifetime of the
        instance, not just the last iteration) — what the service
        scenario's ``--json-out`` telemetry block reports so BENCH
        artifacts capture distributions, not just means."""
        return {
            stage: {f"p{int(q * 100)}": child.quantile(q)
                    for q in quantiles}
            for stage, child in self._m_stage.items()
        }

    # -- runtime knobs (live-resizable: the autotuner's bindings) ----------

    @property
    def host_prefetch(self):
        """Bounded host-queue depth. Settable live: the bound applies to
        the running iteration's queue immediately (a producer blocked on
        the old, smaller bound is woken)."""
        return self._host_prefetch

    @host_prefetch.setter
    def host_prefetch(self, value):
        value = int(value)
        if value < 1:
            raise ValueError("host_prefetch must be >= 1")
        self._host_prefetch = value
        queue_ = (self._host_queue if self._stage_in_producer
                  else self._queue)
        if queue_ is not None:
            resize_bounded_queue(queue_, value)

    @property
    def device_prefetch(self):
        """In-flight device batches kept ahead. Settable live: the
        consumer's fill loop reads it per batch, so a raise deepens the
        window on the next fill and a shrink drains down naturally."""
        return self._device_prefetch

    @device_prefetch.setter
    def device_prefetch(self, value):
        value = int(value)
        if value < 1:
            raise ValueError("device_prefetch must be >= 1")
        self._device_prefetch = value
        if self._stage_in_producer and self._queue is not None:
            # In producer-staging mode the device queue's bound IS
            # device_prefetch (HBM budget) — resize it live too.
            resize_bounded_queue(self._queue, max(1, value))

    def _ensure_autotune(self):
        """Build (once) and start the autotune controller when armed."""
        if self._autotune_config is None:
            return
        if self.autotune is None:
            from petastorm_tpu.pipeline import (
                AutotuneController,
                Planner,
                build_loader_graph,
            )

            cfg = self._autotune_config
            graph = build_loader_graph(self, bounds=cfg.get("bounds"))
            planner = Planner(
                {name: knob.descriptor()
                 for name, knob in graph.knobs.items()},
                hysteresis=cfg.get("hysteresis", 2),
                placement_hysteresis=cfg.get("placement_hysteresis", 4),
                tolerance=cfg.get("tolerance", 0.05),
                probe_defer=cfg.get("probe_defer", 3),
                classify_kwargs=cfg.get("classify_kwargs"),
                # Graph rewrites (docs/guides/pipeline.md#graph-rewrites):
                # on by default — triggers gate them, so knob-only
                # workloads never probe one; rewrites=False pins the
                # PR 10 knob-only action space.
                rewrite_hysteresis=cfg.get("rewrite_hysteresis", 6),
                rewrites=cfg.get("rewrites", True),
                rewrite_thresholds=cfg.get("rewrite_thresholds"))
            self.autotune = AutotuneController(
                graph, interval_s=cfg.get("interval_s", 0.5),
                planner=planner)
        self.autotune.start()

    # -- producer ---------------------------------------------------------

    def _produce(self):
        try:
            if self._source_iter is not None:
                batches = iter(self._source_iter)
                if self._max_batches is not None:
                    import itertools

                    batches = itertools.islice(batches, self._max_batches)
            else:
                batches = iter(self._reader_batches())
            # With producer-side staging, decode feeds a separate staging
            # thread (see _stage_loop) so decode and H2D dispatch OVERLAP —
            # both release the GIL (pyarrow/cv2; transport writes), so even
            # a single-core host pipelines them instead of paying their sum.
            target = (self._host_queue if self._stage_in_producer
                      else self._queue)
            while True:
                with tracing.span("loader.decode",
                                  hist=self._m_stage["decode"]):
                    batch = next(batches, _SENTINEL)
                if batch is _SENTINEL:
                    break
                t0 = time.perf_counter()
                while not self._stop.is_set():
                    try:
                        target.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                # Drop the producer's reference the moment the queue owns
                # the batch: while the producer blocks on a full queue for
                # the NEXT batch, it must not pin a consumed one alive.
                batch = None
                self._m_stage["queue_wait"].observe(
                    time.perf_counter() - t0)
                if self._stop.is_set():
                    return
        except Exception as exc:  # surfaced on the consumer side
            self._producer_error = exc
        finally:
            target = (self._host_queue if self._stage_in_producer
                      else self._queue)
            self._put_sentinel(target)

    def _reader_batches(self):
        """The producer's batch stream off the local reader, with the
        decoded-batch cache in front when one is armed: a hit serves the
        whole epoch's collated sequence out of the cache (the reader is
        never pulled — re-iterating the loader replays the epoch even
        though the exhausted ``num_epochs=1`` reader would yield nothing);
        a miss streams batches through while writing them into an entry
        that is published only on clean exhaustion (an abandoned iteration
        can never be served as a complete epoch).

        Shuffle-compatible serving: when shuffling is requested (a
        shuffle buffer, an explicit ``shuffle_seed``, or a
        ``shuffle_row_groups`` reader), the entry stays canonical (the
        fill pass's decode order, read WITHOUT the shuffle buffer) and
        each pass serves it through a fresh seed-tree permutation at
        batch granularity — order changes per epoch, bytes don't, and
        the cache key is seed/epoch-invariant
        (``docs/guides/caching.md#shuffle-compatible-serving``). The
        shuffled fill pass buffers the epoch before serving (the entry
        IS the buffer), so its first batch arrives after the decode
        completes; warm passes stream immediately."""
        if self._batch_cache is None:
            yield from batch_iterator(
                self.reader, self._batch_size,
                last_batch=self._last_batch,
                max_batches=self._max_batches,
                shuffle_buffer_size=self._shuffle_buffer_size,
                shuffle_seed=self._shuffle_seed)
            return
        key = self._reader_cache_key()
        permute_seed = self._cache_permute_seed()
        if self._cache_resume_has_seed \
                and self._cache_resume_seed != permute_seed:
            raise ValueError(
                f"cache_resume was snapshotted under shuffle_seed="
                f"{self._cache_resume_seed!r} but this loader's effective "
                f"permutation seed is {permute_seed!r}: the resume "
                f"position indexes that seed's permutation, so resuming "
                f"here would silently re-serve some batches and skip "
                f"others — reconstruct the loader (and reader) with the "
                f"snapshot's shuffle configuration")
        cache_epoch = self._cache_epoch
        self._cache_epoch += 1
        skip, self._cache_skip = self._cache_skip, 0
        if permute_seed is not None:
            # Snapshot the pass BEFORE any yield: a state_dict() taken
            # mid-fill resumes at `skip` (nothing yielded yet). ``n`` is
            # filled in once the entry exists — state_dict uses it to
            # roll a COMPLETED pass forward to the next pass's start.
            self._cache_pass = {"cache_epoch": cache_epoch, "base": skip,
                                "seed": permute_seed, "n": None}
        entry, tier = self._batch_cache.get_tiered(key)
        if entry is not None:
            yield from self._serve_entry(entry, tier, permute_seed,
                                         cache_epoch, skip)
            return
        if self._cache_fill_attempted:
            # The reader's start position was already consumed (by a
            # complete OR abandoned earlier pass): what it yields now is a
            # tail of the stream, not an epoch — serve it uncached and
            # never commit it under the epoch key. Not a permuted cache
            # pass either: a state_dict() here has no replayable position.
            self._cache_pass = None
            produced = 0
            for batch in batch_iterator(self.reader, self._batch_size,
                                        last_batch=self._last_batch,
                                        max_batches=self._max_batches):
                produced += 1
                yield batch
            if produced == 0:
                # Miss over an exhausted reader: the epoch WAS cached once
                # (this loader filled it) but no tier holds it now — e.g.
                # a sibling loader's fill LRU-evicted it. The "replay"
                # is an empty epoch; say so instead of letting a
                # range(N)-epoch training loop end early in silence.
                import warnings

                warnings.warn(
                    "batch_cache miss over an exhausted reader: the "
                    "previously cached epoch entry is no longer retained "
                    "(evicted by other fills?), so this iteration yields "
                    "no batches — raise the cache budgets or enable the "
                    "disk tier", RuntimeWarning, stacklevel=2)
            return
        self._cache_fill_attempted = True
        builder = self._batch_cache.begin_fill(key)
        if permute_seed is not None:
            # Shuffled fill: buffer the canonical epoch into the entry
            # (no yields — the builder already holds every frame), then
            # serve it through this pass's permutation so epoch 1 is
            # shuffled too. The fill reads WITHOUT the shuffle buffer:
            # the entry must be canonical or two jobs with different
            # seeds could not share it.
            for batch in batch_iterator(self.reader, self._batch_size,
                                        last_batch=self._last_batch,
                                        max_batches=self._max_batches):
                if self._stop.is_set():
                    return  # abandoned fill: the builder never commits
                builder.add_batch(batch)
            entry = builder.commit()
            if not self._batch_cache.retained(key):
                import warnings

                warnings.warn(
                    "batch_cache could not retain this epoch's entry "
                    "(larger than the memory budget and no disk tier kept "
                    "it); re-iterating this exhausted reader will yield "
                    "no batches — raise mem_budget_bytes or enable the "
                    "disk tier", RuntimeWarning, stacklevel=2)
            yield from self._serve_entry(entry, None, permute_seed,
                                         cache_epoch, skip)
            return
        for batch in batch_iterator(self.reader, self._batch_size,
                                    last_batch=self._last_batch,
                                    max_batches=self._max_batches):
            builder.add_batch(batch)
            yield batch
        builder.commit()
        if not self._batch_cache.retained(key):
            # Committed but kept by no tier (the epoch outgrew every
            # budget): the replay contract cannot be honored — the next
            # iteration finds a miss over an exhausted reader and yields
            # an EMPTY epoch. Say so now, while the user can still raise
            # the budget, instead of ending training N-1 epochs early in
            # silence.
            import warnings

            warnings.warn(
                "batch_cache could not retain this epoch's entry (larger "
                "than the memory budget and no disk tier kept it); "
                "re-iterating this exhausted reader will yield no batches "
                "— raise mem_budget_bytes or enable the disk tier",
                RuntimeWarning, stacklevel=2)

    def _cache_permute_seed(self):
        """The serve-time permutation seed, or ``None`` when replays must
        be byte-exact (no shuffling requested — the pre-shuffle replay
        contract). Shuffling is requested by any of the loader's shuffle
        knobs or a ``shuffle_row_groups`` reader; the seed prefers the
        explicit ``shuffle_seed``, then the reader's ``shard_seed``, then
        0 (a fixed default — the determinism lint bans unseeded draws)."""
        ventilator = getattr(self.reader, "_ventilator", None)
        reader_shuffled = bool(getattr(ventilator, "_randomize_item_order",
                                       False))
        if not (self._shuffle_buffer_size or self._shuffle_seed is not None
                or reader_shuffled):
            return None
        if self._shuffle_seed is not None:
            return int(self._shuffle_seed)
        shard_seed = getattr(self.reader, "_shard_seed", None)
        return int(shard_seed) if shard_seed is not None else 0

    def _serve_entry(self, entry, tier, permute_seed, cache_epoch, skip):
        """Serve a whole-epoch cache entry, permuted when shuffling is
        requested: position ``i`` of the pass is the entry's
        ``order[i]``-th canonical batch, where ``order`` derives only
        from ``fold_in(seed, cache-epoch)`` — each pass reshuffles, every
        process replays the same orders, and ``skip`` (a resume position)
        indexes the PERMUTED stream so a restore continues mid-pass
        bit-exactly."""
        from petastorm_tpu.service.seedtree import fold_in, permutation

        if permute_seed is None:
            order = range(entry.num_batches)
        else:
            order = permutation(
                fold_in(int(permute_seed), ("cache-epoch", cache_epoch)),
                entry.num_batches)
            self._batch_cache.note_permuted_serve(tier or "mem")
            if self._cache_pass is not None:
                self._cache_pass["n"] = entry.num_batches
        for position, source in enumerate(order):
            if position < skip:
                continue
            yield entry.batch_at(source).to_dict()

    def _reader_cache_key(self):
        """Content fingerprint of everything that shapes this loader's
        batch sequence: the reader's resolved piece plan (path + row-group
        identity, so a re-materialized dataset misses), its schema view,
        transform, predicate, pass count and resume position, plus this
        loader's batching knobs. Deliberately EXCLUDES every shuffle
        ingredient (seed, flags, buffer size) — order is composed at
        serve time from the seed tree, so one canonical fill serves any
        seed and every epoch (``batch_fingerprint`` enforces the
        exclusion). Under ``shuffle_row_groups`` the canonical order is
        the fill pass's decode order: set ``shard_seed`` for a
        reproducible fill, or construct the reader unshuffled and let
        serve-time permutation do the shuffling."""
        from petastorm_tpu.cache_impl import batch_fingerprint

        reader = self.reader
        pieces = [(piece.path, piece.row_group)
                  for piece in getattr(reader, "_pieces", [])]
        return batch_fingerprint(
            reader._dataset_path_signature(), pieces, self._batch_size,
            fields=sorted(reader.schema.fields),
            transform=getattr(reader, "_transform_spec", None),
            factory=type(reader).__name__ + "/"
            + type(reader._results_queue_reader).__name__,
            extra={"last_batch": self._last_batch,
                   "max_batches": self._max_batches,
                   # num_epochs is CONTENT-shaping (how many passes of
                   # batches one entry holds), not serve order — it stays
                   # in the key, and keeping the PR 5 spelling means old
                   # disk entries are found and version-evicted instead
                   # of lingering as orphaned files.
                   "num_epochs": reader.num_epochs,
                   "predicate": repr(getattr(reader, "_predicate", None)),
                   "resume": repr(getattr(reader, "_resume_state", None))})

    def _stage_loop(self):
        """Staging thread (producer-side staging only): host batches →
        ``device_put`` dispatch → the device queue. Runs concurrently with
        the decode thread, so per-batch pipeline cost is
        max(decode, dispatch), not their sum."""
        try:
            while not self._stop.is_set():
                try:
                    batch = self._host_queue.get(timeout=0.1)
                except queue.Empty:
                    continue
                if batch is _SENTINEL:
                    break
                with tracing.span("loader.device_put"):
                    # _stage observes the dispatch-stage histograms itself
                    # (device_put / raw_stage / device_decode).
                    batch = self._stage(batch)
                while not self._stop.is_set():
                    try:
                        self._queue.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                # The batch is DEVICE-resident here: a lingering reference
                # while this thread blocks on the bounded device queue
                # would hold one extra batch of HBM beyond the
                # device_prefetch budget.
                batch = None
        except Exception as exc:  # surfaced on the consumer side
            self._producer_error = exc
        finally:
            self._put_sentinel(self._queue)

    def _put_sentinel(self, q):
        # The sentinel MUST land or the downstream blocks forever; retry in
        # a stop-checking loop (the consumer may legitimately pause far
        # longer than any fixed timeout — e.g. first-step XLA compile).
        while True:
            try:
                q.put(_SENTINEL, timeout=0.1)
                break
            except queue.Full:
                if self._stop.is_set():
                    break

    # -- consumer ---------------------------------------------------------

    def __iter__(self):
        # A previous iteration's threads may still be running (producer
        # pulling the non-thread-safe reader, stager mid-device_put); BOTH
        # must be stopped and joined before the queues are reassigned — a
        # surviving old thread would inject stale batches and a premature
        # sentinel into the new iteration's queues. Each is checked
        # independently: the producer can exit quickly while the stager is
        # still inside a long dispatch.
        stale = [("producer", self._producer), ("stager", self._stager)]
        if any(t is not None and t.is_alive() for _, t in stale):
            self.stop()
            for name, t in stale:
                if t is None:
                    continue
                t.join(timeout=30)
                if t.is_alive():
                    raise RuntimeError(
                        f"Previous iteration's {name} thread did not stop "
                        "within 30s (blocked on reader I/O or a device "
                        "call?); cannot safely re-iterate")
        # A previous DIRECT iteration has no loader threads, but its source
        # iterator may still own live reader threads (the service drain) —
        # close it before a new iteration resets the source's bookkeeping
        # under them. Also keeps an abandoned first iteration's later
        # finalization from touching the new iteration's source.
        if self._source_iter is not None:
            close = getattr(self._source_iter, "close", None)
            if callable(close):
                close()
        # With producer-side staging the device queue holds DEVICE-resident
        # batches, so its depth is bounded by the device budget
        # (device_prefetch), not the host budget — otherwise device-resident
        # batches grow to host_prefetch + device_prefetch and can OOM a
        # model that fit with consumer-side staging. Total in-flight device
        # batches stay <= 2 * device_prefetch (+1 in the stager's hand);
        # decoded host batches additionally buffer up to host_prefetch
        # between the decode and staging threads (the overlap window).
        # A batch_source whose iterator declares itself ``prefetched`` (the
        # data service's multiplexed drain: its own reader threads feeding a
        # bounded ready-queue) is consumed DIRECTLY on the iterating thread:
        # the producer thread would be pure plumbing between two bounded
        # queues — one extra thread wakeup per batch on the hot path, with
        # no extra buffering to show for it. Prefetch depth and
        # backpressure stay the source's (ready-queue + credit window).
        self._source_iter = None
        self._direct_iter = None
        direct = False
        if self._batch_source is not None:
            self._source_iter = self._batch_source()
            direct = (not self._stage_in_producer
                      and getattr(self._source_iter, "prefetched", False))
        if direct:
            batches = iter(self._source_iter)
            if self._max_batches is not None:
                import itertools

                batches = itertools.islice(batches, self._max_batches)
            self._direct_iter = batches
            self._queue = None
            self._host_queue = None
        else:
            maxsize = (max(1, self._device_prefetch)
                       if self._stage_in_producer else self._host_prefetch)
            self._queue = queue.Queue(maxsize=maxsize)
            self._host_queue = (queue.Queue(maxsize=self._host_prefetch)
                                if self._stage_in_producer else None)
        self._stop.clear()
        self._producer_error = None
        # Yielded-row accounting is relative to the reader's delivery
        # tracker; reader.reset() installs a fresh tracker (counts restart
        # at zero), so the yielded counter must restart with it.
        tracker = getattr(self.reader, "_delivery_tracker", None)
        if tracker is not self._yield_count_tracker:
            self._yield_count_tracker = tracker
            self._total_rows_yielded = 0
        # Diagnostics are per-iteration: stall/wall must describe one pass or
        # input_stall_pct (the north-star metric) is meaningless. The
        # registry series are monotonic; the per-iteration view re-bases on
        # this baseline.
        self._base = self._metric_baseline()
        self._iter_start = time.perf_counter()
        self._iter_end = None
        if self._trace_path is not None:
            # Scoped arming: the first armer clears the buffer (each
            # iteration exports a fresh trace — without the clear, epoch
            # N's file would replay epochs 1..N-1 and the bounded buffer
            # would eventually freeze on the earliest spans); a second
            # trace-armed loader (mid-epoch eval) joins the running trace
            # instead of wiping it.
            tracing.COLLECTOR.acquire()
        if self._direct_iter is None:
            self._producer = threading.Thread(target=self._produce,
                                              daemon=True,
                                              name="jax-loader-producer")
            self._producer.start()
            if self._stage_in_producer:
                self._stager = threading.Thread(target=self._stage_loop,
                                                daemon=True,
                                                name="jax-loader-stager")
                self._stager.start()
        else:
            self._producer = None
            self._stager = None
        self._ensure_autotune()
        return self._iterate()

    def _iterate(self):
        inflight = []       # device batches dispatched ahead (double buffer)
        inflight_bids = []  # their trace batch ids (direct source path)
        done = False
        direct = self._direct_iter
        collector = tracing.COLLECTOR
        # Captured so the finally tears down THIS iteration's source even
        # if a newer iteration has since replaced the attribute.
        source_iter = self._source_iter
        # Seed the source's delivery/recovery counters at iteration START
        # (the finally refreshes them at the end): a consumer polling
        # diagnostics mid-epoch — a stall dashboard, the chaos harness —
        # must see the "source" stage without waiting for the pass to end.
        self._snapshot_source_diagnostics()
        self._iter_start = time.perf_counter()
        try:
            while True:
                # Keep device_prefetch batches in flight.
                while not done and len(inflight) < self._device_prefetch:
                    with tracing.span("loader.wait",
                                      hist=self._m_stage["wait"]) as span:
                        # Direct path: pull the prefetched source here
                        # (its reader threads are the producers); an error
                        # raises inline — no sentinel relay needed.
                        host_batch = (next(direct, _SENTINEL)
                                      if direct is not None
                                      else self._queue.get())
                        # Direct-source batches carry the worker-minted
                        # batch id (the source sets last_bid as it yields,
                        # on this same thread) — the key that joins loader
                        # spans to the batch's worker/client lifecycle.
                        bid = span.bid = (
                            getattr(self._batch_source, "last_bid", None)
                            if direct is not None else None)
                    if host_batch is _SENTINEL:
                        done = True
                        if self._producer_error is not None:
                            raise self._producer_error
                        break
                    if self._stage_in_producer:
                        inflight.append(host_batch)  # already on device
                    else:
                        with tracing.span("loader.device_put", bid=bid):
                            # _stage observes the dispatch-stage histograms
                            # itself (device_put/raw_stage/device_decode).
                            inflight.append(self._stage(host_batch))
                    # Release the host copy now that the device owns one:
                    # keeping it across further fill iterations would pin
                    # up to device_prefetch extra host batches.
                    host_batch = None
                    inflight_bids.append(bid)
                if not inflight:
                    return
                batch = inflight.pop(0)
                bid = inflight_bids.pop(0) if inflight_bids else None
                self._m_batches.inc()
                rows_in_batch = self._batch_rows(batch)
                self._m_rows.inc(rows_in_batch)
                if PAD_MASK_KEY in batch:
                    # Count only real rows toward resume accounting (the
                    # device pull happens at most once, on the padded final
                    # batch of a stream).
                    rows_in_batch = int(np.asarray(
                        batch[PAD_MASK_KEY]).sum())
                self._total_rows_yielded += rows_in_batch
                # The training step between yields: not a loader stage, so
                # no profiler annotation (it would hold the caller's step).
                with tracing.span("loader.consumer", bid=bid,
                                  hist=self._m_stage["consumer"],
                                  annotate=False):
                    yield batch
                # Drop the loader's reference to the consumed batch BEFORE
                # dispatching the next fill: if the consumer's step donated
                # (or discarded) these buffers, a lingering reference here
                # would pin one extra batch of HBM per deep-prefetch slot.
                batch = None
        finally:
            self._iter_end = time.perf_counter()
            # A batch_source with its own delivery counters (e.g. the data
            # service's per-worker stall / ready-queue / credit numbers)
            # lands in the stage breakdown, so one diagnostics dict
            # root-causes a stall across the whole delivery path.
            self._snapshot_source_diagnostics()
            # Reading diagnostics refreshes the dispatch-overlap gauge, so
            # a scrape-only consumer (metrics server armed, dict never
            # read) still sees the iteration's final overlap, not the
            # gauge's 0.0 birth value.
            self.diagnostics
            if self._trace_path is not None:
                collector.export(self._trace_path)
                # Balance the __iter__ acquire: collection stops when the
                # LAST trace-armed consumer finishes, not when the first
                # one does.
                collector.release()
            # Generator abandoned (break) or exhausted: stop the producer so
            # it doesn't keep decoding the rest of the dataset forever. On
            # the direct path, closing the source iterator is what tears
            # down its reader threads and sockets (a no-op if a newer
            # iteration's __iter__ already closed it).
            if direct is not None and source_iter is not None:
                close = getattr(source_iter, "close", None)
                if callable(close):
                    close()
            self.stop()

    def _snapshot_source_diagnostics(self):
        """Copy the batch_source's diagnostics dict (if it has one) into
        the ``diagnostics["source"]`` stage slot."""
        source_diag = (getattr(self._batch_source, "diagnostics", None)
                       if self._batch_source is not None else None)
        if isinstance(source_diag, dict):
            self._source_diag = dict(source_diag)

    @staticmethod
    def _batch_rows(batch):
        for name, col in batch.items():
            if name == PAD_MASK_KEY:
                continue
            try:
                return int(np.asarray(col.shape[0]).item()) \
                    if hasattr(col, "shape") else len(col)
            except TypeError:
                continue
        return 0

    def _stage(self, host_batch):
        """Numpy batch dict → device (or pass through when staging is off).

        With a :class:`DeviceStage` armed the image fields take the raw
        path instead: stage the uint8 BYTES (timed as ``raw_stage``; 4x
        fewer H2D bytes than float32 pixels), then dispatch the fused
        on-device decode/augment kernel (timed as ``device_decode``) which
        the stage donates its raw input to — the loader drops its own raw
        references immediately, so in-flight HBM is the decoded outputs
        plus at most one raw batch.
        """
        if not self._stage_to_device:
            return host_batch
        import jax

        from petastorm_tpu.jax_utils.sharding import (
            local_data_to_global_array,
        )

        raw = {}
        if self._device_stage is not None:
            raw, host_batch = self._device_stage.split(host_batch)
        out, tensors = {}, {}
        # All dispatch timing lives HERE (not in the callers): the
        # ``device_put`` stage is the plain-tensor put time only, so the
        # dispatch ledger (device_put + raw_stage + device_decode) never
        # double-counts.
        put_s = 0.0
        for name, col in host_batch.items():
            arr = np.asarray(col)
            if arr.dtype == object or arr.dtype.kind in ("U", "S", "M", "m"):
                if self._non_tensor_policy == "error":
                    raise TypeError(
                        f"Column {name!r} has non-tensor dtype {arr.dtype}; "
                        f"set non_tensor_policy='host' or 'drop', select "
                        f"numeric schema_fields, or add a TransformSpec")
                if self._non_tensor_policy == "drop":
                    continue
                out[name] = arr  # host-side passthrough
                continue
            if self._sharding is not None:
                self._h2d_bytes += arr.nbytes
                t0 = time.perf_counter()
                out[name] = local_data_to_global_array(
                    self._sharding, arr,
                    observe_shard_put=self._m_stage["shard_put"].observe)
                put_s += time.perf_counter() - t0
            else:
                tensors[name] = arr
        if tensors:
            # One device_put for the whole batch pytree: one dispatch, and the
            # runtime can batch the transfers.
            device = self._device or jax.local_devices()[0]
            self._h2d_bytes += sum(a.nbytes for a in tensors.values())
            t0 = time.perf_counter()
            out.update(jax.device_put(tensors, device))
            put_s += time.perf_counter() - t0
        self._m_stage["device_put"].observe(put_s)
        if raw:
            step = self._stage_step
            self._stage_step += 1
            observe_shard = self._m_stage["shard_put"].observe
            with tracing.span("loader.raw_stage",
                              hist=self._m_stage["raw_stage"]):
                if self._sharding is not None:
                    raw_dev = {
                        name: local_data_to_global_array(
                            self._sharding, arr,
                            observe_shard_put=observe_shard)
                        for name, arr in raw.items()}
                else:
                    device = self._device or jax.local_devices()[0]
                    raw_dev = jax.device_put(raw, device)
            raw_bytes = sum(a.nbytes for a in raw.values())
            self._h2d_bytes += raw_bytes
            self._device_stage.h2d_bytes += raw_bytes
            raw = None  # the kernel owns (and may donate) the raw buffers
            with tracing.span("loader.device_decode",
                              hist=self._m_stage["device_decode"]):
                out.update(self._device_stage.apply(raw_dev, step))
            raw_dev = None  # donated to the kernel — drop ours immediately
        return out

    # -- checkpoint / resume ----------------------------------------------

    def state_dict(self):
        """Input-pipeline checkpoint aligned to what this loader has YIELDED.

        The producer thread pulls rows from the reader ahead of the training
        loop (host queue + device prefetch + shuffle buffer), so the reader's
        own ``state_dict()`` would over-count by whatever is buffered. This
        method subtracts the buffered rows (recorded-by-reader minus
        yielded-by-loader) so buffered rows are re-read on resume
        (at-least-once). Call it between steps from the training thread, then
        pass the result as ``resume_state=`` to the reader factory feeding a
        fresh loader.
        """
        if self._batch_source is not None:
            # A source that knows how to checkpoint itself (e.g. the data
            # service's ServiceBatchSource tracks completed splits) owns the
            # snapshot: delegate. Sources accepting ``yielded_batches`` get
            # this loader's yielded-batch count so batches still buffered in
            # the prefetch queues stay un-checkpointed and are re-delivered
            # on resume (at-least-once, the same contract as the reader
            # path's buffered-row re-read).
            source_state = getattr(self._batch_source, "state_dict", None)
            if callable(source_state):
                import inspect

                try:
                    params = inspect.signature(source_state).parameters
                except (TypeError, ValueError):  # builtins, C callables
                    params = {}
                accepts_yielded = "yielded_batches" in params or any(
                    p.kind is inspect.Parameter.VAR_KEYWORD
                    for p in params.values())
                if accepts_yielded:
                    return source_state(yielded_batches=int(
                        self._m_batches.value - self._base["batches"]))
                return source_state()
            raise ValueError(
                "state_dict is not supported with a custom batch_source "
                "that has no state_dict() of its own (e.g. the packed "
                "loader): yielded-row accounting cannot attribute repacked "
                "batches to reader deliveries. Checkpoint at an epoch "
                "boundary with the reader's state_dict(), or give the "
                "source a state_dict()")
        if self._batch_cache is not None and self._cache_pass is not None:
            # A shuffled cache pass (fill or replay): the resumable
            # position is a batch index into the pass's PERMUTED stream —
            # yielded batches only, so anything still in the prefetch
            # queues is re-served on resume (and nothing twice: the
            # resume skips exactly the yielded prefix of the same
            # deterministic permutation). Pass the dict back as
            # ``JaxDataLoader(cache_resume=...)`` with the same reader
            # construction and cache; a cold cache on resume re-fills
            # canonically and then seeks, so the restore works from a
            # fresh process too.
            pass_info = self._cache_pass
            yielded = pass_info["base"] + int(
                self._m_batches.value - self._base["batches"])
            cache_epoch = pass_info["cache_epoch"]
            n = pass_info.get("n")
            if n is not None and yielded >= n:
                # The pass is fully consumed: snapshot the NEXT pass's
                # start, not position n of this one — resuming "at the
                # end of pass k" must serve pass k+1, not an empty (or,
                # cold, a re-decoded-for-nothing) remainder of pass k.
                cache_epoch, yielded = cache_epoch + 1, 0
            return {
                "version": 1,
                "kind": "cache_replay",
                "cache_epoch": cache_epoch,
                "batches_yielded": yielded,
                "shuffle_seed": pass_info["seed"],
            }
        tracker = getattr(self.reader, "_delivery_tracker", None)
        if tracker is None or not hasattr(self.reader, "state_dict"):
            raise TypeError(
                "state_dict requires a petastorm_tpu Reader (got "
                f"{type(self.reader).__name__})")
        if self._shuffle_buffer_size:
            raise ValueError(
                "state_dict is not supported with shuffle_buffer_size > 0: "
                "the shuffle buffer reorders rows, so buffered rows cannot "
                "be attributed to recent deliveries (an old row may still "
                "be held while newer row groups drained). Shuffle with "
                "shuffle_row_groups/shard_seed instead, or checkpoint at "
                "an epoch boundary with the reader's state_dict()")
        return self.reader.state_dict(yielded_rows=self._total_rows_yielded)

    # -- lifecycle --------------------------------------------------------

    def stop(self):
        """Teardown-only: signals the threads and DISCARDS one queued batch
        per queue to unblock a producer/stager waiting on a full queue.
        Never call it to pause a stream you intend to keep consuming — the
        discarded batches are gone (resume accounting stays correct: the
        at-least-once contract re-reads buffered-but-unyielded rows)."""
        self._stop.set()
        if self.autotune is not None:
            self.autotune.stop()
        for q in (self._queue, self._host_queue):
            if q is not None:
                try:  # unblock a producer/stager waiting on a full queue
                    q.get_nowait()
                except queue.Empty:
                    pass

    def join(self):
        if self._producer is not None:
            self._producer.join(timeout=30)
        if self._stager is not None:
            self._stager.join(timeout=30)
        if self.autotune is not None:
            self.autotune.join()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
        self.join()
        # reader is None when a custom batch_source owns the pipeline (e.g.
        # the data service's ServiceBatchSource — no local reader exists).
        if self.reader is not None:
            self.reader.stop()
            self.reader.join()
