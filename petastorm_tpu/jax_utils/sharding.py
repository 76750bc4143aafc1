"""Sharding helpers: pod-aware reader shards + global jax.Array assembly.

TPU-first replacement for the reference's implicit Horovod-rank sharding
(SURVEY.md §5 "distributed communication backend"): the reference expects the
user to pass ``cur_shard=hvd.rank(), shard_count=hvd.size()``; here the
defaults come from ``jax.process_index()/process_count()`` so a pod "just
works", and batches can be assembled into globally-sharded ``jax.Array`` s for
pjit. The data plane still never crosses hosts — each host reads its own row
groups from the (DCN-attached) store; ICI collectives belong to the training
step, exactly as the scaling recipe prescribes.
"""

from __future__ import annotations

import random
import warnings


def default_shard_options(cur_shard=None, shard_count=None):
    """Fill (cur_shard, shard_count) from the JAX runtime when unset.

    Single-process (or JAX absent): (None, None) — no sharding, matching the
    reference's default behavior. A backend that fails to initialize raises.
    """
    if cur_shard is not None or shard_count is not None:
        return cur_shard, shard_count
    try:
        import jax
    except ImportError:  # pragma: no cover - jax not installed
        return None, None
    if jax.process_count() > 1:
        return jax.process_index(), jax.process_count()
    return None, None


def split_pieces_for_shards(pieces, shard_count, shard_seed=None):
    """Partition a row-group piece list into ``shard_count`` shards.

    Single source of truth for the shard arithmetic: the optional
    ``shard_seed`` pre-shuffle followed by round-robin ``pieces[s::count]`` —
    exactly what ``Reader`` does (reference parity:
    ``petastorm/reader.py`` shard logic), so metadata-only step-count
    computations agree with what each host's reader will actually deliver.
    """
    if shard_count is None:
        return [list(pieces)]
    if shard_seed is not None:
        pieces = list(pieces)
        random.Random(shard_seed).shuffle(pieces)
    return [pieces[s::shard_count] for s in range(shard_count)]


def _batches_for_rows(rows, batch_size, last_batch):
    """Number of batches ``batch_iterator`` emits for a ``rows``-row stream."""
    if rows <= 0:
        return 0
    if last_batch == "drop":
        return rows // batch_size
    # "pad" and "keep" both emit the final partial batch.
    return -(-rows // batch_size)


def global_step_count(dataset_url, batch_size, shard_count,
                      last_batch="drop", num_epochs=1, shard_seed=None,
                      filters=None, storage_options=None, filesystem=None,
                      hdfs_driver="libhdfs"):
    """Global per-host step count for SPMD lockstep — pure metadata arithmetic.

    pjit programs are SPMD-synchronous: every host must dispatch the same
    number of steps or the pod deadlocks (SURVEY.md §7 hard-part #2). The
    reference's round-robin row-group sharding gives *unequal* row counts per
    shard, so the safe global step count is the **minimum** over shards of the
    number of batches that shard can produce. This helper computes it from
    Parquet metadata alone (no data read): per-shard row counts via the same
    enumeration + shard arithmetic the Reader uses, then the batcher's
    ``last_batch`` policy.

    Pass the result as ``max_batches`` to every host's
    :func:`~petastorm_tpu.jax_utils.make_jax_dataloader` (done automatically
    when a ``sharding`` is given and the reader carries shard metadata — see
    :func:`derive_equal_step_max_batches`).

    Exact when no row-level ``predicate`` is used (``filters`` prune whole row
    groups, so metadata counts stay exact). With a predicate the surviving row
    count is data-dependent — coordinate steps out of band instead.

    :param num_epochs: must be a finite int (``None``/infinite streams have no
        step count).
    :return: int — the global minimum number of full batches across shards
        (0 when any shard is empty: the only safe lockstep count).
    """
    if num_epochs is None:
        raise ValueError(
            "global_step_count requires a finite num_epochs (an infinite "
            "stream has no step count)")
    if shard_count is None or shard_count < 1:
        raise ValueError("shard_count must be a positive integer")
    from petastorm_tpu.fs_utils import FilesystemResolver
    from petastorm_tpu.reader.reader import enumerate_row_group_pieces

    resolver = FilesystemResolver(dataset_url, hdfs_driver=hdfs_driver,
                                  storage_options=storage_options,
                                  filesystem=filesystem)
    from petastorm_tpu.etl.metadata import piece_row_counts

    fs = resolver.filesystem()
    pieces = enumerate_row_group_pieces(fs, resolver.get_dataset_path(),
                                        filters)
    counts = piece_row_counts(fs, pieces)
    shards = split_pieces_for_shards(pieces, shard_count, shard_seed)
    return min(
        _batches_for_rows(
            sum(counts[(p.path, p.row_group)] for p in shard) * num_epochs,
            batch_size, last_batch)
        for shard in shards)


def derive_equal_step_max_batches(reader, batch_size, last_batch="drop"):
    """Derive a pod-safe ``max_batches`` from a constructed Reader, or None.

    Readers record the row counts of *every* shard at planning time
    (``Reader.shard_row_counts``) — each host can therefore compute the global
    minimum locally, with zero cross-host communication (consistent because
    all hosts enumerate the same store with the same shard_seed). Returns
    None when the count cannot be known from metadata: row-level predicate,
    NGram windows (windows per row group are data-dependent), infinite
    epochs, or a reader type that doesn't expose shard metadata.
    """
    # Cheap disqualifiers first: shard_row_counts is a lazy property that may
    # open parquet footers (one read per file on an object store) — don't pay
    # that when derivation is rejected anyway.
    num_epochs = getattr(reader, "num_epochs", 1)
    if num_epochs is None:
        warnings.warn(
            "Cannot derive an equal SPMD step count for an infinite stream "
            "(num_epochs=None). Pass max_batches explicitly (agreed across "
            "hosts) or steps may deadlock the pod",
            UserWarning, stacklevel=3)
        return None
    if getattr(reader, "ngram", None) is not None:
        warnings.warn(
            "Cannot derive an equal SPMD step count for an NGram reader: "
            "windows per row group are data-dependent. Pass max_batches "
            "explicitly (agreed across hosts) or steps may deadlock the pod",
            UserWarning, stacklevel=3)
        return None
    if getattr(reader, "_resume_state", None) is not None:
        warnings.warn(
            "Cannot derive an equal SPMD step count for a resumed reader: "
            "remaining rows are checkpoint-dependent. Pass max_batches "
            "explicitly (observe via count_deliverable_batches, agree via "
            "agree_max_batches)",
            UserWarning, stacklevel=3)
        return None
    if getattr(reader, "_predicate", None) is not None:
        warnings.warn(
            "Cannot derive an equal SPMD step count: a row-level predicate "
            "makes per-shard row counts data-dependent. Pass max_batches "
            "explicitly (observe via count_deliverable_batches, agree via "
            "agree_max_batches) or steps may deadlock the pod",
            UserWarning, stacklevel=3)
        return None
    transform_spec = getattr(reader, "_transform_spec", None)
    if transform_spec is not None and getattr(transform_spec, "func",
                                              None) is not None:
        # A TransformSpec func may drop/duplicate rows (it rewrites the whole
        # frame/batch), so metadata row counts no longer predict delivered
        # rows — same data-dependence hazard as a predicate. Schema-only
        # specs (func=None, edit/removed fields) cannot change row counts
        # and keep automatic derivation.
        warnings.warn(
            "Cannot derive an equal SPMD step count: a TransformSpec can "
            "change per-shard row counts. Pass max_batches explicitly "
            "(observe via count_deliverable_batches, agree via "
            "agree_max_batches) or steps may deadlock the pod",
            UserWarning, stacklevel=3)
        return None
    counts = getattr(reader, "shard_row_counts", None)
    if counts is None:
        return None
    return min(_batches_for_rows(c * num_epochs, batch_size, last_batch)
               for c in counts)


def agree_max_batches(local_count, reduce="min"):
    """Agree a pod-safe ``max_batches`` from per-host OBSERVED batch counts.

    Closes the loop for every case :func:`derive_equal_step_max_batches`
    declines (row-level predicate, NGram windows, TransformSpec funcs,
    resumed readers): each host observes how many batches it can actually
    deliver — e.g. one ``stage_to_device=False`` counting pass over its
    reader, or an application-side row count — and this helper agrees the
    global value with ONE tiny collective (``jax.experimental.
    multihost_utils.process_allgather`` of a single int64; control plane
    only, no data moves).

    :param local_count: this host's locally-observed deliverable batch count.
    :param reduce: ``"min"`` (default — the only *safe* lockstep count with
        ragged shards: every host can deliver at least the minimum) or
        ``"host0"`` (adopt host 0's count — only when the caller guarantees
        every host can deliver it, e.g. a deliberately truncated run).
    :return: the agreed global count (``local_count`` unchanged when
        running single-process).
    """
    if reduce not in ("min", "host0"):
        raise ValueError(f"reduce {reduce!r} is not 'min' or 'host0'")
    local_count = int(local_count)
    try:
        import jax
    except ImportError:  # pragma: no cover - jax not installed
        return local_count
    if jax.process_count() == 1:
        return local_count
    import numpy as np

    from jax.experimental import multihost_utils

    counts = np.asarray(multihost_utils.process_allgather(
        np.asarray([local_count], np.int64)))
    return int(counts.min()) if reduce == "min" else int(counts.flat[0])


def count_deliverable_batches(reader, batch_size, last_batch="drop"):
    """Count the batches ``reader`` can deliver by DRAINING it once (a
    host-side counting pass — no device, no decode retention).

    The observation half of :func:`agree_max_batches` for data-dependent
    pipelines (predicates, NGram): run this on a *separately constructed*
    reader with the same arguments, agree the result across hosts, then pass
    it as ``max_batches`` to the real loader. The counting pass pays one
    decode sweep — worth it once per training run when the alternative is a
    pod deadlock.
    """
    from petastorm_tpu.jax_utils.batcher import batch_iterator

    if getattr(reader, "num_epochs", 1) is None:
        raise ValueError(
            "count_deliverable_batches would never terminate on an infinite "
            "reader (num_epochs=None): construct the counting reader with "
            "num_epochs=1 and scale the agreed count by your epoch budget")
    n = 0
    with reader:
        for _ in batch_iterator(reader, batch_size, last_batch=last_batch):
            n += 1
    return n


def batch_sharding(mesh, axis="data"):
    """NamedSharding that splits the batch (leading) axis over ``mesh[axis]``.

    The standard data-parallel input sharding: every other array dim is
    replicated; model/tensor axes of the mesh replicate the input so the
    training step's pjit can re-shard activations as it likes.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(axis))


def local_data_to_global_array(sharding, array, observe_shard_put=None):
    """Host-local numpy batch → globally-sharded ``jax.Array``.

    Sharding-aware DIRECT delivery on the fast path: when every device of
    ``sharding`` is addressable from this process (the single-controller
    case — one host driving its own chips), each device's slice is
    ``device_put`` straight onto its target device and the global array is
    assembled with ``jax.make_array_from_single_device_arrays`` — per-shard
    H2D transfers with no intermediate host-side global buffer, so each
    device receives exactly its rows. Multi-process shardings (a pod) fall
    back to ``jax.make_array_from_process_local_data``: each host
    contributes its shard of the global batch; XLA never moves data over
    DCN — the global array is metadata stitching over per-host HBM buffers.

    :param observe_shard_put: optional callable receiving each per-shard
        ``device_put``'s dispatch seconds (the loader feeds its
        ``shard_put`` stage histogram through this).
    """
    import jax
    import numpy as np

    arr = np.asarray(array)
    if not getattr(sharding, "is_fully_addressable", False):
        return jax.make_array_from_process_local_data(sharding, arr)
    import time

    # Fully addressable ⇒ the process-local batch IS the global batch.
    index_map = sharding.addressable_devices_indices_map(arr.shape)
    shards = []
    for device, index in index_map.items():
        t0 = time.perf_counter()
        shards.append(jax.device_put(arr[index], device))
        if observe_shard_put is not None:
            observe_shard_put(time.perf_counter() - t0)
    return jax.make_array_from_single_device_arrays(arr.shape, sharding,
                                                    shards)
