"""Joint model + input-pipeline checkpointing (orbax + reader state).

The reference has no checkpointable reader state at all (SURVEY.md §5
"Checkpoint / resume: absent for readers"); this framework added resumable
iteration (``Reader.state_dict`` / ``resume_state=``,
``JaxDataLoader.state_dict``). What was still the user's job is gluing that
to MODEL checkpointing so a preempted training job restores both halves
consistently — this module is that glue:

- model arrays (params / optimizer state — any pytree of jax/numpy arrays)
  go through ``orbax.checkpoint`` (async-capable, TPU-aware restore);
- the loader/reader input state (a small JSON-serializable dict) rides in
  the same checkpoint directory as a JSON file, captured BETWEEN steps from
  the training thread — the consistency point the resume machinery is
  specified against. Local-reader states resume at-least-once (buffered
  rows re-read); a ``ServiceBatchSource`` state is a v2 watermark snapshot,
  so a service-fed job resumes **exactly-once** — each mid-piece piece
  continues at its next batch — and with the dispatcher's ``shuffle_seed``
  plus ``ordered=True`` delivery the restored stream is bit-identical to
  the uninterrupted run from the checkpoint batch onward
  (``docs/guides/service.md#delivery-semantics``).

Crash safety is pointer-file based: each save writes a COMPLETE checkpoint
(arrays + input state + per-host commit markers) into a fresh versioned
subdirectory, then atomically publishes it by ``os.replace``-ing the
``CURRENT`` pointer file. A crash at ANY point leaves ``CURRENT`` aimed at
the last fully-committed version — there is no window in which the previous
good checkpoint is unrestorable. Superseded versions are pruned on the next
successful save.

On a pod every host checkpoints its OWN input state (shard identity is part
of it) while orbax handles the array layout; restore hands each host back
the state it saved (``input_state.<process_index>.json``) and refuses a
checkpoint whose host count differs from the restoring job's.
"""

from __future__ import annotations

import json
import os
import shutil

_INPUT_STATE_TMPL = "input_state.{}.json"
_COMMIT_MARKER_PREFIX = "COMMITTED."
_CURRENT_FILE = "CURRENT"
_VERSION_TMPL = "v{}"
_ARRAYS_DIR = "arrays"
_checkpointer = None


def _process_index():
    try:
        import jax
    except ImportError:  # pragma: no cover - jax not installed
        return 0
    return jax.process_index()


def _process_count():
    try:
        import jax
    except ImportError:  # pragma: no cover - jax not installed
        return 1
    return jax.process_count()


def _get_checkpointer():
    """One orbax checkpointer per process: StandardCheckpointer owns async
    background resources, so constructing one per save would leak them."""
    global _checkpointer
    if _checkpointer is None:
        import orbax.checkpoint as ocp

        _checkpointer = ocp.StandardCheckpointer()
    return _checkpointer


def _barrier(name):
    """Cross-host barrier (no-op single-host): hosts must not race each
    other through the version-dir lifecycle on a shared filesystem."""
    if _process_count() > 1:  # pragma: no cover - single-host test env
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def _agree_version(next_num):
    """Process 0's version number, broadcast to every host. Each host reads
    CURRENT from the shared filesystem independently; with stale attribute
    caching (NFS) they can disagree — and since the barrier names embed the
    version string, a disagreement would make ``sync_global_devices`` hang
    on mismatched barrier names instead of failing cleanly. Agreeing the
    number via a device collective first makes the barrier names provably
    identical on every host."""
    if _process_count() > 1:
        import numpy as np
        from jax.experimental import multihost_utils

        next_num = int(
            multihost_utils.broadcast_one_to_all(np.int64(next_num)))
    return next_num


def _is_version_name(name):
    """Strictly ``v<int>`` — the only names this module creates; anything
    else in the directory belongs to the user and must never be pruned."""
    return name.startswith("v") and name[1:].isdigit()


def _read_current(directory):
    """Version name ``CURRENT`` points at, or ``None`` if unpublished."""
    try:
        with open(os.path.join(directory, _CURRENT_FILE)) as f:
            name = f.read().strip()
        return name or None
    except OSError:
        return None


def save_training_state(directory, arrays, loader=None, input_state=None,
                        force=True):
    """Write ``arrays`` (pytree) + the input-pipeline state under
    ``directory``.

    :param arrays: pytree of params / optimizer state (jax or numpy arrays).
    :param loader: a :class:`~petastorm_tpu.jax_utils.loader.JaxDataLoader`
        to snapshot via its ``state_dict()`` (call between steps). Mutually
        exclusive with ``input_state``.
    :param input_state: a pre-captured reader/loader state dict.
    :param force: overwrite an existing checkpoint at ``directory``. The new
        checkpoint is fully written to a new versioned subdirectory before
        the ``CURRENT`` pointer moves, so the last good checkpoint survives
        a crash at any point during the save.
    """
    if loader is not None and input_state is not None:
        raise ValueError("pass loader OR input_state, not both")
    if loader is not None:
        input_state = loader.state_dict()

    directory = os.path.abspath(directory)
    current = _read_current(directory)
    if current is not None and not force:
        # Refuse BEFORE touching anything — the existing checkpoint stays
        # fully restorable.
        raise ValueError(f"checkpoint already exists at {directory} "
                         "(pass force=True to overwrite)")
    os.makedirs(directory, exist_ok=True)
    try:
        next_num = int(current[1:]) + 1 if current else 1
    except ValueError:  # pragma: no cover - hand-edited CURRENT
        next_num = 1
    version = _VERSION_TMPL.format(_agree_version(next_num))
    vdir = os.path.join(directory, version)
    # Barrier: no host may clear/write the shared version dir while another
    # is still deciding the version (or finishing a previous save call).
    _barrier(f"petastorm_tpu_ckpt_enter:{version}")
    if _process_index() == 0:
        shutil.rmtree(vdir, ignore_errors=True)  # debris of a crashed save
    _barrier(f"petastorm_tpu_ckpt_clean:{version}")
    _write_checkpoint(vdir, arrays, input_state)
    # Barrier: every host's input state + commit marker must be on disk
    # before CURRENT moves — otherwise a crash right after publish leaves a
    # version that restore rejects as torn AND the old version pruned.
    _barrier(f"petastorm_tpu_ckpt_written:{version}")
    if _process_index() == 0:
        # Atomic publish: from here on, restore sees the NEW checkpoint;
        # any crash before this line left CURRENT on the previous good one.
        tmp = os.path.join(directory, _CURRENT_FILE + ".tmp")
        with open(tmp, "w") as f:
            f.write(version)
        os.replace(tmp, os.path.join(directory, _CURRENT_FILE))
        # Prune superseded/orphaned versions (best-effort; a crash here
        # only delays cleanup to the next save). Strictly v<int> names —
        # anything else in the directory is the user's.
        for name in os.listdir(directory):
            if (name != version and _is_version_name(name)
                    and os.path.isdir(os.path.join(directory, name))):
                shutil.rmtree(os.path.join(directory, name),
                              ignore_errors=True)
    # No host returns (and potentially starts the next save) before the
    # publish is visible everywhere.
    _barrier(f"petastorm_tpu_ckpt_published:{version}")
    return directory


def _write_checkpoint(directory, arrays, input_state):
    os.makedirs(directory, exist_ok=True)
    idx = _process_index()
    ckptr = _get_checkpointer()
    ckptr.save(os.path.join(directory, _ARRAYS_DIR), arrays, force=True)
    ckptr.wait_until_finished()
    if input_state is not None:
        path = os.path.join(directory, _INPUT_STATE_TMPL.format(idx))
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(input_state, f)
        os.replace(tmp, path)  # atomic publish
    # Commit marker goes last within the version: its presence certifies
    # arrays + input state were both fully written by this host.
    marker = os.path.join(directory, _COMMIT_MARKER_PREFIX + str(idx))
    tmp = marker + ".tmp"
    with open(tmp, "w") as f:
        f.write("ok")
    os.replace(tmp, marker)


def restore_training_state(directory, abstract_arrays=None):
    """Restore ``(arrays, input_state)`` from ``directory``.

    :param abstract_arrays: optional pytree of ``jax.ShapeDtypeStruct`` (or
        concrete arrays) guiding orbax's typed/sharded restore; ``None``
        restores as saved.
    :return: ``(arrays, input_state_or_None)`` — pass the input state as
        ``resume_state=`` to the reader factory (or ``ServiceBatchSource``)
        feeding a fresh loader. Local readers re-read buffered-but-
        unyielded rows (at-least-once); a service source resumes at its
        per-piece watermarks (exactly-once — nothing re-delivered, nothing
        lost).
    :raises RuntimeError: if no published checkpoint exists, this host's
        commit marker is absent (torn save), or the checkpoint was saved by
        a different number of hosts than are restoring (the other hosts'
        reader positions would be silently dropped).
    """
    directory = os.path.abspath(directory)
    current = _read_current(directory)
    if current is None:
        raise RuntimeError(
            f"no published checkpoint at {directory} (missing/empty "
            f"{_CURRENT_FILE}): either nothing was saved here or every "
            "save crashed before completing")
    vdir = os.path.join(directory, current)
    idx = _process_index()
    if not os.path.exists(os.path.join(vdir,
                                       _COMMIT_MARKER_PREFIX + str(idx))):
        raise RuntimeError(
            f"checkpoint {current} at {directory} has no commit marker for "
            f"host {idx}: the save did not complete on this host (torn "
            "checkpoint) — restoring it could pair arrays with stale or "
            "missing input state")
    saved_hosts = len([n for n in os.listdir(vdir)
                       if n.startswith(_COMMIT_MARKER_PREFIX)
                       and not n.endswith(".tmp")])
    if saved_hosts != _process_count():
        raise RuntimeError(
            f"checkpoint {current} at {directory} was saved by "
            f"{saved_hosts} host(s) but {_process_count()} are restoring: "
            "the other hosts' input-pipeline positions would be silently "
            "dropped — restore with the same process count, or restore "
            "arrays only via orbax directly")
    ckptr = _get_checkpointer()
    arrays_path = os.path.join(vdir, _ARRAYS_DIR)
    if abstract_arrays is None:
        arrays = ckptr.restore(arrays_path)
    else:
        arrays = ckptr.restore(arrays_path, abstract_arrays)
    path = os.path.join(vdir, _INPUT_STATE_TMPL.format(idx))
    input_state = None
    if os.path.exists(path):
        with open(path) as f:
            input_state = json.load(f)
    return arrays, input_state
