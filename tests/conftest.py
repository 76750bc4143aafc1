"""Test-session configuration.

JAX runs on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (SURVEY.md §4 "implication for the rebuild").
Env vars must be set before jax is first imported anywhere in the test run.
"""

import os

# Force CPU even on a host with a chip: the suite needs 8 virtual devices to
# exercise sharding; the chip is for chip_smoke.py and bench.py only.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import threading  # noqa: E402
import time  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import pytest  # noqa: E402


def _open_socket_fds():
    """Snapshot of this process's open socket fds as (fd, inode) pairs —
    Linux-only (/proc); empty elsewhere, which disables the socket check."""
    out = set()
    try:
        fds = os.listdir("/proc/self/fd")
    except OSError:
        return out
    for fd in fds:
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue  # fd closed between listdir and readlink
        if target.startswith("socket:"):
            out.add((fd, target))
    return out


def _open_memfd_fds():
    """Snapshot of this process's open shm-transport memfd fds as
    (fd, name) pairs. Every arena :mod:`petastorm_tpu.service.shm_ring`
    creates — ring data regions, frame pools — carries the ``ptshm``
    memfd name prefix precisely so this scan can spot one surviving a
    test: an orphaned arena pins its full size in /dev/shm for the rest
    of the session. Linux-only (/proc); empty elsewhere."""
    out = set()
    try:
        fds = os.listdir("/proc/self/fd")
    except OSError:
        return out
    for fd in fds:
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue  # fd closed between listdir and readlink
        if target.startswith("/memfd:ptshm"):
            out.add((fd, target))
    return out


#: Process-lifetime thread pools libraries create on first use and keep
#: forever (not per-test leaks): orbax-checkpoint's async machinery.
_LIBRARY_SINGLETON_THREAD_PREFIXES = ("metadata_store", "base_pytree_ch",
                                      "orbax", "grpc")

#: Reader-pool worker threads are DAEMON threads (the non-daemon check
#: misses them), but an orphaned one means a Reader — e.g. the one owned
#: by a service stream's streaming piece engine — was never stopped: it
#: keeps decoding into a bounded queue nobody drains and pins its pool
#: for the rest of the session.
_READER_POOL_THREAD_PREFIX = "petastorm-tpu-worker"

#: The pipeline autotuner's controller thread is a daemon too; one
#: surviving a test means an autotuned loader was never stopped — it
#: keeps re-planning (and resizing pools!) against a dead pipeline for
#: the rest of the session. Graph-rewrite rounds (stage fusion, filter
#: hoisting, cache placement — pipeline/rewrites.py) run on this same
#: controller thread, so a leaked rewrite controller is caught by this
#: prefix too — rewrites spawn no threads of their own (the fused pool
#: task reuses the reader pool's "petastorm-tpu-worker" threads, guarded
#: above).
_AUTOTUNE_THREAD_PREFIX = "pipeline-autotune"

#: The fleet autoscaler's controller thread: one surviving a test means a
#: dispatcher armed with autoscale= was never stopped — it keeps applying
#: (and journaling!) admit/drain decisions against a dead fleet.
_FLEET_AUTOSCALE_THREAD_PREFIX = "fleet-autoscale"

#: The chaos-schedule fuzzer's per-seed run threads: one surviving a test
#: means a fuzz run hung past its join budget and was abandoned with a
#: live service topology inside it.
_FUZZ_THREAD_PREFIX = "failpoint-fuzz"

#: The fleet cache tier's peer threads — ``cache-peer-push-<wid>``
#: (placement pusher) and ``cache-peer-handoff-<wid>`` (drain handoff
#: shipper, worker.py). Both are daemons; one surviving a test means a
#: fleet-cache worker was never stopped (or its tier never cleanup()d) —
#: the pusher keeps dialing ring peers that no longer exist.
_CACHE_PEER_THREAD_PREFIX = "cache-peer"


def _orphan_cache_tmp_files():
    """``.tmp`` staging files inside every LIVE cache dir. The disk tier
    writes entries as ``mkstemp(... suffix=".tmp")`` + ``os.replace``; a
    write interrupted between the two — a failpoint (``handoff-torn``,
    ``cache-peer-gone``) firing mid-handoff-adoption, a killed worker —
    orphans the staging file, which ``os.replace`` will never claim and
    eviction (keyed on the entry suffix) will never delete."""
    from petastorm_tpu.cache_impl import live_cache_dirs

    out = set()
    for cache_dir in live_cache_dirs():
        try:
            names = os.listdir(cache_dir)
        except OSError:
            continue  # dir vanished — live_cache_dirs leak check owns it
        out.update(os.path.join(cache_dir, n) for n in names
                   if n.endswith(".tmp"))
    return out


@pytest.fixture(autouse=True)
def _resource_leak_guard(request):
    """Fail any tier-1 test that leaks a non-daemon thread, a socket, or a
    cache-created directory past its teardown.

    The service stack (dispatcher/worker/client, heartbeats, chaos) is all
    threads + sockets; a test that forgets to stop a node would silently
    tax every later test in the session. Caches (the decoded-batch cache's
    tiers, ``LocalDiskCache``) register every directory they *create* with
    ``cache_impl`` and deregister on ``cleanup()`` — an entry surviving the
    test means some owner (a worker, a reader, the cache itself) was never
    cleaned up, the exact leak class that accumulates spill dirs across
    worker restarts. A short grace loop absorbs asynchronous teardown
    (daemon handler threads closing sockets, GC-collected connections);
    whatever survives it is a leak. Opt out with
    ``@pytest.mark.allow_resource_leaks`` (and a reason)."""
    from petastorm_tpu import failpoints
    from petastorm_tpu.cache_impl import live_cache_dirs
    from petastorm_tpu.service.fleet import open_job_registrations
    from petastorm_tpu.service.mixture import open_mixture_passes
    from petastorm_tpu.service.shm_ring import live_shm_counts

    if request.node.get_closest_marker("allow_resource_leaks"):
        yield
        return
    before_threads = set(threading.enumerate())
    before_sockets = _open_socket_fds()
    before_memfds = _open_memfd_fds()
    before_shm = live_shm_counts()
    before_cache_dirs = live_cache_dirs()
    before_cache_tmp = _orphan_cache_tmp_files()
    before_jobs = open_job_registrations()
    before_mixture_passes = open_mixture_passes()
    yield
    leaked_schedule = failpoints.ACTIVE
    if leaked_schedule is not None:
        # Disarm FIRST so one leak cannot inject faults into every later
        # test, then fail: an armed schedule outliving its test is the
        # quarantine/chaos analogue of an unstopped node.
        failpoints.disarm()
    # A leaked schedule is already a failure — the grace loop below only
    # absorbs ASYNCHRONOUS teardown, which cannot un-leak it: take one
    # pass collecting the other leak classes and fail immediately.
    deadline = time.monotonic() + (0.0 if leaked_schedule is not None
                                   else 2.0)
    while True:
        leaked_threads = [
            t for t in threading.enumerate()
            if t not in before_threads and t.is_alive() and not t.daemon
            and not t.name.startswith(_LIBRARY_SINGLETON_THREAD_PREFIXES)]
        leaked_pool_threads = [
            t for t in threading.enumerate()
            if t not in before_threads and t.is_alive()
            and t.name.startswith((_READER_POOL_THREAD_PREFIX,
                                   _AUTOTUNE_THREAD_PREFIX,
                                   _FLEET_AUTOSCALE_THREAD_PREFIX,
                                   _FUZZ_THREAD_PREFIX,
                                   _CACHE_PEER_THREAD_PREFIX))]
        leaked_sockets = _open_socket_fds() - before_sockets
        leaked_memfds = _open_memfd_fds() - before_memfds
        # Live-arena registry deltas: a leaked RingProducer/RingConsumer
        # or FramePool (or its doorbell eventfds — invisible to the
        # memfd scan) means a stream transport was never closed.
        after_shm = live_shm_counts()
        leaked_shm = {kind: after_shm[kind] - before_shm.get(kind, 0)
                      for kind in after_shm
                      if after_shm[kind] > before_shm.get(kind, 0)}
        leaked_cache_dirs = live_cache_dirs() - before_cache_dirs
        leaked_cache_tmp = _orphan_cache_tmp_files() - before_cache_tmp
        leaked_jobs = open_job_registrations() - before_jobs
        # An abandoned MixedBatchSource pass holds N per-corpus inner
        # iterators (stream threads, heartbeats, sockets) — the mixture
        # analogue of an unstopped Reader.
        leaked_mixture = open_mixture_passes() - before_mixture_passes
        if not leaked_threads and not leaked_pool_threads \
                and not leaked_sockets and not leaked_memfds \
                and not leaked_shm and not leaked_cache_dirs \
                and not leaked_cache_tmp \
                and not leaked_jobs and leaked_mixture <= 0 \
                and leaked_schedule is None:
            return
        if time.monotonic() >= deadline:
            break
        time.sleep(0.05)
    pytest.fail(
        f"test leaked resources past teardown: "
        f"non-daemon threads {[t.name for t in leaked_threads]}, "
        f"reader-pool/autotune/fleet-autoscale/failpoint-fuzz/cache-peer "
        f"threads {[t.name for t in leaked_pool_threads]} "
        f"(an unstopped Reader — e.g. a streaming piece engine whose "
        f"owner never stopped/joined it — an autotuned loader whose "
        f"controller was never stopped, a Dispatcher(autoscale=) never "
        f"stopped, a hung fuzz run, or a fleet-cache worker whose peer "
        f"pusher/handoff thread was never stopped), "
        f"sockets {sorted(leaked_sockets)}, "
        f"shm arenas: memfds {sorted(leaked_memfds)}, live ring/pool/"
        f"eventfd registry deltas {leaked_shm} (a RingProducer/"
        f"RingConsumer or FramePool never close()d — an orphaned arena "
        f"pins its full size in /dev/shm), "
        f"cache dirs {sorted(leaked_cache_dirs)}, "
        f"orphaned cache .tmp staging files {sorted(leaked_cache_tmp)} "
        f"(a disk-tier write — e.g. a handoff adoption spilling to disk "
        f"— interrupted between mkstemp and os.replace), "
        f"open job registrations {sorted(leaked_jobs)} (a register_job "
        f"without end_job — use fleet.JobHandle), "
        f"open mixture passes {max(leaked_mixture, 0)} (a "
        f"MixedBatchSource iterator abandoned without close() — its "
        f"per-corpus inner sources stay live), "
        f"armed failpoint schedule "
        f"{'yes (now disarmed)' if leaked_schedule is not None else 'no'} "
        f"(use failpoints.armed(...) so the scope always disarms) — "
        f"stop/close every service node, loader, engine, and connection "
        f"the test started, and cleanup() every cache "
        f"(mark allow_resource_leaks only with a documented reason)",
        pytrace=False)


#: ROADMAP's tier-1 timeout and the fraction of it the `-m 'not slow'`
#: suite may consume before the gate fails: steal/chaos tests must not
#: silently bloat the fast suite until the 870s timeout starts flaking.
_TIER1_TIMEOUT_S = 870.0
_TIER1_BUDGET_FRACTION = 0.8


def pytest_configure(config):
    config._tier1_budget_start = time.monotonic()


def pytest_sessionfinish(session, exitstatus):
    """Fail an otherwise-green `-m 'not slow'` run that exceeds 80% of the
    ROADMAP's 870s tier-1 timeout — a runtime regression is a gate
    failure BEFORE it becomes a timeout flake."""
    markexpr = getattr(session.config.option, "markexpr", "") or ""
    if "not slow" not in markexpr:
        return
    start = getattr(session.config, "_tier1_budget_start", None)
    if start is None:
        return
    elapsed = time.monotonic() - start
    budget = _TIER1_TIMEOUT_S * _TIER1_BUDGET_FRACTION
    if elapsed > budget and exitstatus == 0:
        reporter = session.config.pluginmanager.get_plugin(
            "terminalreporter")
        message = (
            f"tier-1 runtime budget exceeded: the -m 'not slow' suite took "
            f"{elapsed:.0f}s, over {_TIER1_BUDGET_FRACTION:.0%} of the "
            f"{_TIER1_TIMEOUT_S:.0f}s ROADMAP timeout ({budget:.0f}s). "
            f"Move slow additions behind @pytest.mark.slow or shrink them.")
        if reporter is not None:
            reporter.write_sep("!", message)
        session.exitstatus = 1


@pytest.fixture(scope="session")
def petastorm_dataset(tmp_path_factory):
    """Session-scoped synthetic petastorm-format dataset (30 rows, 3 row
    groups) — the analogue of the reference's ``create_test_dataset`` fixture."""
    from petastorm_tpu.test_util.dataset_factory import TestSchema, create_test_dataset

    path = tmp_path_factory.mktemp("data") / "petastorm_ds"
    url = f"file://{path}"
    rows = create_test_dataset(url, rows_count=30, rows_per_row_group=10)
    return SimpleNamespace(url=url, path=str(path), rows=rows, schema=TestSchema)


@pytest.fixture(scope="session")
def scalar_dataset(tmp_path_factory):
    """Session-scoped plain-Parquet dataset for make_batch_reader tests."""
    from petastorm_tpu.test_util.dataset_factory import ScalarSchema, create_test_scalar_dataset

    path = tmp_path_factory.mktemp("data") / "scalar_ds"
    url = f"file://{path}"
    rows = create_test_scalar_dataset(url, rows_count=30, rows_per_row_group=10)
    return SimpleNamespace(url=url, path=str(path), rows=rows, schema=ScalarSchema)
