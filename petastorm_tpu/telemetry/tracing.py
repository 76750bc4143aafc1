"""Batch lifecycle tracing: spans across the worker/client process boundary.

A batch's journey through the disaggregated service crosses threads and (in
real deployments) processes: worker decode → framed send → client stream
reader → shared ready-queue → loader device dispatch → consumer yield. Rates
tell you *that* delivery is slow; only per-batch spans tell you *where one
batch* spent its time. The scheme:

- the worker mints a **batch id** at decode time
  (``<worker_id>:<stream>:<seq>``) and carries it in the ``batch`` frame
  header — the only cross-process plumbing needed;
- every stage records a span against that id into the process-wide
  :class:`TraceCollector` (begin/end event pairs);
- the collector exports Chrome ``trace_event`` JSON
  (https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU)
  — load it in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``
  and follow one ``bid`` across rows.

Collection is **off by default** and costs one attribute read per call
site when off (``record_span`` returns immediately); arming it is
``JaxDataLoader(trace_path=...)``, the service scenario's ``--trace-out``,
or :func:`enable` directly. In a loopback run all stages share one process
and land in one file; multi-process deployments export one file per process
and merge on the bid (Perfetto overlays multiple files by pid).

Stages that run inside the trainer's process (the local reader's workers,
the loader) time themselves with :func:`span`: one pair of clock reads
feeds the collector, an optional histogram, and a
``jax.profiler.TraceAnnotation`` — so the same stage also lands in a
profiler trace, on the clock the device's events are stamped with.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

#: Bounded event buffer: at ~10 spans per batch a 200k-event ring covers
#: ~10k batches — hours of tracing at training rates — while bounding a
#: forgotten trace flag to ~50 MB instead of eating the heap forever.
DEFAULT_MAX_EVENTS = 200_000


class TraceCollector:
    """Process-wide span sink (Chrome ``trace_event`` semantics).

    ``enabled`` is a plain bool read without the lock — producers check it
    before computing timestamps, so a disabled collector costs one
    attribute read per potential span.
    """

    def __init__(self, max_events=DEFAULT_MAX_EVENTS):
        self.enabled = False
        self._max_events = max_events
        self._lock = threading.Lock()
        self._events = []
        self._dropped = 0
        self._armers = 0  # acquire/release refcount (scoped arming)
        # trace_event ts is microseconds; perf_counter gives the monotonic
        # duration math, the wall anchor makes traces from different
        # processes of one run line up on a shared axis (close enough for
        # eyeballing; exact alignment needs a shared clock anyway).
        self._epoch = time.time() - time.perf_counter()

    def enable(self):
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        with self._lock:
            self._armers = 0

    def acquire(self):
        """Scoped arming for components that share the process collector
        (e.g. a train loader and a mid-epoch eval loader, both with
        ``trace_path``): the FIRST armer clears the buffer, later armers
        join the running trace instead of wiping it, and collection stays
        on until the last armer releases. Pair with :meth:`release`."""
        with self._lock:
            self._armers += 1
            if self._armers == 1:
                self._events = []
                self._dropped = 0
        self.enabled = True
        return self

    def release(self):
        with self._lock:
            self._armers = max(0, self._armers - 1)
            if self._armers == 0:
                self.enabled = False

    def clear(self):
        with self._lock:
            self._events = []
            self._dropped = 0

    def _ts_us(self, t):
        return (self._epoch + t) * 1e6

    def ts_us(self, t):
        """A ``time.perf_counter()`` reading in this collector's trace
        timebase (wall-anchored microseconds) — the unit every event's
        ``ts`` is denominated in. Public so clock alignment can convert
        RPC midpoints into the same axis the merged trace renders on."""
        return self._ts_us(t)

    def now_us(self):
        """The current instant in the trace timebase. Shipped in control
        replies (``dispatcher_time_us``) so peers can estimate their
        offset against the dispatcher's axis NTP-style."""
        return self._ts_us(time.perf_counter())

    def record_span(self, name, t_start, t_end, bid=None, args=None,
                    tid=None):
        """One completed span as a B/E event pair. ``t_start``/``t_end``
        are ``time.perf_counter()`` readings; ``bid`` is the batch id the
        span belongs to (lands in ``args.bid`` so Perfetto's query/search
        finds every stage of one batch)."""
        if not self.enabled:
            return
        span_args = dict(args or {})
        if bid is not None:
            span_args["bid"] = bid
        pid = os.getpid()
        tid = tid if tid is not None else threading.get_ident() % 1_000_000
        begin = {"name": name, "cat": "petastorm", "ph": "B",
                 "ts": self._ts_us(t_start), "pid": pid, "tid": tid,
                 "args": span_args}
        end = {"name": name, "cat": "petastorm", "ph": "E",
               "ts": self._ts_us(t_end), "pid": pid, "tid": tid}
        with self._lock:
            if len(self._events) + 2 > self._max_events:
                self._dropped += 2
                return
            self._events.append(begin)
            self._events.append(end)

    def instant(self, name, t, bid=None, args=None):
        """A zero-duration marker (``ph: i``) — queue handoffs, fences,
        control-plane lifecycle decisions (breaker trips, brownout
        stages, fencing bumps carry their detail in ``args``)."""
        if not self.enabled:
            return
        event_args = dict(args or {})
        if bid is not None:
            event_args["bid"] = bid
        event = {"name": name, "cat": "petastorm", "ph": "i", "s": "t",
                 "ts": self._ts_us(t), "pid": os.getpid(),
                 "tid": threading.get_ident() % 1_000_000,
                 "args": event_args}
        with self._lock:
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            self._events.append(event)

    def events(self):
        with self._lock:
            return list(self._events)

    def ship(self):
        """Atomically take-and-clear the buffered events (with the drop
        count) — the trace-shipping primitive: an armed peer pushes its
        ring to the dispatcher on each heartbeat tick and keeps
        recording into an empty buffer, so no event is ever shipped
        twice and the ring never grows past one tick's production."""
        with self._lock:
            events, self._events = self._events, []
            dropped, self._dropped = self._dropped, 0
        return events, dropped

    @property
    def dropped(self):
        with self._lock:
            return self._dropped

    def export(self, path):
        """Write the buffered events as Perfetto-loadable trace JSON."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"producer": "petastorm_tpu.telemetry",
                             "dropped_events": dropped}}
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        return len(events)


#: The process-default collector every producer records into.
COLLECTOR = TraceCollector()


def enable():
    return COLLECTOR.enable()


def disable():
    COLLECTOR.disable()


def record_span(name, t_start, t_end, bid=None, args=None):
    COLLECTOR.record_span(name, t_start, t_end, bid=bid, args=args)


#: Prefix of every profiler event :func:`span` writes.
PROFILER_PREFIX = "petastorm_tpu."


def _annotation(name, bid):
    """A ``jax.profiler.TraceAnnotation`` when jax is already loaded, else
    ``None`` — the numpy-only path never imports jax for a span."""
    jax = sys.modules.get("jax")
    # getattr guard: another thread may be mid-way through `import jax`, in
    # which case sys.modules already holds a partially-initialized module.
    profiler = getattr(jax, "profiler", None) if jax is not None else None
    if profiler is None:
        return None
    if bid is None:
        return profiler.TraceAnnotation(PROFILER_PREFIX + name)
    # Keywords land as stats of the event, beside its bare name.
    return profiler.TraceAnnotation(PROFILER_PREFIX + name, bid=bid)


class span:
    """Time one pipeline stage: ``with span("loader.wait", hist=child):``.

    One pair of ``perf_counter`` reads feeds (a) a profiler annotation
    ``petastorm_tpu.<name>`` (carrying ``bid``) when jax is loaded and
    ``annotate`` is true, (b) the collector's span ``<name>`` when it is
    armed, and (c) ``hist.observe(duration)`` when a histogram child is
    given. ``bid`` and ``args`` may be set on the object inside the block
    (values known only once the stage ran); those reach the collector only.
    A block that raises records nothing but closes its annotation."""

    __slots__ = ("name", "bid", "args", "_hist", "_annotate", "_annotation",
                 "_t0")

    def __init__(self, name, bid=None, hist=None, annotate=True, **args):
        self.name = name
        self.bid = bid
        self.args = args
        self._hist = hist
        self._annotate = annotate

    def __enter__(self):
        annotation = (_annotation(self.name, self.bid) if self._annotate
                      else None)
        if annotation is not None:
            annotation.__enter__()
        self._annotation = annotation
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is None:
            if self._hist is not None:
                self._hist.observe(t1 - self._t0)
            if COLLECTOR.enabled:
                COLLECTOR.record_span(self.name, self._t0, t1, bid=self.bid,
                                      args=self.args)
        return False


def export(path):
    return COLLECTOR.export(path)


def wall_us():
    """The process's current wall-anchored trace timestamp (µs) from the
    default collector — the one sanctioned wall-clock read outside this
    module (the flight recorder stamps its ring entries with it so dumps
    from different processes correlate on one axis)."""
    return COLLECTOR.now_us()
