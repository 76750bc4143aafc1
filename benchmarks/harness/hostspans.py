"""Reduce a profiler trace's host lines: the program's stage spans, the
runtime's relayout of staged batches, and what the host was doing in each
of the device's longest idle gaps.

The program writes its stages as ``jax.profiler.TraceAnnotation`` events
named ``petastorm_tpu.<stage>`` (``petastorm_tpu/telemetry/tracing.py``),
on the profiler's clock, the clock the device's events carry. The runtime
converts every staged host buffer to the device's tiled layout on its own
threads (``XlaLinearize`` events) after ``device_put`` returns. The window
is the one :func:`harness.trace.reduce` uses: first to last harness span.

By hand, on a kept trace: ``cd benchmarks && python3 -m harness.hostspans
<file.xplane.pb[.gz]>`` prints the reduction as JSON.
"""

import json
import sys

from harness import trace as tr

PREFIX = "petastorm_tpu."
#: The runtime's host-side relayout of a staged buffer.
LINEARIZE = ("XlaLinearize",)
#: The loader producer's stage: its line is the producer thread's.
PRODUCER_SPAN = PREFIX + "loader.decode"


def _lines(profile):
    """``(key, events)`` for every host line, events as ``(name, start_ns,
    end_ns)``; the key is ``<index>:<line name>`` (Python threads may share
    a line name)."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out.append((f"{i}:{line.name}", tr._events(line)))
    return out


def _self_ns(span, children, lo, hi):
    """``span``'s time in ``[lo, hi)`` less the part its children cover."""
    s, e = max(span[1], lo), min(span[2], hi)
    if e <= s:
        return 0
    covered = tr.union([(c[1], c[2]) for c in children], s, e)
    return (e - s) - sum(b - a for a, b in covered)


def _stage_table(events, lo, hi):
    """Per name: count (starts in the window), total and self seconds
    clipped to the window. Children are the program's spans nested in a
    span on the same line."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    table, open_ = {}, []
    children = {id(ev): [] for ev in events}
    for ev in events:
        while open_ and open_[-1][2] <= ev[1]:
            open_.pop()
        if open_:
            children[id(open_[-1])].append(ev)
        open_.append(ev)
    for ev in events:
        row = table.setdefault(ev[0][len(PREFIX):],
                               {"count": 0, "total_s": 0.0, "self_s": 0.0})
        row["count"] += lo <= ev[1] < hi
        row["total_s"] += tr._overlap(ev[1], ev[2], lo, hi) / 1e9
        row["self_s"] += _self_ns(ev, children[id(ev)], lo, hi) / 1e9
    return table


def _innermost(events, t):
    """The latest-started event open at ``t``, or ``None``."""
    found = None
    for name, s, e in events:
        if s > t:
            break
        if e > t:
            found = name
    return found


def reduce(profile):
    """The host side of a traced window: ``spans`` (per stage, all lines),
    ``lines`` (per line and stage), ``linearize`` (``None`` when the
    runtime wrote no relayout event in the window) and ``idle_causes``,
    the ten longest device idle gaps as ``["<harness span>/<cause>",
    seconds]``."""
    devices, harness = tr.read_planes(profile)
    if not devices or not harness:
        raise RuntimeError("the trace holds no device plane or no harness "
                           "span")
    lo, hi = harness[0][1], max(e for _, _, e in harness)
    lines, program, relayout = {}, {}, []
    trainer = producer = []
    for key, events in _lines(profile):
        ours = sorted((ev for ev in events if ev[0].startswith(PREFIX)),
                      key=lambda ev: ev[1])
        relayout.extend(ev for ev in events if ev[0] in LINEARIZE)
        if ours:
            lines[key] = _stage_table(ours, lo, hi)
            for name, row in lines[key].items():
                total = program.setdefault(
                    name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
                for k in total:
                    total[k] += row[k]
        names = {ev[0] for ev in events}
        if names & set(tr.HOST_SPANS):
            trainer = ours
        if PRODUCER_SPAN in names:
            producer = ours
    in_window = [ev for ev in relayout if tr._overlap(ev[1], ev[2], lo, hi)]
    linearize = None
    if in_window:
        linearize = {
            "count": sum(lo <= s < hi for _, s, _ in in_window),
            "total_s": sum(tr._overlap(s, e, lo, hi)
                           for _, s, e in in_window) / 1e9,
        }

    gaps = []
    for d in devices:
        busy = tr.union([(s, e) for _, s, e in d["ops"]], lo, hi)
        gaps.extend(tr.gaps(busy, lo, hi))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    causes = []
    for s, e in gaps[:tr.TOP]:
        t = (s + e) // 2
        cause = ("h2d.linearize"
                 if any(a <= t < b for _, a, b in relayout) else
                 _innermost(trainer, t) or _innermost(producer, t))
        cause = cause[len(PREFIX):] if cause and cause.startswith(PREFIX) \
            else cause or "none"
        causes.append([f"{tr.span_at(harness, t)}/{cause}", (e - s) / 1e9])
    return {"window_s": (hi - lo) / 1e9, "spans": program, "lines": lines,
            "linearize": linearize, "idle_causes": causes}


if __name__ == "__main__":
    print(json.dumps(reduce(tr.load(sys.argv[1])), indent=1))
