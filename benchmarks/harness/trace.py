"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Busy time is the union of the intervals in which an operation ran on a
device ("XLA Ops" line of each ``/device:TPU:<n>`` plane). The step's
device time is that of the program runs ("XLA Modules" line) named
``jit_<step name>``; every other program run counts as device input work
(the device stage, transfers' programs). Idle gaps are the complement of
busy time inside the window, each named by the benchmark's host span it
fell in (``loader.next``, ``step.dispatch``, ``step.wait``, written with
``jax.profiler.TraceAnnotation``). Device and host timestamps share the
profiler's clock.
"""

import glob
import os

HOST_SPANS = ("loader.next", "step.dispatch", "step.wait")
TOP = 10
#: Op names are HLO text; the breakdown keeps the head (name and shape).
NAME_CHARS = 120


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return _events(line)
    return []


def read_planes(profile):
    """``(devices, spans)``: per device plane its op and module events,
    and the host spans, each ``(name, start_ns, end_ns)``."""
    devices, spans = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in \
                plane.name:
            devices.append({"name": plane.name,
                            "ops": _line(plane, "XLA Ops"),
                            "modules": _line(plane, "XLA Modules")})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line) if e[0] in HOST_SPANS)
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    spans.sort(key=lambda e: e[1])
    return devices, spans


def union(intervals, lo, hi):
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo, hi):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans, t):
    """The host span open at ``t`` (the latest started), or ``other``."""
    name = "other"
    for n, s, e in spans:
        if s > t:
            break
        if e > t:
            name = n
    return name


def leaves(events):
    """The events that hold no other event: an op such as a ``while`` spans
    the ops of its body on the same line, and would count them twice."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for ev, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt[1] >= ev[2]]


def _overlap(s, e, lo, hi):
    return max(0, min(e, hi) - max(s, lo))


def reduce(profile, step_name):
    """Device metrics of a traced window. The window runs from the first
    host span's start to the last one's end."""
    devices, spans = read_planes(profile)
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    if not spans:
        raise RuntimeError("the trace holds none of the host spans "
                           f"{HOST_SPANS}")
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    step_module = f"jit_{step_name}"
    per_device, op_time, all_gaps = [], {}, []
    for d in devices:
        busy = union([(s, e) for _, s, e in d["ops"]], lo, hi)
        step_ns = input_ns = steps = 0
        for name, s, e in d["modules"]:
            ns = _overlap(s, e, lo, hi)
            if name.split("(")[0] == step_module:
                step_ns += ns
                steps += lo <= s < hi
            else:
                input_ns += ns
        for name, s, e in leaves(d["ops"]):
            name = name[:NAME_CHARS]
            op_time[name] = op_time.get(name, 0) + _overlap(s, e, lo, hi)
        for s, e in gaps(busy, lo, hi):
            all_gaps.append((span_at(spans, (s + e) // 2), e - s))
        per_device.append({"device": d["name"],
                           "busy_s": sum(e - s for s, e in busy) / 1e9,
                           "step_device_s": step_ns / 1e9,
                           "input_device_s": input_ns / 1e9,
                           "steps": steps})
    n = len(per_device)
    all_gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "step_device_s": sum(d["step_device_s"] for d in per_device) / n,
        "input_device_s": sum(d["input_device_s"] for d in per_device) / n,
        "steps": min(d["steps"] for d in per_device),
        "devices": per_device,
        "breakdown": {
            "device_ops": [[k, v / 1e9 / n] for k, v in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[name, ns / 1e9] for name, ns in all_gaps[:TOP]],
        },
    }


def load(path):
    """A ``ProfileData`` from an ``.xplane.pb`` file, gzipped or not."""
    import gzip

    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def reduce_dir(trace_dir, step_name):
    return reduce(load(find_xplane(trace_dir)), step_name)
