"""The trace reduction: on a made-up trace whose answers are known, and on
a small trace recorded on a v5e (``data/v5e_probe.xplane.pb``)."""

import os
from types import SimpleNamespace as NS

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def profile():
    """One device. Host spans: loader.next [0,100), step.dispatch
    [100,120), step.wait [120,400). Device: the stage program [50,90) with
    one op, the step program [130,330) with ops [130,200) and [220,330)."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("loader.next", 0, 100), ev("step.dispatch", 100, 120),
        ev("step.wait", 120, 400), ev("unrelated", 0, 400)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit__kernel(7)", 50, 90),
                                       ev("jit_bench_step(3)", 130, 330)]),
        NS(name="XLA Ops", events=[ev("fusion.1", 50, 90),
                                   ev("convolution.2", 130, 200),
                                   ev("fusion.1", 220, 330)])])
    return NS(planes=[host, dev])


def test_leaves_drop_enclosing_ops():
    ops = [("while", 0, 100), ("body.1", 10, 40), ("body.2", 50, 90),
           ("after", 100, 120)]
    assert trace.leaves(ops) == ops[1:]


def test_union_and_gaps():
    busy = trace.union([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25)
    assert busy == [[1, 3], [5, 12], [20, 25]]
    assert trace.gaps(busy, 0, 30) == [(0, 1), (3, 5), (12, 20), (25, 30)]


def test_reduce_made_up_trace():
    s = trace.reduce(profile(), "bench_step")
    assert s["window_s"] == 400e-9
    assert s["busy_s"] == (40 + 70 + 110) * 1e-9
    assert s["step_device_s"] == 200e-9
    assert s["input_device_s"] == 40e-9
    assert s["steps"] == 1
    gaps = s["breakdown"]["idle_gaps"]
    # [0,50) under loader.next, [90,130) spans loader.next/step.dispatch/
    # step.wait (midpoint 110: step.dispatch), [200,220) and [330,400)
    # under step.wait.
    assert gaps == [["step.wait", 70e-9], ["loader.next", 50e-9],
                    ["step.dispatch", 40e-9], ["step.wait", 20e-9]]
    assert s["breakdown"]["device_ops"] == [["fusion.1", 150e-9],
                                            ["convolution.2", 70e-9]]


def test_no_device_plane_is_an_error():
    p = profile()
    p.planes = p.planes[:1]
    with pytest.raises(RuntimeError):
        trace.reduce(p, "bench_step")


def test_recorded_v5e_trace():
    s = trace.reduce(trace.load(os.path.join(DATA, "v5e_probe.xplane.pb.gz")),
                     "bench_step")
    # Six steps ran; the probe waited for the last one outside any span.
    assert s["steps"] == 5
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["step_device_s"] > 0 and s["input_device_s"] > 0
    assert s["step_device_s"] + s["input_device_s"] <= s["window_s"]
    assert {name for name, _ in s["breakdown"]["idle_gaps"]} <= {
        "loader.next", "step.dispatch", "step.wait", "other"}
