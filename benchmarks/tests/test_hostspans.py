"""The host-side reduction: on a made-up trace whose answers are known, and
on the small trace recorded on a v5e (``data/v5e_probe.xplane.pb``)."""

import copy
import os
from types import SimpleNamespace as NS

from harness import hostspans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
P = hostspans.PREFIX


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def profile(linearize=True):
    """Trainer: harness spans loader.next [0,100), step.dispatch [100,120),
    step.wait [120,400); loader.wait [10,60), loader.device_put [60,95)
    holding loader.raw_stage [65,85). Producer: loader.decode [0,300)
    holding reader.wait [20,250) and loader.collate [260,290). A reader
    worker: reader.read [30,50), reader.decode [50,240). A runtime thread:
    XlaLinearize [200,215). Device ops: [95,130), [160,200), [215,230),
    [300,380)."""
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            ev("loader.next", 0, 100), ev("step.dispatch", 100, 120),
            ev("step.wait", 120, 400), ev(P + "loader.wait", 10, 60),
            ev(P + "loader.device_put", 60, 95),
            ev(P + "loader.raw_stage", 65, 85)]),
        NS(name="python", events=[
            ev(P + "loader.decode", 0, 300), ev(P + "reader.wait", 20, 250),
            ev(P + "loader.collate", 260, 290)]),
        NS(name="python", events=[
            ev(P + "reader.read", 30, 50), ev(P + "reader.decode", 50, 240)]),
        NS(name="pjrt-tpu-tasks/1", events=[
            ev("XlaLinearize" if linearize else "Transpose", 200, 215)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_bench_step(3)", 95, 380)]),
        NS(name="XLA Ops", events=[ev("a", 95, 130), ev("b", 160, 200),
                                   ev("c", 215, 230), ev("d", 300, 380)])])
    return NS(planes=[host, dev])


def test_stage_totals_and_self_time():
    s = hostspans.reduce(profile())
    assert s["window_s"] == 400e-9
    spans = s["spans"]
    assert spans["loader.wait"] == {"count": 1, "total_s": 50e-9,
                                    "self_s": 50e-9}
    assert spans["loader.device_put"]["total_s"] == 35e-9
    assert spans["loader.device_put"]["self_s"] == 15e-9
    assert spans["loader.decode"]["total_s"] == 300e-9
    assert spans["loader.decode"]["self_s"] == 40e-9
    assert spans["reader.wait"]["self_s"] == 230e-9
    assert spans["reader.decode"] == {"count": 1, "total_s": 190e-9,
                                      "self_s": 190e-9}
    assert set(s["lines"]["2:python"]) == {"reader.read", "reader.decode"}
    assert s["linearize"] == {"count": 1, "total_s": 15e-9}


def test_idle_causes_each_branch():
    s = hostspans.reduce(profile())
    assert s["idle_causes"] == [
        ["loader.next/loader.wait", 95e-9],     # the trainer's own stage
        ["step.wait/loader.collate", 70e-9],    # the producer's stage
        ["step.wait/reader.wait", 30e-9],       # the producer, nested
        ["step.wait/none", 20e-9],              # nothing open
        ["step.wait/h2d.linearize", 15e-9],     # the runtime's relayout
    ]


def test_no_linearize_reads_none():
    s = hostspans.reduce(profile(linearize=False))
    assert s["linearize"] is None
    assert ["step.wait/reader.wait", 15e-9] in s["idle_causes"]


def test_recorded_v5e_trace():
    p = trace.load(os.path.join(DATA, "v5e_probe.xplane.pb.gz"))
    before = trace.reduce(p, "bench_step")
    s = hostspans.reduce(p)
    # The same profile still reduces to the same device metrics.
    assert trace.reduce(p, "bench_step") == copy.deepcopy(before)
    gaps = before["breakdown"]["idle_gaps"]
    assert [[name.split("/")[0], d] for name, d in s["idle_causes"]] == gaps
    # Its three ~60 ms gaps are the runtime relayout of a staged batch.
    for name, d in s["idle_causes"][:3]:
        assert name == "step.wait/h2d.linearize" and 0.055 < d < 0.065
    assert s["linearize"]["count"] == 6
    assert 0.3 < s["linearize"]["total_s"] < 0.4
    # The probe's program wrote no stage spans.
    assert s["spans"] == {}
