"""The check against planted faults: the harness drives a whole run at a
tiny size on the CPU (skipping its look for a chip) with the timed path
broken underneath, and ``correct`` comes out false. The clean run is in
``test_cells_cpu.py``; the exchange between chips has no fault here, as
no cell spans chips."""

import jax
import jax.numpy as jnp
import pytest

from harness import cell as cells
from tiny import TINY, cell

CELLS = ["imagenet1k.local", "criteo1tb.local"]


def run_with(name, monkeypatch, **patch):
    c = cell(name)
    cfg = cells.load_config(c["config"])
    for attr, make in patch.items():
        setattr(cfg, attr, make(cfg))
    monkeypatch.setattr(cells, "load_config", lambda _: cfg)
    run, checks = cells.run_cell(c, 987654321, 1.0, 0, jax.devices(),
                                 overrides=TINY[c["config"]], log=print)
    return {n: (v, lim) for n, v, lim in checks}


def failed(checks):
    return sorted(n for n, (v, lim) in checks.items() if not v <= lim)


def _state_unchanged(cfg):
    make = cfg.make_step

    def make_step(sz):
        step = make(sz)
        return lambda params, batch: (params, step(params, batch)[1])

    return make_step


def _half_batch(cfg):
    """The step's loss and gradient over the first half of each batch."""
    make = cfg.make_step

    def make_step(sz):
        step = make(sz)

        def half(params, batch):
            n = batch["label"].shape[0] // 2
            return step(params, {k: v[:n] for k, v in batch.items()})

        return half

    return make_step


def _answer_altered(monkeypatch):
    from petastorm_tpu.jax_utils.loader import JaxDataLoader

    stage = JaxDataLoader._stage

    def altered(self, host_batch):
        out = stage(self, host_batch)
        name = "image" if "image" in out else "I1"
        out[name] = out[name].at[(0,) * out[name].ndim].add(1)
        return out

    monkeypatch.setattr(JaxDataLoader, "_stage", altered)


def _other_order(cfg):
    make = cfg.make_reader
    return lambda data, sz, seed, **kw: make(data, sz, seed + 1, **kw)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "other_order"])
def test_fault_is_not_correct(name, fault, monkeypatch):
    if fault == "answer_altered":
        _answer_altered(monkeypatch)
        checks = run_with(name, monkeypatch)
    else:
        patch = {"state_unchanged": {"make_step": _state_unchanged},
                 "half_batch": {"make_step": _half_batch},
                 "other_order": {"make_reader": _other_order}}[fault]
        checks = run_with(name, monkeypatch, **patch)
    print(fault, failed(checks), checks)
    assert failed(checks), checks
