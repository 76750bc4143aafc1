"""The control (the reference in float8, put in the program's place) comes
out not correct, at a tiny size on the CPU; on the chip it is read at the
cells' own size with ``run.py --control 1`` (PERF.md)."""

import jax
import pytest

from harness import cell as cells
from tiny import TINY, cell


@pytest.mark.parametrize("name", ["imagenet1k.local", "criteo1tb.local"])
def test_control_fails(name):
    c = cell(name)
    run, checks = cells.run_cell(c, 31337, 1.0, 0, jax.devices(),
                                 overrides=TINY[c["config"]], control=True,
                                 log=print)
    control = {n: (v, lim) for n, v, lim in checks if n.startswith("control.")}
    print(control)
    assert any(not v <= lim for v, lim in control.values()), control
