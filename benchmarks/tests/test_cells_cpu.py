"""Whole runs of each cell at tiny sizes on the CPU: the run is correct.

At widths of 8 to 32, bfloat16 rounds a gradient norm by a few percent,
more than at the cells' own widths, so a clean tiny run is held to twice
each limit (the planted faults in ``test_faults.py`` read far above it)."""

import pytest

from tiny import TINY, cell


@pytest.mark.parametrize("name", ["imagenet1k.local", "criteo1tb.local"])
def test_tiny_run_is_correct(name):
    import jax

    from harness.cell import run_cell

    c = cell(name)
    run, checks = run_cell(c, 2 ** 31 + 12345, 2.0, 0, jax.devices(),
                           overrides=TINY[c["config"]], log=print)
    for n, v, lim in checks:
        print(n, v, lim)
    assert run.steps > 10
    assert run.failed == 0
    bad = [(n, v, lim) for n, v, lim in checks if not v <= 2 * lim]
    assert not bad, bad
