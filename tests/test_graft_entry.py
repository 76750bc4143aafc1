"""Driver-contract tests for ``__graft_entry__``.

The driver compile-checks ``entry()`` single-chip and runs
``dryrun_multichip(n)`` with N virtual CPU devices; these tests exercise both
under the test session's 8-device CPU mesh (tests/conftest.py).
"""

import jax
import pytest

import __graft_entry__


def test_entry_compiles_and_runs():
    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)


def test_dryrun_multichip_in_process():
    # The test session already has 8 CPU devices, so this goes through the
    # in-process path (no subprocess).
    assert len(jax.devices()) >= 8
    __graft_entry__.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_subprocess_bootstrap():
    # Force the subprocess path regardless of local device count — this is
    # the path a one-chip host takes.
    __graft_entry__._dryrun_multichip_subprocess(8)
