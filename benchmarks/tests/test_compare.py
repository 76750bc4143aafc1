"""The order numbers on made-up deliveries whose answers are known."""

from harness.compare import once_violations, order_faults, order_lags
from harness.paths import local

G = 8
REF = local.ref_order(5, G, 4)


def test_sound_jitter_reads_no_violation():
    got = REF[:24]
    got[3], got[5] = got[5], got[3]          # two threads out of turn
    assert once_violations(got, REF, G) == 0
    assert max(order_lags(got, REF)) == 2


def test_twice_and_lost_are_violations():
    assert once_violations(REF[:12] + REF[11:23], REF, G) >= 1
    assert once_violations(REF[:12] + REF[13:25], REF, G) >= 1


def test_other_seed_order_lags():
    faults = order_faults(REF, local.ref_order(6, G, 4), 24, G)
    assert faults["order_mean_lag"] > 1
    assert faults["rows_once_violations"] >= 1
