"""Shared code of the benchmark: everything that is not one configuration,
one traffic mix or one metric (those sit in ``configs/``, ``workloads/``
and ``metrics/``, found by the names in ``BENCHMARK.json``)."""
