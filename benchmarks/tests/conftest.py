"""The benchmark's own tests, run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They run the harness at tiny sizes on the CPU (no chip): the trace
reduction on a recorded trace, the operation counts against hand counts,
the check against planted faults, and the control at a tiny size."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]
