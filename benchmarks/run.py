"""Entry point: ``python3 benchmarks/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
