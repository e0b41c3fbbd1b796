"""The benchmark's command: one run of one cell on the chips it asks for.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result. The last line
of standard output is the run's JSON result; the checks of its correctness
comparison are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
