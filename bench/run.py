"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints
one JSON object as the last line of standard output.  Exits nonzero, and
prints no result, when JAX finds no TPU or fewer chips than the cell needs.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(t_start=T_START))
