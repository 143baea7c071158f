"""Run one benchmark cell once and print its result as the last line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cells, metrics and bounds are in BENCHMARK.json at the checkout's
root; chipbench/harness.py says what a run does. Exits non-zero, with
no result line, when JAX finds no TPU or fewer chips than the cell
asks for.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t0=T0))
