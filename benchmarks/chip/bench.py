#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the root of
the checkout. Without the TPU chips a cell asks for, the command exits
non-zero and prints no result.
"""
import time

T_PROCESS = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
