#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result (see benchmark/README.md).
"""

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import os      # noqa: E402
import sys     # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import main     # noqa: E402

if __name__ == "__main__":
    sys.exit(main(T_PROCESS))
