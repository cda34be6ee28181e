"""Entry point of the benchmark: see ``harness.py`` and BENCHMARK.json."""

import os
import sys
import time

T_START = time.time()  # set-up is timed from the process's start
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
