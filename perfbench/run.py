#!/usr/bin/env python3
"""Run one cell of the chip benchmark (see BENCHMARK.json at the root).

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks
for.  Without a TPU it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import sys                                                    # noqa: E402
from pathlib import Path                                      # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

if __name__ == "__main__":
    from perfbench.harness.runner import main                 # noqa: E402
    sys.exit(main(t_start=T_START))
