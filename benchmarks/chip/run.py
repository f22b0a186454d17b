#!/usr/bin/env python3
"""The on-chip benchmark's command. One run of one cell of BENCHMARK.json:

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

It runs on the machine it is started on, needs a TPU (any other platform
exits nonzero with no result), and prints one JSON object as the last
line of standard output.
"""
import sys
import time

T_PROCESS = time.perf_counter()

from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench.bench import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
