"""The benchmark's command (see BENCHMARK.json and benchmarks/README.md).

    python3 benchmarks/run.py --workload <cell> --seed <n> \
                              --seconds <s> --trace <0|1>

Refuses any platform but a TPU, and prints its one result line last.
"""
import os
import sys
import time

T_START = time.perf_counter()          # set-up counts from process start
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks.lib.harness import main

    main(t_start=T_START)
