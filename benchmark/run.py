"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout; the cell, its configuration, traffic and
metrics are read from BENCHMARK.json and the files beside this script.
Exits non-zero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from peaks.json.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    # the system under test lives at the checkout's root, the harness here
    sys.path[:0] = [here, os.path.dirname(here)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchlib.harness import main

    sys.exit(main(t_process=T_PROCESS))
