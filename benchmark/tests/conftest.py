"""The benchmark's self-tests run on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

``test_control.py`` holds the chip-only calibration (skipped elsewhere)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
