"""The benchmark's self-tests run on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Every cell of ``BENCHMARK.json`` is rehearsed, faulted and controlled by
what its configuration declares; :data:`CELLS` is read as the tests are
collected, so a cell added to ``BENCHMARK.json`` is tested with no edit
here.  ``calibrate.py`` gives the same control readings on the chip."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")
sys.path[:0] = [BENCH, ROOT]

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
