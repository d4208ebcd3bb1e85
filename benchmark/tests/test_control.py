"""The control of each cell comes out not correct through the run's own
check, the program correct: ``calibrate.py``'s readings at a size a test
run can hold, on the CPU.  (On the chip, at the cells' own sizes, the same
script gives the readings that PERF.md sets the limits from.)"""

import json
import os

import pytest

from conftest import ROOT
import calibrate

CONFIGS = os.path.join(ROOT, "benchmark", "configs")
SIZES = {"n": 2048, "nrhs": 4}


def limits(config):
    return json.load(open(os.path.join(CONFIGS, config + ".json")))["limits"]


@pytest.mark.parametrize("cell", ["posv_n16384", "potrs_n16384"])
def test_dense_control_reads_not_correct(cell):
    name = cell.split("_")[0] + "_residual"
    lim = limits("dense_spd_solve")[name]
    got = {kind: calibrate.readings(ROOT, cell, [5], 0.2, kind=kind,
                                    sizes=SIZES, allow_cpu=True)[0]
           for kind in ("program", "reference", "control")}
    for r in got.values():
        assert r["limits"] == {name: lim} and r["steps"] > 0
    assert got["program"]["correct"] and got["program"]["readings"][name] <= lim
    # the reference at the stated precision reads like the program
    assert got["reference"]["correct"]
    # one step lower fails the run's own verdict, by its limit
    assert got["control"]["correct"] is False
    assert got["control"]["readings"][name] > lim
