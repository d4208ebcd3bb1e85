"""The control of each cell comes out not correct through the run's own
check, by at least one of the numbers compared, the program correct:
``calibrate.py``'s readings at the configuration's rehearsal size, on the
CPU.  (On the chip, at the cells' own sizes, the same script gives the
readings that PERF.md sets the limits from.)"""

import pytest

from conftest import CELLS, ROOT
from benchlib.harness import Cell
import calibrate


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_not_correct(cell):
    config = Cell(ROOT, cell).config
    got = {kind: calibrate.readings(ROOT, cell, [5], 0.2, kind=kind,
                                    sizes=config["rehearsal"]["sizes"],
                                    allow_cpu=True)[0]
           for kind in ("program", "reference", "control")}
    for r in got.values():
        # every limit is the configuration's own
        assert r["limits"] and r["steps"] > 0
        assert all(config["limits"][k] == v for k, v in r["limits"].items())
    program = got["program"]
    assert program["correct"]
    assert all(program["readings"][k] <= v
               for k, v in program["limits"].items())
    # the reference at the stated precision reads like the program
    assert got["reference"]["correct"]
    # one step lower fails the run's own verdict, by a limit
    control = got["control"]
    assert control["correct"] is False
    assert any(control["readings"][k] > v
               for k, v in control["limits"].items())
