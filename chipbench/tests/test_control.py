"""The control, the reference one precision below the configuration's
put in the program's place, fails the cell's comparison, while the
program passes it: at a size a CPU test can hold. The same readings at
the cells' own size come from ``chipbench/calibrate.py`` on the chip."""
import math

import pytest

from chipbench.run import compare, load_driver
from conftest import limits, small_cell

CELLS = ["fl.mlp.100k.full", "decoder.granite-3-2b.t4"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(name):
    driver = load_driver(small_cell(name), 5)
    driver.setup()
    driver.release()
    lim = limits(name)
    program = compare(driver.readings(), lim)
    control = compare(driver.readings(control=True), lim)
    assert all(c["ok"] for c in program.values()), program
    assert not all(c["ok"] for c in control.values()), control
    assert all(math.isfinite(c["value"]) for c in control.values())
