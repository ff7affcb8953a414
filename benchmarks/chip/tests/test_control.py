"""The control fails the comparison a run makes, at a test size.

For each cell, three seeds: the control (the plain reference in three bf16
passes for the fits, the program's 4-bit PQ path for the search) stands in
for the program and goes through the cell's own limits; at least one
compared number must exceed its limit on every seed.  The same readings at
the cell's own size on the chip are what ``limits/<cell>.json`` was set
from (``control.py``).
"""
import jax
import pytest

import control
import harness

CELLS = ("paper-500k.fit", "sift-1m.fit", "sift-1m.search-np10")


@pytest.mark.parametrize("seed", [3, 2**33 + 1, 77])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(small_cells, cell, seed):
    c = harness.Cell.find(cell)
    readings = control.control_readings(c, seed, jax.devices()[:1])
    checks = harness.checks_from(readings, c.limits)
    assert not all(ch.ok for ch in checks), readings
