"""The control of ``correct`` at a tiny grid on the CPU: the stale answer
that ``bench/control.py`` puts in the program's place (the reference over
every edge ingested but the window's last batch) has to fail at least one
of each cell's numbers, while the program's own answers pass them."""
from __future__ import annotations

import time

import pytest

from bench import control, harness
from test_cells import CELLS, tiny_cell


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_a_number_of_every_cell(name):
    cell = tiny_cell(name)
    keep = {}
    out = harness.run_cell(cell, 11, 1.5, False, time.perf_counter(),
                           keep=keep)
    assert out["correct"], out["compared"]
    ctrl = control.control_readings(cell.config, keep)
    assert set(ctrl) == set(out["compared"])
    assert any(v > out["compared"][k]["limit"] for k, v in ctrl.items()), ctrl
