"""The control and the planted faults, at a size a test run holds: judged
by the cell's limits as a run is, each comes out not correct
(``bench/control.py`` reads them at the cells' own sizes on the chip)."""

from __future__ import annotations

import jax

from bench import control
from bench.tests import tiny


def test_train_control_and_half_batch_are_not_correct():
    cell = tiny.cell("lake")
    out = control.train_readings(cell, 7)
    for kind in ("fp8", "half_batch"):
        assert not control.judged(cell, out[kind]).correct, (kind, out)


def test_serve_control_is_not_correct_and_the_program_is():
    cell = tiny.cell("serve")
    out = control.serve_control(cell, 7, jax.devices())
    assert control.judged(cell, out["program"]).correct, out
    assert not control.judged(cell, out["fp8"]).correct, out
