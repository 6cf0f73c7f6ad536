"""The control, the reference with its timing in float32 put in the
program's place, must come out as not correct; the program must not.

At a small cache geometry on the CPU; ``control.py`` takes the same
readings on the chip at each cell's own size.
"""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))

import compare  # noqa: E402
import control  # noqa: E402
from test_faults import CELLS, small  # noqa: E402


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell):
    got = {(seed, side): (values, ok) for seed, side, values, ok
           in control.readings(cell, [5, 2 ** 31 + 3], adjust=small)}
    for (seed, side), (values, ok) in got.items():
        if side == "program":
            assert ok, (seed, values)
        else:
            assert not ok, (seed, values)
            assert values["timing_rel_gap"] > compare.LIMITS[
                "timing_rel_gap"] * 10, values
