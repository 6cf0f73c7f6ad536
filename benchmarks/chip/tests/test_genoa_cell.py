"""The server-LLC cell, ``genoa-hotcold-static``, decides ``correct``.

At a small cache geometry on the CPU (``test_faults.small``), with the
cell's 8 cores, hot/cold traffic and 1:1 interleave: a sound run of
``run_cell.run`` is correct, and the control (the reference with its
timing in float32, ``control.py``) is not.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))

import compare  # noqa: E402
import control  # noqa: E402
import run_cell  # noqa: E402
from test_faults import small  # noqa: E402

CELL = "genoa-hotcold-static"


def test_sound_run_is_correct():
    out = run_cell.run(CELL, 2 ** 31 + 13, 0.2, False, require_tpu=False,
                       adjust=small)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_control_is_not_correct():
    for seed, side, values, ok in control.readings(CELL, [7],
                                                   adjust=small):
        if side == "program":
            assert ok, (seed, values)
        else:
            assert not ok, (seed, values)
            assert values["timing_rel_gap"] > compare.LIMITS[
                "timing_rel_gap"] * 10, values
