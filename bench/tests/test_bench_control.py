"""The lower-precision control fails the comparison: the plain
reference, computed in bfloat16 in the program's place, on three seeds,
at a size a test run holds. Float32, the precision the configurations
state, passes it."""
import ml_dtypes
import numpy as np
import pytest

from bench import control, harness
from bench.tests import tiny

TICKS = 40


@pytest.mark.parametrize("name", ["dos.replay"])
@pytest.mark.parametrize("seed", [5, 2**31 + 6, 7])
def test_bfloat16_control_fails_and_float32_passes(name, seed):
    cell = tiny.tiny(harness.load_cell(name))
    limits = harness.limits_of(cell.config)
    low = control.readings(cell, seed, TICKS, ml_dtypes.bfloat16)
    assert any(low[k] > limits[k] for k in low), low
    same = control.readings(cell, seed, TICKS, np.float32)
    assert all(same[k] <= limits[k] for k in same), same
