"""The control — each cell's plain reference computed in bfloat16, one
precision below the configuration's float32, in the program's place — must
fail the cell's limits; the same comparison passes the float32 reference
against itself."""
import pytest

from bench import control, spec
from conftest import tiny

CELLS = ["closures1024.steady", "paths4096.replay"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [2**31 + 21, 2**31 + 22, 2**31 + 23])
def test_control_fails_the_limits(name, seed):
  cell = tiny(spec.load_cell(name))
  limits = cell.config["check"]["limits"]
  got = control.readings(cell, seed, 2.0)
  assert set(got) == set(limits)
  assert any(got[k] > limits[k] for k in got), got


@pytest.mark.parametrize("name", CELLS)
def test_the_reference_in_its_own_precision_reads_zero(name):
  cell = tiny(spec.load_cell(name))
  got = control.readings(cell, 2**31 + 24, 2.0, precision="float32")
  assert all(v == 0.0 for v in got.values()), got
