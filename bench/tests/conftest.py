"""The benchmark's own tests run on the CPU: ``python -m pytest bench/tests``.

They check what needs no chip: the reduction of a trace recorded on one,
the work and peak tables, that every cell, configuration, mix and metric is
found by name from its files, the shape of the result line, the refusal of a
host without a TPU, and that the correctness check fails both the control
and a timed path broken on purpose.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
  if p not in sys.path:
    sys.path.insert(0, p)


def tiny(cell):
  """Shrink a cell to a size a CPU test run holds: graphs of at most 64
  vertices, a low rate, a small sample.  Everything else is the cell's."""
  mix, cfg = cell.traffic, cell.config
  if mix["loop"] == "open":
    mix["n"] = {"min": 16, "pareto_shape": 1.2, "cap": 64}
    mix["rate_per_s"] = min(float(mix["rate_per_s"]), 40.0)
    cfg["check"]["sample"] = 8
  else:
    mix["n"] = {"fixed": 64}
    mix["inputs_per_app"] = 1
  return cell


def run_tiny(name, *, seed=2**31 + 11, seconds=2.0, setup=None):
  """One CPU run of cell ``name`` at the tiny size; returns its result."""
  import time

  import jax

  from bench import harness, spec
  cell = tiny(spec.load_cell(name))
  if setup is not None:
    setup(cell)
  return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                          devices=jax.devices(), t_start=time.perf_counter())
