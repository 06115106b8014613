"""Busy seconds of the least busy chip over the busiest chip's in the
traced window (profiler trace, per device), in %."""
from bench import placement


def read(run):
  return placement.chip_balance_pct(run)
