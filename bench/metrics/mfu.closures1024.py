"""Required time of the window's work at the chip's peak (bench/work.py,
bench/peaks.py) over the device busy time, in %: what the device reached
while busy, whatever kernel ran."""
from bench import layers


def read(run):
  return layers.busy_mfu_pct(run)
