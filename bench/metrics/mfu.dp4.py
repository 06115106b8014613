"""Required time of the window's work at one chip's peak (bench/work.py,
bench/peaks.py) over the device busy time summed over the four chips, in
%: what the chips reached while busy, whatever kernel ran."""
from bench import layers


def read(run):
  return layers.busy_mfu_pct(run)
