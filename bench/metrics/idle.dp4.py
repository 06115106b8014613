"""Share of the traced window in which no operation ran on the device
(mean over the four chips), in %."""
from bench import layers


def read(run):
  return layers.idle_pct(run)
