"""Share of the traced window in which no operation ran on the device
(mean over the devices used), in %."""
from bench import layers


def read(run):
  return layers.idle_pct(run)
