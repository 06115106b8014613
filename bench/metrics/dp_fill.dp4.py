"""Request slots over all slots of the dp batches in the window, from the
placement args of the flight recorder's ``batch_dispatch`` spans, in %."""
from bench import placement


def read(run):
  return placement.dp_fill_pct(run)
