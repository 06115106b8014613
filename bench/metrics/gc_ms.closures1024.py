"""Garbage-collection pauses of the process (flight recorder ``gc_pause``
spans) inside the window, in ms per second of window."""
from bench import spans


def read(run):
  return spans.gc_ms_per_s(run)
