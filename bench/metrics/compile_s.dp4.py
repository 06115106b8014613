"""Seconds of set-up spent compiling or loading programs: the flight
recorder's ``compile`` spans (one per executable-cache miss) before the
window opens."""
from bench import spans


def read(run):
  return spans.compile_s_before(run)
