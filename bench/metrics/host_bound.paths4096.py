"""Share of the window in which the device sat idle while the serving
loop was not waiting for work: profiler idle gaps outside the flight
recorder's ``loop_wait`` spans, in %."""
from bench import spans


def read(run):
  return spans.host_bound_pct(run)
