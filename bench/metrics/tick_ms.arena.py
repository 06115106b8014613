"""Mean length of one arena tick: the fused chunk launch over every live
slot plus the eviction sweep that waits on it (flight recorder
``arena_tick`` spans), in ms."""
from bench import layers


def read(run):
  return layers.span_mean_ms(run, ("arena_tick",))
