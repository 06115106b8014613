"""Host time per answered request reading evicted slots back: the read
program, its copy to the host and the slicing (flight recorder
``arena_readout`` spans), in ms."""
from bench import layers


def read(run):
  return layers.host_ms_per_request(run, ("arena_readout",))
