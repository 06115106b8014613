"""Mean length of one arena admission: the host pads the request and
dispatches the admit program (flight recorder ``arena_admit`` spans), in
ms."""
from bench import layers


def read(run):
  return layers.span_mean_ms(run, ("arena_admit",))
