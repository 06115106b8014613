"""Host time per answered request in the compiled call before it returns,
the copy of every slot's stacked operands to its chip included (flight
recorder ``batch_dispatch`` spans), in ms."""
from bench import layers


def read(run):
  return layers.host_ms_per_request(run, ("batch_dispatch",))
