"""Host time per answered request copying the batch's result, every slot
of it, back from the chips (flight recorder ``batch_d2h`` spans), in ms."""
from bench import layers


def read(run):
  return layers.host_ms_per_request(run, ("batch_d2h",))
