"""Host time per answered request in the batching layer: pad-and-stack
(inert padding slots included) and split-results, which copies every slot
of the batch back from the four chips (flight recorder spans
``pad_and_stack`` and ``split_results``), in ms."""
from bench import layers


def read(run):
  return layers.host_ms_per_request(run, ("pad_and_stack", "split_results"))
