"""Mean wait of a request from submit to the start of its batch (flight
recorder: begin of the queued slice to begin of the execute slice), in
ms."""
from bench import layers


def read(run):
  return layers.queue_ms(run)
