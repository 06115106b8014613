"""Mean wait of a request from submit to its arena slot (flight recorder:
begin of the queued slice to begin of the execute slice), in ms."""
from bench import layers


def read(run):
  return layers.queue_ms(run)
