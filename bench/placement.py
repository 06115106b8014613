"""Per-layer quantities of the mesh placement, for the metric readers of the
four-chip cells in ``bench/metrics/``.  Each returns None when the run holds
nothing to read (a program whose ``batch_dispatch`` spans carry no
placement, a trace of one device), and the harness then leaves the metric
out of the result line.
"""
from __future__ import annotations


def chip_balance_pct(run):
  """The least busy device's busy seconds in the window over the busiest
  one's, in %: 100 when every chip did the same work."""
  busy = list(run.trace.busy_s.values())
  if len(busy) < 2 or max(busy) <= 0:
    return None
  return 100.0 * min(busy) / max(busy)


def dp_fill_pct(run):
  """Request slots over all slots of the dp batches dispatched in the
  window (the engine's ``batch_dispatch`` span args), in %: the rest is
  inert padding that rounds a batch up to a multiple of the devices."""
  live = slots = 0
  for ev in run.events:
    args = ev.get("args") or {}
    if (ev.get("ph") == "X" and ev.get("name") == "batch_dispatch"
        and args.get("schedule") == "dp"
        and run.t0 <= ev["ts"] * 1e-6 < run.t1):
      live += int(args["live"])
      slots += int(args["rb"])
  return 100.0 * live / slots if slots else None
