"""Readers of the flight recorder's runtime spans (``loop_wait``,
``gc_pause``, ``compile``) for the per-layer metrics in ``bench/metrics/``.
Each returns None when the run holds none of the spans it reads, as a
program without them gives, and the harness then leaves the metric out.
"""
from __future__ import annotations

from bench import layers, trace_reduce


def all_spans(run, name: str) -> list:
  """Every complete recorder span called ``name``, window or not, as
  (start_s, end_s, args)."""
  return [(ev["ts"] * 1e-6, (ev["ts"] + ev["dur"]) * 1e-6,
           ev.get("args", {})) for ev in run.events
          if ev.get("ph") == "X" and ev.get("name") == name]


def gc_ms_per_s(run):
  """Garbage-collection pauses starting inside the window, in ms per
  second of window."""
  found = layers.spans(run, ("gc_pause",))
  if not found:
    return None
  return 1e3 * sum(d for _, d in found) / (run.t1 - run.t0)


def host_bound_pct(run):
  """Device idle time that the serving loop did not spend waiting for
  work, as a share of the window: the idle gaps of the profiler trace
  less their overlap with ``loop_wait`` spans, in %."""
  waits = all_spans(run, "loop_wait")
  if not waits:
    return None
  waits = trace_reduce.union([(s, e) for s, e, _ in waits])
  idle = 0.0
  for g0, g1 in run.trace.gaps:
    covered = sum(e - s for s, e in trace_reduce.clip(waits, g0, g1))
    idle += (g1 - g0) - covered
  return 100.0 * idle / (run.t1 - run.t0)


def compile_s_before(run):
  """Seconds the executable cache spent compiling (or loading) programs
  before the window opened: the set-up's compile share."""
  found = [args.get("seconds", e - s) for s, e, args in
           all_spans(run, "compile") if s < run.t0]
  return sum(found) if found else None
