"""Per-layer quantities of one traced run, shared by the metric readers in
``bench/metrics/``.  Each returns None when the run holds nothing to read,
and the harness then leaves the metric out of the result line.

Spans and counters come from the engine's flight recorder
(``engine.tracer.events()``, the program's own spans on ``perf_counter``);
device time comes from the profiler trace (``bench/trace_reduce.py``);
required work from ``bench/work.py``.
"""
from __future__ import annotations

import statistics

from bench import work


def lifecycle(run) -> dict:
  """request id → {"queued": s, "execute": s} from the recorder's async
  request slices (begin of ``queued`` at submit, begin of ``execute`` at
  batch pick or arena admission)."""
  out = {}
  for ev in run.events:
    if ev.get("cat") == "request" and ev.get("ph") == "b":
      out.setdefault(ev["id"], {}).setdefault(ev["name"], ev["ts"] * 1e-6)
  return out


def window_requests(run) -> list:
  return [s for s in run.served if s.future is not None]


def queue_ms(run):
  """Mean wait from submit to the start of service, over every request of
  the window that started service."""
  times = lifecycle(run)
  waits = [t["execute"] - t["queued"] for t in
           (times.get(s.request.request_id, {}) for s in window_requests(run))
           if "execute" in t and "queued" in t]
  return 1e3 * statistics.fmean(waits) if waits else None


def spans(run, names) -> list:
  """Complete (``X``) recorder spans named in ``names`` that start inside
  the window, as (start_s, duration_s)."""
  return [(ev["ts"] * 1e-6, ev["dur"] * 1e-6) for ev in run.events
          if ev.get("ph") == "X" and ev.get("name") in names
          and run.t0 <= ev["ts"] * 1e-6 < run.t1]


def span_mean_ms(run, names):
  found = spans(run, names)
  return 1e3 * statistics.fmean(d for _, d in found) if found else None


def host_ms_per_request(run, names):
  """Seconds in the named host spans over the window, per request
  completed in it, in ms."""
  found = spans(run, names)
  done = sum(1 for s in window_requests(run)
             if s.outcome == "done" and s.done_s <= run.t1)
  return 1e3 * sum(d for _, d in found) / done if found and done else None


def required_seconds(run) -> float:
  """Required time at the chip's peak of the window's work: each answered
  request's ``work.required_seconds``, times the share of its service
  (start of service to answer) that lies inside the window."""
  times = lifecycle(run)
  total = 0.0
  for s in window_requests(run):
    if s.outcome != "done":
      continue
    start = times.get(s.request.request_id, {}).get("execute", s.sent_s)
    end = s.done_s
    inside = min(end, run.t1) - max(start, run.t0)
    if inside <= 0 or end <= start:
      continue
    need = work.required_seconds(s.n, s.result.extras["iterations"],
                                 s.adj.dtype.itemsize, run.peak)
    total += need * inside / (end - start)
  return total


def idle_pct(run):
  return 100.0 * run.trace.idle_share()


def kernel_roofline_pct(run, pattern: str):
  """Required time over the device time of the kernels matching
  ``pattern``, summed over the devices used."""
  kernel_s = run.trace.kernel_seconds(pattern)
  return 100.0 * required_seconds(run) / kernel_s if kernel_s > 0 else None


def busy_mfu_pct(run):
  """Required time over device busy time, summed over the devices used:
  the share of the chip's peak the device reached while it was busy,
  whatever ran."""
  busy = sum(run.trace.busy_s.values())
  return 100.0 * required_seconds(run) / busy if busy > 0 else None
