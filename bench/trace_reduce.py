"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
device time per operation, and the longest idle gaps.

The device planes are ``/device:TPU:<id>``; their ``XLA Ops`` line holds one
event per HLO operation, named by its HLO text (``%simd2_minplus.3 = f32[...]
custom-call(...)``), so a Pallas kernel appears under the ``name`` its
``pallas_call`` was given.  Operations nest there (a ``while`` holds the
kernels of its body): busy time is the union of the intervals, and the time
of an operation is its self time, without the operations inside it.

The harness stamps a ``jax.profiler.TraceAnnotation`` (``ANCHOR``) at a known
``time.perf_counter()`` reading; its start on the host plane maps the
trace's clock onto ``perf_counter`` seconds, the clock of the engine's own
spans, so each idle gap can be set beside what the serving thread was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

ANCHOR = "bench_anchor"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_HLO_NAME = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)?(?: =|$)")


def op_name(event_name: str) -> str:
  """``%simd2_fixpoint_orand.1 = (f32[...]) custom-call(...)`` →
  ``simd2_fixpoint_orand``."""
  m = _HLO_NAME.match(event_name)
  return m.group(1) if m else event_name.split(" ", 1)[0]


def find_xplane(log_dir: str) -> str:
  paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                           recursive=True))
  if not paths:
    raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
  return paths[-1]


def load(path: str):
  from jax.profiler import ProfileData
  return ProfileData.from_file(path)


def anchor_ns(profile, name: str = ANCHOR) -> float:
  """Trace-clock start of the anchor annotation on the host plane."""
  for plane in profile.planes:
    if not plane.name.startswith("/host"):
      continue
    for line in plane.lines:
      for ev in line.events:
        if ev.name == name:
          return float(ev.start_ns)
  raise ValueError(f"no {name!r} event on a host plane of the trace")


def device_ops(profile, device_ids=None) -> dict:
  """device id → [(start_ns, end_ns, op name)] from its ``XLA Ops`` line."""
  out = {}
  for plane in profile.planes:
    m = _DEVICE.match(plane.name)
    if not m or (device_ids is not None and int(m.group(1)) not in device_ids):
      continue
    evs = []
    for line in plane.lines:
      if line.name == OPS_LINE:
        evs.extend((float(e.start_ns), float(e.start_ns + e.duration_ns),
                    op_name(e.name)) for e in line.events)
    out[int(m.group(1))] = sorted(evs)
  return out


def union(intervals) -> list:
  """Sorted, merged copy of (start, end) intervals."""
  merged = []
  for s, e in sorted(intervals):
    if merged and s <= merged[-1][1]:
      merged[-1][1] = max(merged[-1][1], e)
    else:
      merged.append([s, e])
  return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list:
  return [(max(s, lo), min(e, hi)) for s, e in intervals
          if min(e, hi) > max(s, lo)]


def self_times(events) -> list:
  """(start, end, name, self duration) for nested events on one line: each
  event's duration less that of the events directly inside it."""
  out = []
  stack = []  # indices into out of the open enclosing events
  for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
    while stack and out[stack[-1]][1] <= s:
      stack.pop()
    if stack:
      parent = out[stack[-1]]
      out[stack[-1]] = parent[:3] + (parent[3] - (min(e, parent[1]) - s),)
    out.append((s, e, name, e - s))
    stack.append(len(out) - 1)
  return out


@dataclasses.dataclass
class Reduced:
  window_s: float
  busy_s: dict            # device id → seconds busy within the window
  op_seconds: dict        # op name → self seconds, summed over devices
  gaps: list              # idle (start_s, end_s) of the first device

  @property
  def mean_busy_s(self) -> float:
    return sum(self.busy_s.values()) / max(1, len(self.busy_s))

  def idle_share(self) -> float:
    return 1.0 - self.mean_busy_s / self.window_s

  def kernel_seconds(self, pattern: str) -> float:
    """Self seconds of every op whose name matches ``pattern`` (regex)."""
    rx = re.compile(pattern)
    return sum(s for name, s in self.op_seconds.items() if rx.match(name))


def reduce(profile, *, t0_s: float, t1_s: float, anchor_perf_s: float,
           device_ids=None) -> Reduced:
  """Reduce ``profile`` over the window [t0_s, t1_s] of ``perf_counter``
  seconds; ``anchor_perf_s`` is the perf_counter reading at the anchor."""
  base = anchor_ns(profile)

  def to_s(ns):
    return (ns - base) * 1e-9 + anchor_perf_s

  per_dev = device_ops(profile, device_ids)
  if not per_dev:
    raise ValueError("the trace holds no TPU device plane")
  busy, ops, gaps = {}, {}, []
  for i, dev in enumerate(sorted(per_dev)):
    evs = [(to_s(s), to_s(e), n) for s, e, n in per_dev[dev]]
    spans = union(clip([(s, e) for s, e, _ in evs], t0_s, t1_s))
    busy[dev] = sum(e - s for s, e in spans)
    for s, e, name, own in self_times(evs):
      inside = min(e, t1_s) - max(s, t0_s)
      if inside > 0 and e > s:
        ops[name] = ops.get(name, 0.0) + own * inside / (e - s)
    if i == 0:
      edges = [t0_s] + [x for sp in spans for x in sp] + [t1_s]
      gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
              if edges[j + 1] > edges[j]]
  return Reduced(window_s=t1_s - t0_s, busy_s=busy, op_seconds=ops,
                 gaps=gaps)


def attribute_gaps(gaps, spans, top: int = 10) -> list:
  """The ``top`` longest idle gaps as [label, seconds], each labelled with
  the host span (start_s, end_s, name) that overlaps it most, or
  ``outside_spans`` when the serving thread was in none."""
  out = []
  for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
    best, label = 0.0, "outside_spans"
    for s, e, name in spans:
      ov = min(e, g1) - max(s, g0)
      if ov > best:
        best, label = ov, name
    out.append([label, g1 - g0])
  return out
