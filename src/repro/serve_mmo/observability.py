"""Request-lifecycle tracing: a bounded flight recorder over the serving path.

Latency percentiles say *how much* time a request spent; they never say
*where*.  This module stamps monotonic-clock spans at every state transition
a request goes through — submit, admit/reject, queued, batch pick,
pad-and-stack, resolve+compile, device compute, split-results,
done/expired/failed — into a ``FlightRecorder``: a fixed-capacity ring
buffer of Chrome trace events.

Why a ring-buffer flight recorder and not a log: the serving loop must never
block on, allocate unboundedly for, or fsync its own telemetry.  A ring of
the last N events costs one short lock + one deque extend per emission,
keeps memory constant under any load, and still answers the question an
operator actually asks ("what did the engine do *just now*?").  Old events
fall off the back; ``stats()`` reports how many were dropped so a truncated
window is visible, never silent.

The export format is Chrome trace-event JSON (``export()`` →
``{"traceEvents": [...]}``), loadable directly in Perfetto /
``about://tracing``:

  * per-request lifecycle — nestable async events (``ph`` 'b'/'e', one id
    per request): a ``queued`` slice (submit → batch pick) followed by an
    ``execute`` slice (pick → results), with kind/op/tenant on the begin
    and the terminal outcome (done / expired / failed) on the end;
  * per-batch phases — complete events (``ph`` 'X') on the executing
    thread's track: ``pad_and_stack`` (args carry the bytes the host wrote
    staging the batch and whether it was a zero-copy view),
    ``resolve_compile`` (args say cache hit or miss), ``device_compute``
    (args carry backend, schedule, padded batch, H2D bytes, measured
    iterations), ``split_results``.  Together
    these are the host/device time breakdown per batch.  Inside them:
    ``batch_dispatch`` (the compiled call returning, operand staging
    included; on a mesh-placed batch its args carry the schedule, padded
    size, live slots and devices holding one) and ``batch_wait``
    (``block_until_ready``) partition
    ``device_compute``; ``batch_d2h`` (the result's copy to the host)
    opens ``split_results``;
  * per-tick arena phases — ``arena_tick`` (launch + sweep) partitioned by
    ``arena_launch`` (the chunk dispatch), ``arena_wait`` (the host
    blocking on the tick's flags) and, on ticks that evict,
    ``arena_readout`` (reading every evicted slot back); then
    ``arena_finish`` (validation, results, futures) after the tick, and
    one ``arena_admit`` per admission (host pad + admit dispatch);
  * runtime spans — ``loop_wait`` (the serving loop blocked for want of
    work), ``compile`` (one per executable-cache miss, with the key and
    seconds) and ``gc_pause`` (one per garbage collection, with the
    generation and the objects collected, while ``watch_gc`` is on);
  * instants (``ph`` 'i') for admission rejections and batch failures.

A child span comes before its parent in the emitted list, so a reader that
takes the first span of largest overlap names the innermost phase.

Timestamps come from the engine's injected clock (microseconds), so
synthetic-clock tests produce exact, deterministic traces.

Cost discipline (benchmarks/serve_bench.py asserts the steady-state
overhead stays under its budget): the whole per-batch event set — batch
phases, every member request's pick + completion — is built locally and
pushed in ONE ``batch_complete`` call (one lock, one deque extend); an
arena tick, its readout and its finished requests likewise ride one
``arena_tick`` call, and an admission one ``arena_admit`` call.
``enabled=False`` turns every hook into an attribute check + return.
"""
from __future__ import annotations

import collections
import gc
import threading
import time
from typing import Optional, Sequence

__all__ = ["FlightRecorder", "DEFAULT_TRACE_CAPACITY"]

DEFAULT_TRACE_CAPACITY = 131072

_PID = 1  # one engine process per recorder


def _span(name: str, cat: str, tid: int, t0_s: float, t1_s: float,
          args: Optional[dict] = None) -> dict:
  """One complete (``X``) event from ``t0_s`` to ``t1_s`` (clock seconds).
  The length is taken between the scaled edges, so ``ts + dur`` is the
  scaled ``t1_s`` itself: a child and its parent that share an edge end
  (or start) on the same float, and overlap a gap by the same amount."""
  ts, te = t0_s * 1e6, t1_s * 1e6
  ev = {"ph": "X", "cat": cat, "name": name, "pid": _PID, "tid": tid,
        "ts": ts, "dur": max(0.0, te - ts)}
  if args is not None:
    ev["args"] = args
  return ev


class FlightRecorder:
  """Bounded ring buffer of Chrome trace events, thread-safe, O(1) append.

  Hooks are grouped by call site: ``request_begin`` (submit),
  ``request_rejected`` (admission), ``batch_complete`` (the whole per-batch
  event set in one emission), ``arena_admit`` / ``arena_tick`` (one
  emission per admission and per tick), ``request_picked`` /
  ``request_end`` (the expire/fail paths, where requests terminate outside
  a completed batch or tick), ``span`` (loop waits, compiles),
  ``instant``; ``watch_gc`` hooks garbage collections in.  Every hook is a no-op when ``enabled`` is False; callers
  with non-trivial args construction should still guard with
  ``if recorder.enabled:`` to keep the disabled path free."""

  def __init__(self, *, capacity: int = DEFAULT_TRACE_CAPACITY,
               clock=None, enabled: bool = True):
    if capacity < 1:
      raise ValueError(f"capacity must be >= 1, got {capacity}")
    self.capacity = int(capacity)
    self.enabled = bool(enabled)
    self._clock = clock if clock is not None else time.perf_counter
    self._lock = threading.Lock()
    self._events: collections.deque = collections.deque(maxlen=self.capacity)
    self._recorded = 0
    # gc_pause events wait here until the ring's lock is free: a collection
    # can start in a thread that holds it (see _on_gc)
    self._gc_pending: collections.deque = collections.deque()
    self._gc_t0: Optional[float] = None
    self._gc_hook = self._on_gc

  # -- clock -------------------------------------------------------------------

  def now(self) -> float:
    """The recorder's clock, in seconds."""
    return self._clock()

  def _ts(self, t_s: Optional[float] = None) -> float:
    """Trace timestamp in microseconds (Chrome trace's unit)."""
    return (self._clock() if t_s is None else t_s) * 1e6

  @staticmethod
  def _tid() -> int:
    return threading.get_ident() & 0x7FFFFFFF

  # -- raw emission ------------------------------------------------------------

  def _emit(self, events) -> None:
    with self._lock:
      self._events.extend(events)
      self._recorded += len(events)
      self._drain_gc_locked()

  def _drain_gc_locked(self) -> None:
    """Move pending ``gc_pause`` events into the ring.  Lock held."""
    while self._gc_pending:
      self._events.append(self._gc_pending.popleft())
      self._recorded += 1

  def span(self, name: str, *, cat: str, t0_s: float, t1_s: float,
           args: Optional[dict] = None) -> None:
    """One complete event on the calling thread's track: ``loop_wait``
    (the serving loop blocked on an empty queue), ``compile`` (one
    executable-cache miss)."""
    if not self.enabled:
      return
    self._emit((_span(name, cat, self._tid(), t0_s, t1_s, args),))

  # -- garbage collections -----------------------------------------------------

  def watch_gc(self) -> None:
    """Record a ``gc_pause`` span per garbage collection (idempotent)."""
    if self.enabled and self._gc_hook not in gc.callbacks:
      gc.callbacks.append(self._gc_hook)

  def unwatch_gc(self) -> None:
    if self._gc_hook in gc.callbacks:
      gc.callbacks.remove(self._gc_hook)

  def _on_gc(self, phase: str, info: dict) -> None:
    """``gc.callbacks`` hook.  The collection runs in whichever thread
    allocated, possibly one inside ``_emit`` holding the ring's lock, so
    the event is queued lock-free and moved into the ring only if the lock
    is free now; otherwise the next emission or read moves it.  No event
    is lost and no thread waits."""
    if phase == "start":
      self._gc_t0 = self._clock()
      return
    t0 = self._gc_t0
    if t0 is None:  # hooked in mid-collection
      return
    self._gc_t0 = None
    self._gc_pending.append(_span(
        "gc_pause", "runtime", self._tid(), t0, self._clock(),
        {"generation": info.get("generation"),
         "collected": info.get("collected")}))
    if self._lock.acquire(blocking=False):
      try:
        self._drain_gc_locked()
      finally:
        self._lock.release()

  # -- request lifecycle (nestable async, one id per request) ------------------

  def request_begin(self, rid: int, *, kind: str, op: str, tenant: str,
                    t_s: Optional[float] = None) -> None:
    """The request was admitted and queued: open its ``queued`` slice."""
    if not self.enabled:
      return
    self._emit((
        {"ph": "b", "cat": "request", "id": rid, "name": "queued",
         "pid": _PID, "tid": self._tid(), "ts": self._ts(t_s),
         "args": {"kind": kind, "op": op, "tenant": tenant}},))

  def request_picked(self, rid: int, *, t_s: Optional[float] = None) -> None:
    """Queued slice ends, execute slice begins (batch pick) — used by the
    batch-failure path; completed batches ride ``batch_complete``."""
    if not self.enabled:
      return
    ts = self._ts(t_s)
    tid = self._tid()
    self._emit((
        {"ph": "e", "cat": "request", "id": rid, "name": "queued",
         "pid": _PID, "tid": tid, "ts": ts},
        {"ph": "b", "cat": "request", "id": rid, "name": "execute",
         "pid": _PID, "tid": tid, "ts": ts}))

  def request_end(self, rid: int, outcome: str, *, executing: bool,
                  t_s: Optional[float] = None,
                  args: Optional[dict] = None) -> None:
    """Close a request's open slice with its terminal outcome ('done',
    'expired', 'failed').  ``executing`` says which slice is open: True
    closes ``execute`` (the request was in a batch), False closes
    ``queued`` (it never left the queue)."""
    if not self.enabled:
      return
    end_args = {"outcome": outcome}
    if args:
      end_args.update(args)
    self._emit((
        {"ph": "e", "cat": "request", "id": rid,
         "name": "execute" if executing else "queued",
         "pid": _PID, "tid": self._tid(), "ts": self._ts(t_s),
         "args": end_args},))

  # -- arena slot lifecycle (admit → tick×k → evict) ---------------------------

  def arena_admit(self, rid: int, *, slot: int, bucket: str,
                  t0_s: Optional[float] = None,
                  t_s: Optional[float] = None) -> None:
    """The request left the queue INTO an arena slot: its ``queued`` slice
    closes and its ``execute`` slice opens at ``t_s``, carrying the slot
    index.  The slice stays open across every tick the request resides
    (``arena_tick`` X-events land inside it) until the tick that evicts it
    closes it — together the admit → tick×k → evict span of one slot
    residency.  With ``t0_s`` an ``arena_admit`` span [t0_s, t_s] (host
    pad + admit dispatch) rides the same emission."""
    if not self.enabled:
      return
    ts = self._ts(t_s)
    tid = self._tid()
    events = []
    if t0_s is not None:
      events.append(_span("arena_admit", "arena", tid, t0_s, ts * 1e-6,
                          {"bucket": bucket, "slot": slot}))
    events.append({"ph": "e", "cat": "request", "id": rid, "name": "queued",
                   "pid": _PID, "tid": tid, "ts": ts})
    events.append({"ph": "b", "cat": "request", "id": rid, "name": "execute",
                   "pid": _PID, "tid": tid, "ts": ts,
                   "args": {"bucket": bucket, "slot": slot}})
    self._emit(events)

  def arena_tick(self, bucket: str, *, live: int, evicted: int, g: int,
                 t0_s: float, t1_s: float,
                 launched_s: Optional[float] = None,
                 flags_s: Optional[float] = None,
                 finish: Optional[tuple] = None, done=()) -> None:
    """One arena tick (≤ g fused iterations over every live slot) in one
    emission: the ``arena_tick`` span [t0_s, t1_s] with occupancy and the
    sweep's eviction count in args, preceded by its phases when
    ``launched_s`` is given — ``arena_launch`` [t0_s, launched_s],
    ``arena_wait`` [launched_s, flags_s] and ``arena_readout``
    [flags_s, t1_s] on a tick that evicts; ``arena_wait`` runs to t1_s on
    one that does not.  ``done`` holds (request id, slot, iterations,
    t_s) per answered eviction, each closing its ``execute`` slice;
    ``finish`` is the (start, end) of turning evictions into results
    (``arena_finish``)."""
    if not self.enabled:
      return
    tid = self._tid()
    events = []
    if launched_s is not None:
      readout = evicted > 0 and flags_s is not None
      events.append(_span("arena_launch", "arena", tid, t0_s, launched_s))
      events.append(_span("arena_wait", "arena", tid, launched_s,
                          flags_s if readout else t1_s))
      if readout:
        events.append(_span("arena_readout", "arena", tid, flags_s, t1_s,
                            {"evicted": evicted}))
    events.append(_span("arena_tick", "arena", tid, t0_s, t1_s,
                        {"bucket": bucket, "live": live, "evicted": evicted,
                         "g": g}))
    for rid, slot, iterations, t_s in done:
      events.append({"ph": "e", "cat": "request", "id": rid,
                     "name": "execute", "pid": _PID, "tid": tid,
                     "ts": t_s * 1e6,
                     "args": {"outcome": "done", "slot": slot,
                              "iterations": iterations}})
    if finish is not None:
      events.append(_span("arena_finish", "arena", tid, finish[0], finish[1],
                          {"bucket": bucket, "evicted": evicted}))
    self._emit(events)

  def request_rejected(self, rid: int, reason: str, *, kind: str, op: str,
                       tenant: str, t_s: Optional[float] = None) -> None:
    """Admission refused the request: one instant — a rejection has no
    duration, so it gets a point on the timeline, not an async pair."""
    if not self.enabled:
      return
    self._emit((
        {"ph": "i", "cat": "admission", "name": "reject", "pid": _PID,
         "tid": self._tid(), "ts": self._ts(t_s), "s": "t",
         "args": {"id": rid, "reason": reason, "kind": kind, "op": op,
                  "tenant": tenant}},))

  # -- the completed-batch fast path -------------------------------------------

  def batch_complete(self, *, label: str, scheduled_s: float,
                     stacked_s: float, executed_s: float, device_s: float,
                     completed_s: float, backend: str, schedule: str,
                     batch: int, padded: int, h2d_bytes: int,
                     cache_hit: bool, request_ids: Sequence[int],
                     arrivals_s: Sequence[float],
                     iterations=None, emit_pick: bool = True,
                     dispatched_s: Optional[float] = None,
                     fetched_s: Optional[float] = None,
                     sharding: Optional[dict] = None,
                     zero_copy: bool = False) -> None:
    """Emit one completed batch's whole event set in a single lock
    acquisition: the four phase spans (pad_and_stack / resolve_compile /
    device_compute / split_results), their children when stamped —
    ``batch_dispatch`` [executed_s, dispatched_s] and ``batch_wait``
    [dispatched_s, device_s] inside ``device_compute``, ``batch_d2h``
    [device_s, fetched_s] inside ``split_results``, each before its
    parent — and every member request's queued→execute transition (at the
    pick instant) and ``execute`` end (outcome done, with its latency).
    This is the serving loop's only steady-state trace call on the batch
    path, so its cost IS the tracing overhead the bench budgets.

    ``emit_pick=False`` skips the per-request queued→execute transition:
    retried/bisected sub-batches already closed ``queued`` and opened a
    fresh ``execute`` slice via ``batch_attempt_fail`` /
    ``batch_attempt_begin``, so only the terminal ``execute`` end is
    emitted here — one ``e`` per ``b`` per attempt.

    ``sharding`` (a mesh-placed batch only: its ``schedule``, padded size
    ``rb``, ``live`` request slots and ``chips_live``, the devices holding
    at least one of them) becomes the ``batch_dispatch`` span's args.

    ``zero_copy`` says the batch was staged as a view of its one request's
    buffer (``batching.zero_copy``); the ``pad_and_stack`` span's
    ``host_bytes`` is then 0, else ``h2d_bytes``: staging writes each
    slot once."""
    if not self.enabled:
      return
    tid = self._tid()
    ts_sched = scheduled_s * 1e6
    ts_done = completed_s * 1e6
    dev_args = {"bucket": label, "padded": padded, "backend": backend,
                "schedule": schedule, "h2d_bytes": h2d_bytes}
    if iterations is not None and len(iterations):
      dev_args["iterations"] = [int(i) for i in iterations]
    events = [
        _span("pad_and_stack", "batch", tid, scheduled_s, stacked_s,
              {"bucket": label, "batch": batch, "padded": padded,
               "h2d_bytes": h2d_bytes,
               "host_bytes": 0 if zero_copy else h2d_bytes,
               "zero_copy": zero_copy}),
        _span("resolve_compile", "batch", tid, stacked_s, executed_s,
              {"bucket": label, "cache": "hit" if cache_hit else "miss",
               "backend": backend, "schedule": schedule}),
    ]
    if dispatched_s is not None:
      events.append(_span("batch_dispatch", "batch", tid, executed_s,
                          dispatched_s, sharding))
      events.append(_span("batch_wait", "batch", tid, dispatched_s,
                          device_s))
    events.append(_span("device_compute", "batch", tid, executed_s, device_s,
                        dev_args))
    if fetched_s is not None:
      events.append(_span("batch_d2h", "batch", tid, device_s, fetched_s))
    events.append(_span("split_results", "batch", tid, device_s, completed_s,
                        {"bucket": label}))
    for rid, arrival_s in zip(request_ids, arrivals_s):
      if emit_pick:
        events.append({"ph": "e", "cat": "request", "id": rid,
                       "name": "queued", "pid": _PID, "tid": tid,
                       "ts": ts_sched})
        events.append({"ph": "b", "cat": "request", "id": rid,
                       "name": "execute", "pid": _PID, "tid": tid,
                       "ts": ts_sched})
      events.append({"ph": "e", "cat": "request", "id": rid,
                     "name": "execute", "pid": _PID, "tid": tid,
                     "ts": ts_done,
                     "args": {"outcome": "done",
                              "latency_ms": (completed_s - arrival_s) * 1e3}})
    self._emit(events)

  # -- the recovery path (retries / bisection) ---------------------------------

  def batch_attempt_begin(self, request_ids: Sequence[int], *,
                          t_s: Optional[float] = None) -> None:
    """Open a fresh ``execute`` slice for every member of a retried or
    bisected sub-batch — the previous attempt closed its slice with outcome
    'retried' (``batch_attempt_fail``), so each attempt reads as its own
    execute span under the request's async track."""
    if not self.enabled:
      return
    ts = self._ts(t_s)
    tid = self._tid()
    self._emit([{"ph": "b", "cat": "request", "id": rid, "name": "execute",
                 "pid": _PID, "tid": tid, "ts": ts}
                for rid in request_ids])

  def batch_attempt_fail(self, request_ids: Sequence[int], *, outcome: str,
                         picked_t_s: Optional[float] = None,
                         t_s: Optional[float] = None,
                         args: Optional[dict] = None) -> None:
    """Close every member's open ``execute`` slice after a failed attempt:
    ``outcome`` is 'retried' when recovery continues (retry or bisection)
    or 'failed' at the terminal attempt.  ``picked_t_s`` handles the first
    attempt, whose members never individually transitioned queued→execute
    (the success path batches that into ``batch_complete``): their
    ``queued`` end + ``execute`` begin are emitted first, at the pick
    time — keeping one ``e`` per ``b`` whichever way the attempt ends."""
    if not self.enabled:
      return
    ts = self._ts(t_s)
    tid = self._tid()
    events = []
    if picked_t_s is not None:
      ts_pick = picked_t_s * 1e6
      for rid in request_ids:
        events.append({"ph": "e", "cat": "request", "id": rid,
                       "name": "queued", "pid": _PID, "tid": tid,
                       "ts": ts_pick})
        events.append({"ph": "b", "cat": "request", "id": rid,
                       "name": "execute", "pid": _PID, "tid": tid,
                       "ts": ts_pick})
    end_args = {"outcome": outcome}
    if args:
      end_args.update(args)
    events.extend({"ph": "e", "cat": "request", "id": rid, "name": "execute",
                   "pid": _PID, "tid": tid, "ts": ts, "args": dict(end_args)}
                  for rid in request_ids)
    self._emit(events)

  def instant(self, name: str, *, cat: str = "engine",
              args: Optional[dict] = None,
              t_s: Optional[float] = None) -> None:
    if not self.enabled:
      return
    ev = {"ph": "i", "cat": cat, "name": name, "pid": _PID,
          "tid": self._tid(), "ts": self._ts(t_s), "s": "t"}
    if args:
      ev["args"] = args
    self._emit((ev,))

  # -- reading -----------------------------------------------------------------

  def events(self) -> list:
    """Snapshot of the live ring (oldest first)."""
    with self._lock:
      self._drain_gc_locked()
      return list(self._events)

  def stats(self) -> dict:
    with self._lock:
      self._drain_gc_locked()
      live = len(self._events)
      recorded = self._recorded
    return {"enabled": self.enabled, "capacity": self.capacity,
            "recorded": recorded, "live": live,
            "dropped": recorded - live}

  def clear(self) -> None:
    with self._lock:
      self._gc_pending.clear()
      self._events.clear()
      self._recorded = 0

  def export(self, *, process_name: str = "serve_mmo engine") -> dict:
    """Chrome trace-event JSON object: load the dump in Perfetto or
    ``about://tracing``.  Metadata events name the process; async request
    slices and per-thread batch tracks come from the ring."""
    meta = [{"ph": "M", "pid": _PID, "name": "process_name",
             "args": {"name": process_name}}]
    return {"traceEvents": meta + self.events(), "displayTimeUnit": "ms"}
