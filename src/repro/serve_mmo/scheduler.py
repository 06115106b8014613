"""Shape-bucketed request scheduler for the MMO serving engine.

Requests land in buckets keyed by (kind, op, padded shape, dtype, static
params).  Padding each dimension up to the next power of two (with a floor)
collapses the long tail of real-world problem shapes onto a handful of
compiled programs while bounding wasted compute at <4× (2× per padded axis
in the worst case, far less on average).

*Which* bucket batches next — and in what order requests leave a bucket —
is a pluggable ``SchedulingPolicy`` (serve_mmo/policy.py): FIFO (oldest
head first, the default and the engine's historical behavior), deadline
(earliest-feasible-deadline with priority tiers and fail-fast), or fair
share (weighted round-robin across tenants).  The scheduler itself only
owns storage: one heap per bucket, ordered by the policy's request rank
with submit seq breaking ties, so the FIFO policy's heaps degenerate to
exact submit order.

Deadline bookkeeping also lives here: ``add`` stamps each request's
absolute ``deadline_at``, and ``next_batch`` diverts requests whose
deadline already passed — or that the policy declares hopeless — into an
``expired`` side channel (``take_expired``) instead of the batch, so the
engine can fail them without burning executable time.

With ``max_batch_seconds``, batches are additionally *service-time-capped*
while deadline-tagged traffic is around: the policy's ``batch_cap`` hook
bounds each batch to roughly that many predicted seconds of work
(``predict_seconds`` × batch size), so a bulk batch on device can delay an
urgent arrival by at most the cap instead of a full ``max_batch`` service
time.  The scheduler tracks whether deadline traffic is queued (a live
counter) or recent (``deadline_lookback_s`` since the last deadline-tagged
submit) so pure-bulk workloads keep full batches.  See DESIGN.md §Adaptive
prediction.
"""
from __future__ import annotations

import heapq
import time
from typing import NamedTuple, Optional

import numpy as np

from repro.serve_mmo.api import ProblemRequest
from repro.serve_mmo.policy import QueueEntry, make_policy
# Canonical bucketing lives in tuning.cost_table so the cost table's key —
# the bucket signature — is the same function of a shape everywhere.
from repro.tuning.cost_table import MIN_BUCKET, bucket_dim, bucket_shape

__all__ = ["MIN_BUCKET", "BucketKey", "bucket_dim", "bucket_shape",
           "contract_shape", "request_bucket", "BucketScheduler"]


class BucketKey(NamedTuple):
  kind: str
  op: str
  shape: tuple     # padded problem shape
  dtypes: tuple    # one dtype string per operand, in operand order
  params: tuple


def contract_shape(key: BucketKey) -> tuple:
  """The (M, K, N) contraction a bucket's executable runs per request — what
  the cost table is keyed on and the dispatcher resolves with."""
  if key.kind == "mmo":
    return key.shape
  if key.kind == "closure":
    (nb,) = key.shape
    return (nb, nb, nb)
  if key.kind == "knn":
    qb, rb, db = key.shape  # addnorm contracts the feature dim
    return (qb, db, rb)
  raise ValueError(f"unknown kind {key.kind!r}")


def request_bucket(req: ProblemRequest,
                   min_bucket: int = MIN_BUCKET) -> BucketKey:
  """Deterministic bucket assignment for one request.  Every operand's dtype
  goes into the key: a bucket's AOT executable is dtype-exact, so two
  requests may share it only if ALL their operands agree."""
  dtypes = tuple(str(np.dtype(a.dtype)) for a in req.arrays.values())
  return BucketKey(kind=req.kind, op=req.op,
                   shape=bucket_shape(req.shape, min_bucket),
                   dtypes=dtypes, params=req.params)


class BucketScheduler:
  """Request queue + policy-driven bucket picker (host-side).

  ``predict_seconds`` is an optional ``BucketKey → seconds`` hook (the
  engine wires it to the cost table's per-request service prediction,
  ``MMOEngine.predict_request_seconds``) that deadline-aware policies use
  for feasibility; without it, fail-fast degrades to plain already-expired
  detection.
  """

  DEADLINE_LOOKBACK_S = 1.0  # default recency window for the batch cap

  def __init__(self, *, policy="fifo", max_batch: int = 8, clock=None,
               max_batch_seconds: Optional[float] = None,
               deadline_lookback_s: Optional[float] = None):
    if max_batch < 1:
      raise ValueError("max_batch must be >= 1")
    if max_batch_seconds is not None and not max_batch_seconds > 0.0:
      raise ValueError(
          f"max_batch_seconds must be > 0, got {max_batch_seconds}")
    self.policy = make_policy(policy)
    self.max_batch = max_batch
    self.max_batch_seconds = max_batch_seconds
    self.deadline_lookback_s = (self.DEADLINE_LOOKBACK_S
                                if deadline_lookback_s is None
                                else float(deadline_lookback_s))
    self.predict_seconds = None  # set by the engine (see MMOEngine)
    self._clock = clock if clock is not None else time.perf_counter
    self._buckets: dict[BucketKey, list[QueueEntry]] = {}  # heaps
    self._seq = 0
    # observability counters (read by MMOEngine.observability_state): how
    # many batches the policy picked and the wall time spent picking —
    # always real host seconds (perf_counter, not the injected clock, which
    # tests replace with synthetic time) so the exposed pick cost is the
    # actual scheduling overhead
    self.picks = 0
    self.pick_seconds = 0.0
    self._expired: list[ProblemRequest] = []
    self._deadline_queued = 0          # deadline-tagged entries not yet popped
    self._last_deadline_s: Optional[float] = None  # last deadline-tagged add

  def __len__(self) -> int:
    return sum(len(q) for q in self._buckets.values())

  def add(self, req: ProblemRequest) -> BucketKey:
    now = self._clock()
    if req.deadline_s is not None and req.deadline_at is None:
      req.deadline_at = now + float(req.deadline_s)
    key = request_bucket(req)
    entry = QueueEntry(self._seq, req, self.policy.request_rank(req, now))
    self._seq += 1
    if req.deadline_at is not None:
      self._deadline_queued += 1
      self._last_deadline_s = now
    heapq.heappush(self._buckets.setdefault(key, []), entry)
    self.policy.on_add(entry, key, self)
    return key

  def deadline_traffic_active(self, now: float) -> bool:
    """Whether the service-time batch cap should bind: deadline-tagged work
    is queued right now, or arrived within the last ``deadline_lookback_s``
    (an ongoing deadline stream keeps bulk batches short *between* urgent
    arrivals — the arrival that benefits from the cap is by definition not
    queued yet when the bulk batch is built)."""
    if self._deadline_queued > 0:
      return True
    return (self._last_deadline_s is not None
            and now - self._last_deadline_s <= self.deadline_lookback_s)

  def pending_buckets(self) -> dict:
    return {k: len(q) for k, q in self._buckets.items() if q}

  def next_batch(self, now: Optional[float] = None) -> Optional[tuple]:
    """(BucketKey, [requests]) for the policy's chosen bucket, or None.

    Requests whose deadline already passed, or that the policy fails fast,
    are diverted to the ``take_expired`` side channel rather than returned;
    a pick whose bucket expires away entirely falls through to the next
    pick, so a non-None return always carries at least one live request.
    """
    if now is None:
      now = self._clock()
    t0 = time.perf_counter()
    try:
      return self._next_batch(now)
    finally:
      self.pick_seconds += time.perf_counter() - t0

  def _next_batch(self, now: float) -> Optional[tuple]:
    while True:
      key = self.policy.pick(self, now)
      if key is None:
        return None
      cap = min(self.max_batch, self.policy.batch_cap(key, self, now))
      batch = self._take_locked(key, cap, now)
      if batch:
        return key, batch

  def _take_locked(self, key, cap: int, now: float) -> list:
    """Pop up to ``cap`` live requests from one bucket's heap — the shared
    core of ``next_batch`` and ``take_from``.  Expired / failed-fast
    entries are diverted to the ``take_expired`` side channel and do not
    count toward the cap; an emptied heap deletes its bucket."""
    heap = self._buckets.get(key)
    if not heap:  # stale pick (e.g. the bucket dict was cleared)
      self._buckets.pop(key, None)
      return []
    batch = []
    while heap and len(batch) < cap:
      entry = heapq.heappop(heap)
      if entry.taken:
        continue
      entry.taken = True
      if entry.req.deadline_at is not None:
        self._deadline_queued = max(0, self._deadline_queued - 1)
      deadline = entry.req.deadline_at
      if ((deadline is not None and deadline < now)
          or self.policy.fail_fast(entry, key, self, now)):
        self._expired.append(entry.req)
        continue
      batch.append(entry.req)
    if not heap:
      del self._buckets[key]
    if batch:
      self.policy.on_batch(key, batch, self)
      self.picks += 1
    return batch

  def peek_bucket(self, now: Optional[float] = None):
    """The policy's current bucket choice WITHOUT popping anything — the
    arena admission path peeks to decide whether the queue head is closure
    traffic (arena-eligible) or must go through the batch path.  Stale
    picks are cleaned up exactly like ``next_batch``."""
    if now is None:
      now = self._clock()
    while True:
      key = self.policy.pick(self, now)
      if key is None:
        return None
      if self._buckets.get(key):
        return key
      self._buckets.pop(key, None)

  def take_from(self, key, limit: int, now: Optional[float] = None) -> list:
    """Pop up to ``limit`` live requests from ONE specific bucket — the
    arena admission path, where the engine (not max_batch) bounds how many
    requests leave the queue: its free slot count.  Shares ``next_batch``'s
    mechanics (expiry diversion, policy bookkeeping, pick accounting)."""
    if now is None:
      now = self._clock()
    t0 = time.perf_counter()
    try:
      return self._take_locked(key, limit, now)
    finally:
      self.pick_seconds += time.perf_counter() - t0

  def take_expired(self) -> list:
    """Requests diverted by deadline expiry / fail-fast since the last call
    (drained by the engine, which fails their futures)."""
    expired, self._expired = self._expired, []
    return expired

