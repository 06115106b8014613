"""The MMO serving engine: continuous micro-batching over shape buckets.

One engine owns a policy-driven bucket scheduler (FIFO by default; deadline
and fair-share policies via ``policy=`` — see serve_mmo/policy.py), an
admission controller (``max_queue`` / ``tenant_quota`` / ``max_backlog_s``
— see serve_mmo/admission.py), a live metrics registry
(``engine.metrics_snapshot()`` works mid-run from any thread — see
serve_mmo/metrics.py), an AOT executable cache, and the request
bookkeeping.  Two ways to run it:

  * synchronous — ``submit()`` then ``step()`` / ``run_until_idle()`` (or
    just ``future.result()``, which drives steps lazily).  Deterministic;
    what the benchmarks and tests use.
  * background loop — ``start()`` spawns a serving thread that batches
    whatever is queued as fast as it drains; ``submit()`` is then fully
    async and ``future.result()`` blocks on the completion event.  What the
    open-loop traffic driver (launch/serve_mmo.py) uses.

Batches execute OUTSIDE the queue lock: a long closure batch never blocks
concurrent ``submit`` calls — the continuous-batching property that lets
arrivals pile into the next batch while the current one runs.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Optional

import jax
import numpy as np

from repro.serve_mmo import batching
from repro.serve_mmo.admission import AdmissionController
from repro.serve_mmo.api import (DeadlineExceededError, MMOFuture, MMOResult,
                                 ProblemRequest, RejectedError)
from repro.serve_mmo.arena import (DEFAULT_ARENA_G, DEFAULT_CAPACITY,
                                   RequestArena)
from repro.serve_mmo.cache import ExecutableCache
from repro.serve_mmo.estimator import Estimate, ServiceEstimator
from repro.serve_mmo.faults import (ARM_FAILURE_KINDS, BatchTimeoutError,
                                    InjectedFault, NonFiniteResultError,
                                    classify_failure)
from repro.serve_mmo.metrics import ServeMetrics, bucket_label
from repro.serve_mmo.observability import FlightRecorder
from repro.serve_mmo.placement import Placement
from repro.serve_mmo.resilience import ResilienceManager
from repro.serve_mmo.scheduler import (BucketScheduler, contract_shape,
                                       request_bucket)

# the arena's (backend, block, schedule) identity for breaker/estimator
# accounting: one arm per closure bucket, never re-dispatched (per-slot
# state isolates poisoned requests instead of bisection)
_ARENA_ARM = ("arena", (), "local")


@dataclasses.dataclass
class RequestRecord:
  request_id: int
  kind: str
  op: str
  bucket: tuple
  batch_size: int
  arrival_s: float
  scheduled_s: float
  completed_s: float

  @property
  def latency_s(self) -> float:
    return self.completed_s - self.arrival_s

  @property
  def queue_s(self) -> float:
    return self.scheduled_s - self.arrival_s


@dataclasses.dataclass
class EngineStats:
  completed: int
  batches: int
  mean_batch: float
  latencies_s: np.ndarray
  cache: dict
  rejected: int = 0
  expired: int = 0
  # closure requests above the megakernel's size cap that an arena-mode or
  # megakernel-pinned engine served on the per-iteration batch path
  over_cap: int = 0
  # (bucket label, backend, schedule) → batches (arena: ticks) that arm ran
  arms: dict = dataclasses.field(default_factory=dict)
  # slots of the dp batches served: requests, and the inert padding that
  # rounds each batch up to a multiple of the mesh's devices
  dp_live_slots: int = 0
  dp_inert_slots: int = 0

  def percentile(self, q: float) -> float:
    if len(self.latencies_s) == 0:
      return float("nan")
    return float(np.percentile(self.latencies_s, q))

  def summary(self) -> str:
    # must stay printable for an engine that served nothing (zero batches,
    # zero records, all-rejected runs): percentiles report n/a, never a
    # formatting error or division by zero
    if len(self.latencies_s):
      lat = (f"p50={self.percentile(50) * 1e3:.1f}ms "
             f"p99={self.percentile(99) * 1e3:.1f}ms")
    else:
      lat = "p50=n/a p99=n/a"
    return (f"completed={self.completed} batches={self.batches} "
            f"mean_batch={self.mean_batch:.2f} {lat} "
            f"rejected={self.rejected} expired={self.expired} "
            f"over_cap={self.over_cap} "
            f"cache_hits={self.cache['hits']} "
            f"cache_misses={self.cache['misses']}")


class MMOEngine:
  """Serving engine for semiring problem requests (see api.py).

  Placement (``engine.placement``, serve_mmo/placement.py) decides each
  bucket's arm.  ``backend="auto"`` resolves backend *and* block config per
  bucket from the cost table (``cost_table=`` argument, else the
  process-global table — see repro.tuning.dispatch) at batch-build time.
  With a ``mesh``, batches whose per-request contraction exceeds
  ``shard_flops`` execute as a batched distributed schedule
  (core.distributed dp / SUMMA / kspan / ring) across the mesh, smaller
  buckets keep the single-device path; ``schedule="auto"`` picks the
  schedule from the cost table's mesh rows, a schedule name pins it.
  Decisions are memoized per bucket and baked into the executable-cache key,
  so steady state replays one stored executable per (bucket, batch, arm) and
  never retraces even if the global table is later mutated.

  QoS: ``policy`` selects the scheduling policy ('fifo' — the default and
  the historical behavior, 'deadline', 'fair', or a SchedulingPolicy
  instance); ``max_queue`` / ``tenant_quota`` / ``max_backlog_s`` configure
  admission control (all-None = admit everything, the historical behavior);
  requests carrying ``deadline_s`` that are still queued past their deadline
  fail with ``DeadlineExceededError`` under every policy.  ``clock`` injects
  a monotonic time source for the engine's arrival/deadline/metrics
  bookkeeping (tests use a synthetic clock; the default is
  ``time.perf_counter``).

  Adaptive QoS: the engine always *records* live feedback — every batch's
  measured service latency and every closure batch's measured convergence
  counts feed a per-(bucket, backend, schedule) EWMA estimator
  (serve_mmo/estimator.py).  With ``adaptive=True``,
  ``predict_request_seconds`` — the one number deadline feasibility,
  backlog admission, and the batch cap all consume — answers from that
  estimator (warm EWMA > static cost × measured iterations > static cost ×
  worst-case trips) instead of the static cost table alone, so predictions
  track the actual device under load.  ``max_batch_seconds`` arms the
  service-time batch cap: while deadline-tagged traffic is active, bulk
  batches are bounded to ~that many predicted seconds so an urgent arrival
  never waits a full max_batch service time behind one (see
  ``SchedulingPolicy.batch_cap``).  Neither knob changes dispatch decisions
  or executable-cache keys, so steady state still never retraces.

  Observability: the engine stamps request-lifecycle spans (submit,
  queued, batch pick, pad-and-stack, compile, device compute, split, done/
  expired/failed) into a bounded flight recorder
  (serve_mmo/observability.py; ``trace=False`` turns it off,
  ``export_trace()`` returns Chrome trace-event JSON), measures every
  batch's host vs device time breakdown into the metrics registry, and
  assembles ``observability_state()`` — the snapshot the Prometheus
  renderer (serve_mmo/exposition.py) and the HTTP endpoint
  (serve_mmo/httpd.py) serve.  Tracing is on by default.

  Fault tolerance (DESIGN.md §Fault tolerance): a failed batch no longer
  fails every co-batched future.  The recovery driver retries the failed
  sub-batch under ``transient_retries`` with exponential backoff
  (``retry_backoff_s``), then bisects it (``bisect=True``) so a single
  poisoned request costs O(log B) extra launches and fails alone while
  its siblings complete.  Per-(bucket, backend, schedule) circuit breakers
  (``breaker_threshold`` consecutive failures open one; ``None`` disables;
  serve_mmo/resilience.py) re-dispatch a persistently-failing arm's
  traffic to cost-ranked sibling arms — ultimately the reference dense
  backend — behind their own executable-cache keys, with a half-open
  probe batch after ``breaker_probe_s`` to recover.  Batch outputs are
  validated for NaNs before futures fulfill (``validate_results``;
  ±inf is legitimate tropical output), ``watchdog_s`` bounds a hung
  device computation (the batch fails instead of wedging the loop), and
  ``faults`` accepts a deterministic ``FaultInjector``
  (serve_mmo/faults.py) that exercises every one of these paths on the
  real code path.  Every retry/bisection/breaker transition lands in the
  flight recorder and the Prometheus surfaces.

  Continuous batching (DESIGN.md §Request arena): ``mode="arena"`` serves
  closure buckets from a device-resident slot buffer (serve_mmo/arena.py)
  instead of bucket-cycle batches — requests are admitted into free slots
  the moment they arrive, every live slot advances ``arena_g`` fused
  iterations per tick, and converged slots evict and backfill between
  ticks without retracing.  Non-closure buckets keep the batch path.
  Outputs and iteration counts stay bit-identical to ``mode="batch"``
  (pinned on the shared parity corpus in tests/test_arena.py); what
  changes is the waiting: an urgent arrival joins the running fixpoint at
  the next tick boundary instead of queueing behind a full bucket cycle.
  Closure buckets above the megakernel's size cap
  (``closure_megakernel.MAX_N``) stay on the batch path in either mode and
  are counted in ``stats().over_cap``; placement never hands them to the
  megakernel.
  """

  def __init__(self, *, backend: str = "auto", max_batch: int = 8,
               interpret: Optional[bool] = None,
               cost_table=None, mesh=None, schedule: str = "auto",
               shard_flops: float = 1e8,
               policy="fifo", max_queue: Optional[int] = None,
               tenant_quota=None, max_backlog_s: Optional[float] = None,
               clock=None,
               adaptive: bool = False,
               estimator: Optional[ServiceEstimator] = None,
               max_batch_seconds: Optional[float] = None,
               deadline_lookback_s: Optional[float] = None,
               trace: bool = True,
               faults=None, transient_retries: int = 1,
               retry_backoff_s: float = 0.002, bisect: bool = True,
               breaker_threshold: Optional[int] = 5,
               breaker_probe_s: float = 0.25,
               watchdog_s: Optional[float] = None,
               validate_results: bool = True,
               fallback_backends=None,
               mode: str = "batch",
               arena_capacity: int = DEFAULT_CAPACITY,
               arena_g: int = DEFAULT_ARENA_G):
    if mode not in ("batch", "arena"):
      raise ValueError(f"unknown mode {mode!r}; one of ('batch', 'arena')")
    self._clock = clock if clock is not None else time.perf_counter
    self.tracer = FlightRecorder(clock=self._clock, enabled=trace)
    self.cache = ExecutableCache(recorder=self.tracer)
    self.placement = Placement(
        backend=backend, cost_table=cost_table, mesh=mesh, schedule=schedule,
        shard_flops=shard_flops, interpret=interpret,
        fallback_backends=fallback_backends, cache=self.cache)
    self._static_cost: dict = {}  # BucketKey → (contraction s, worst trips)
    self.adaptive = bool(adaptive)
    self.estimator = estimator if estimator is not None else ServiceEstimator()
    self.scheduler = BucketScheduler(policy=policy, max_batch=max_batch,
                                     clock=self._clock,
                                     max_batch_seconds=max_batch_seconds,
                                     deadline_lookback_s=deadline_lookback_s)
    self.scheduler.predict_seconds = self.predict_request_seconds
    self.admission = AdmissionController(max_queue=max_queue,
                                         tenant_quota=tenant_quota,
                                         max_backlog_s=max_backlog_s)
    self.metrics = ServeMetrics(clock=self._clock)
    # -- fault tolerance (DESIGN.md §Fault tolerance) -----------------------
    if transient_retries < 0:
      raise ValueError(f"transient_retries must be >= 0, "
                       f"got {transient_retries}")
    self.faults = faults
    self.transient_retries = int(transient_retries)
    self.retry_backoff_s = float(retry_backoff_s)
    self.bisect = bool(bisect)
    self.watchdog_s = None if watchdog_s is None else float(watchdog_s)
    self.validate_results = bool(validate_results)
    self.resilience = ResilienceManager(threshold=breaker_threshold,
                                        probe_after_s=breaker_probe_s,
                                        clock=self._clock)
    # -- continuous batching (DESIGN.md §Request arena) ---------------------
    self.mode = mode
    self.arena_capacity = int(arena_capacity)
    self.arena_g = int(arena_g)
    self._arenas: dict = {}          # BucketKey → RequestArena
    self._arena_failures: dict = {}  # BucketKey → consecutive tick failures
    self._lock = threading.RLock()
    self._work = threading.Condition(self._lock)
    self._idle = threading.Condition(self._lock)  # signaled: _pending empty
    self._records: list[RequestRecord] = []
    self._batches = 0
    self._rejected = 0
    self._expired = 0
    self._over_cap = 0
    self._arms: dict = {}  # (bucket label, backend, schedule) → batches
    self._dp_live = 0   # request slots of the dp batches served
    self._dp_inert = 0  # inert padding slots of those batches
    self._next_id = 0
    self._pending: dict[int, MMOFuture] = {}
    self._inflight: set[int] = set()  # popped from the queue, executing now
    self._thread: Optional[threading.Thread] = None
    self._running = False
    self._stopped = False  # stop() was called; submit refuses until start()

  # -- submission ------------------------------------------------------------

  @staticmethod
  def _iteration_factor(key) -> float:
    """Contractions one request in this bucket runs: 1 for mmo/knn, the
    solver's worst-case trip count for closures (Leyzorek squares ~lg(nb)
    times, Bellman-Ford relaxes up to nb−1 times).  The cost-table row is
    one contraction; service predictions must scale by this or closure
    buckets look log-to-linear-factors cheaper than they are."""
    if key.kind != "closure":
      return 1.0
    (nb,) = key.shape
    (algorithm,) = key.params
    if algorithm == "bellman_ford":
      return float(max(1, nb - 1))
    return float(max(1, math.ceil(math.log2(nb))))

  def _static_point(self, key) -> tuple:
    """(per-contraction seconds, worst-case trips) for one bucket — the
    static prior the adaptive path corrects.  The per-contraction answer is
    ``tuning.dispatch.contraction_seconds`` (measured cost-table row when
    someone benchmarked the point — for a fixed ``backend`` the table is
    consulted for that backend's rows too — else the roofline prior);
    memoized per bucket under the engine lock like the dispatch decision
    itself."""
    with self._lock:
      memo = self._static_cost.get(key)
      if memo is None:
        m, k, n = contract_shape(key)
        from repro.tuning import dispatch as _dispatch
        # arena-mode closure buckets execute on the arena arm, so their
        # static prior prices slot-seconds there (the fused-chunk roofline —
        # see tuning/cost_table.py), not whatever the batch path would pick
        backend = ("arena" if self._arena_serves(key)
                   else self.placement.backend)
        _, _, s = _dispatch.contraction_seconds(
            key.op, m, k, n, key.dtypes[0], backend=backend,
            table=self.placement.cost_table)
        memo = (s, self._iteration_factor(key))
        self._static_cost[key] = memo
      return memo

  def predict_request(self, key) -> Estimate:
    """Predicted service seconds for ONE request of this bucket, with its
    provenance.  Batch compute scales linearly with occupied slots, so this
    is also the request's marginal contribution to a batch and to queue
    backlog — what the deadline policy's feasibility check (a lower bound
    on the serving batch's duration), the admission controller's backlog
    accounting, and the service-time batch cap all consume.

    Non-adaptive engines answer the static prediction (per-contraction cost
    × the bucket's worst-case trip count) — the historical behavior.
    Adaptive engines route through the EWMA estimator, which prefers warm
    measured service latency, then static cost × measured convergence
    counts, then the static prediction."""
    contraction_s, trips = self._static_point(key)
    if not self.adaptive:
      return Estimate(contraction_s * trips, "static")
    if self._arena_serves(key):
      # the arena's estimator cell holds measured slot-seconds per request
      # (admit → evict), observed at eviction — exactly the residency the
      # admission controller charges for
      backend, _, schedule = _ARENA_ARM
    else:
      backend, _, schedule = self.placement.resolve(key)
    return self.estimator.predict(key, backend, schedule, contraction_s,
                                  trips)

  def predict_request_seconds(self, key) -> float:
    """``predict_request`` without the provenance — the scheduler hook."""
    return self.predict_request(key).seconds

  def submit(self, req: ProblemRequest) -> MMOFuture:
    """Queue one request; returns its future.  Admission may refuse — the
    future then arrives already failed with ``RejectedError`` (state
    'rejected') and nothing was queued.  Raises RuntimeError after
    ``stop()`` (submit-after-stop is an error, not a silent queue-forever)."""
    fut = MMOFuture(self, req)
    with self._work:
      if self._stopped:
        raise RuntimeError(
            "submit() on a stopped engine: stop() shut the serving loop "
            "down; call start() to resume accepting requests")
      req.request_id = self._next_id
      self._next_id += 1
      req.arrival_s = self._clock()
      if req.deadline_s is not None and req.deadline_at is None:
        req.deadline_at = req.arrival_s + float(req.deadline_s)
      cost = 0.0
      if self.admission.max_backlog_s is not None:
        key = request_bucket(req)
        est = self.predict_request(key)
        cost = est.seconds
        req.predicted_source = est.source
      verdict = self.admission.try_admit(req, cost_s=cost)
      if verdict is not None:
        kind, reason = verdict
        self._rejected += 1
        self.metrics.on_reject(kind)
        self.tracer.request_rejected(req.request_id, kind, kind=req.kind,
                                     op=req.op, tenant=req.tenant,
                                     t_s=req.arrival_s)
        fut._fail(RejectedError(
            f"request {req.request_id} ({req.kind}/{req.op}) rejected: "
            f"{reason}"))
        return fut
      self.metrics.on_submit()
      self.tracer.request_begin(req.request_id, kind=req.kind, op=req.op,
                                tenant=req.tenant, t_s=req.arrival_s)
      self.scheduler.add(req)
      self._pending[req.request_id] = fut
      self._work.notify()
    return fut

  def pending(self) -> int:
    with self._lock:
      return len(self._pending)

  # -- execution -------------------------------------------------------------

  def _expire_locked(self, reqs) -> None:
    """Fail requests whose deadline passed while queued (or that the policy
    failed fast as hopeless).  Engine lock held by the caller."""
    self._expired += len(reqs)
    for r in reqs:
      self.admission.on_dequeue(r)
      self.admission.on_done(r)
      self.metrics.on_expire(request_bucket(r))
      self.tracer.request_end(r.request_id, "expired", executing=False)
      fut = self._pending.pop(r.request_id, None)
      if fut is not None:
        fut._fail(DeadlineExceededError(
            f"request {r.request_id} ({r.kind}/{r.op}) missed its "
            f"{r.deadline_s:g}s deadline while queued"))
    if not self._pending:
      self._idle.notify_all()

  def step(self) -> int:
    """Serve one engine step; returns #requests completed.  Batch mode
    schedules + executes one bucket batch.  Arena mode admits queued
    closure requests into free slots, ticks every live arena, and completes
    evictions (non-closure traffic still batches)."""
    if self.mode == "arena":
      return self._step_arena()
    return self._step_batch()

  def _step_batch(self) -> int:
    """Schedule + execute one bucket batch; returns #requests completed.
    Requests whose deadline lapsed in the queue are failed here (the
    scheduler diverts them out of the batch) without costing a batch slot."""
    with self._lock:
      picked = self.scheduler.next_batch(now=self._clock())
      expired = self.scheduler.take_expired()
      if expired:
        self._expire_locked(expired)
      if picked is None:
        return 0
      key, reqs = picked
      for r in reqs:
        self.admission.on_dequeue(r)
      self._inflight.update(r.request_id for r in reqs)
    scheduled_s = self._clock()
    try:
      return self._serve_batch(key, reqs, scheduled_s)
    except Exception as e:  # noqa: BLE001 — recovery-driver bug safety net:
      # whatever went wrong inside the driver itself, never leak in-flight
      # requests (a wedged future blocks result() forever)
      with self._lock:
        leaked = [r for r in reqs if r.request_id in self._inflight]
      self._fail_requests(key, leaked, e)
      self.tracer.instant("batch_fail", cat="batch",
                          args={"bucket": bucket_label(key),
                                "error": type(e).__name__})
      return 0

  def _fail_requests(self, key, reqs, exc) -> None:
    """Terminally fail ``reqs`` with ``exc``: the once-per-request final
    accounting (inflight, admission, metrics, future).  Trace emission is
    the caller's job — the recovery driver already closed these requests'
    execute slices with outcome 'failed'."""
    with self._lock:
      for r in reqs:
        self._inflight.discard(r.request_id)
        self.admission.on_done(r)
        self.metrics.on_fail(key)
        fut = self._pending.pop(r.request_id, None)
        if fut is not None:
          fut._fail(exc)
      if not self._pending:
        self._idle.notify_all()

  # -- shared completion and launch accounting ------------------------------

  def _complete(self, key, done) -> int:
    """The once-per-request final accounting of answered requests, shared by
    the batch and arena paths: request record, admission, metrics, the
    future, the idle notify.  ``done`` holds (request, result, scheduled_s,
    completed_s, batch size) tuples; the engine lock is held once for all
    of them.  Returns how many requests completed."""
    with self._lock:
      for r, res, scheduled_s, completed_s, batch_size in done:
        self._inflight.discard(r.request_id)
        self._records.append(RequestRecord(
            request_id=r.request_id, kind=r.kind, op=r.op, bucket=tuple(key),
            batch_size=batch_size, arrival_s=r.arrival_s,
            scheduled_s=scheduled_s, completed_s=completed_s))
        self.admission.on_done(r)
        self.metrics.on_complete(key, queue_s=scheduled_s - r.arrival_s,
                                 service_s=completed_s - scheduled_s)
        fut = self._pending.pop(r.request_id, None)
        if fut is not None:
          try:
            fut._fulfill(res)
          except Exception as cb:  # noqa: BLE001 — a bad future callback
            # must not take down the serving loop or its co-batched
            # siblings; the result IS delivered (state was set before the
            # callback ran), so this request still counts completed
            self.tracer.instant(
                "future_callback_error", cat="engine",
                args={"id": r.request_id, "error": type(cb).__name__})
      if not self._pending:
        self._idle.notify_all()
    return len(done)

  def _count_launch_locked(self, key, label: str, backend: str,
                           schedule: str, *, host_s: float, device_s: float,
                           h2d_bytes: int) -> None:
    """One successful launch (a batch, or an arena tick): the batch count,
    its arm's count in ``stats().arms``, and the batch metrics.  Engine
    lock held."""
    self._batches += 1
    arm = (label, backend, schedule)
    self._arms[arm] = self._arms.get(arm, 0) + 1
    self.metrics.on_batch(key, host_s=host_s, device_s=device_s,
                          h2d_bytes=h2d_bytes)

  def _arm_ok(self, key, arm: tuple, label: str) -> None:
    """Feed a successful launch on ``arm`` (backend, block, schedule) to its
    breaker; a breaker that recovers leaves a ``breaker_close`` instant."""
    if self.resilience.on_success(key, arm) == "close" and self.tracer.enabled:
      self.tracer.instant("breaker_close", cat="resilience",
                          args={"bucket": label, "backend": arm[0],
                                "schedule": arm[2]})

  def _arm_failed(self, key, arm: tuple, label: str, exc,
                  phase: str) -> None:
    """Classify a failed launch on ``arm`` and count its kind.  Only
    arm-implicating kinds feed the breaker — a host-side stack/split
    failure would fail identically on every backend (faults.py) — and a
    breaker that opens leaves a ``breaker_open`` instant."""
    kind = classify_failure(exc, phase)
    self.metrics.on_batch_failure(kind)
    if (kind in ARM_FAILURE_KINDS
        and self.resilience.on_failure(key, arm) == "open"
        and self.tracer.enabled):
      self.tracer.instant("breaker_open", cat="resilience",
                          args={"bucket": label, "backend": arm[0],
                                "schedule": arm[2], "kind": kind})

  def _retry_backoff(self, failures: int) -> None:
    """Count one retry and back off before it: ``retry_backoff_s`` doubled
    per earlier consecutive failure (``failures``), at most 8×."""
    self.metrics.on_retry()
    backoff = self.retry_backoff_s * (2.0 ** min(failures, 3))
    if backoff > 0.0:
      time.sleep(backoff)

  def _launch(self, fn, label: str, backend: str, rids):
    """Run one device launch ``fn`` (a batch program, or an arena tick and
    its sweep) behind the ``execute`` and ``slow`` fault hooks, under the
    engine watchdog (``watchdog_s``; None = inline, the zero-overhead
    path).  On timeout the launch fails with ``BatchTimeoutError`` instead
    of wedging the serving loop; the worker thread is abandoned — XLA's
    async dispatch cannot be cancelled, so the device computation may still
    finish later and its result is discarded (DESIGN.md §Fault tolerance on
    why this is the least-bad option)."""
    slow_rule = None
    if self.faults is not None:
      if self.faults.check("execute", label=label, backend=backend,
                           request_ids=rids):
        raise InjectedFault("execute", label)
      slow_rule = self.faults.check("slow", label=label, backend=backend,
                                    request_ids=rids)

    def run():
      if slow_rule is not None:
        time.sleep(slow_rule.delay_s)
      return fn()

    if self.watchdog_s is None:
      return run()
    box: dict = {}
    done = threading.Event()

    def worker():
      try:
        box["out"] = run()
      except BaseException as e:  # noqa: BLE001 — marshalled to the caller
        box["exc"] = e
      finally:
        done.set()

    t = threading.Thread(target=worker, name="mmo-batch-watchdog",
                         daemon=True)
    t.start()
    if not done.wait(self.watchdog_s):
      raise BatchTimeoutError(label, self.watchdog_s)
    if "exc" in box:
      raise box["exc"]
    return box["out"]

  # -- arena mode (DESIGN.md §Request arena) ---------------------------------

  def _arena_serves(self, key) -> bool:
    return self.mode == "arena" and self.placement.megakernel_serves(key)

  def _arena_for_locked(self, key) -> RequestArena:
    """One arena per closure bucket, created lazily.  Engine lock held."""
    arena = self._arenas.get(key)
    if arena is None:
      arena = RequestArena(key, capacity=self.arena_capacity, g=self.arena_g,
                           cache=self.cache,
                           interpret=self.placement.interpret,
                           clock=self._clock)
      self._arenas[key] = arena
      self._arena_failures[key] = 0
    return arena

  def _arena_live_locked(self) -> bool:
    """Whether any arena holds resident requests.  Engine lock held; part
    of every drain condition — scheduler-empty alone no longer means idle."""
    return any(a.live_slots() for a in self._arenas.values())

  def _step_arena(self) -> int:
    """One arena-mode step: admit → (batch fallback) → tick/evict."""
    batch_head = self._arena_admit_phase()
    completed = 0
    if batch_head:
      # the policy's chosen bucket is not closure traffic: serve it through
      # the unchanged batch path so mixed workloads keep working
      completed += self._step_batch()
    completed += self._arena_tick_phase()
    return completed

  def _arena_admit_phase(self) -> bool:
    """Move queued closure requests into free arena slots, respecting the
    policy's bucket order.  Returns True when the queue head is not arena
    traffic — non-closure, or a closure above the megakernel's cap (the
    caller then runs one batch step).  Admission stops at a full
    arena — its slots free up at the next sweep, so progress is guaranteed
    without ever popping more requests than there are slots."""
    while True:
      with self._lock:
        now = self._clock()
        key = self.scheduler.peek_bucket(now)
        if key is None:
          return False
        if not self._arena_serves(key):
          return True
        arena = self._arena_for_locked(key)
        free = arena.free_slots()
        if free <= 0:
          return False
        taken = self.scheduler.take_from(key, free, now=now)
        expired = self.scheduler.take_expired()
        if expired:
          self._expire_locked(expired)
        label = bucket_label(key)
        for r in taken:
          self.admission.on_dequeue(r)
          self._inflight.add(r.request_id)
          t0 = self._clock()
          slot = arena.admit(r, now=t0)
          if self.tracer.enabled:
            self.tracer.arena_admit(r.request_id, slot=slot, bucket=label,
                                    t0_s=t0, t_s=self._clock())

  def _arena_tick_phase(self) -> int:
    """Tick every arena with live slots, then complete its evictions."""
    with self._lock:
      arenas = [(k, a) for k, a in self._arenas.items() if a.live_slots()]
    completed = 0
    for key, arena in arenas:
      completed += self._tick_arena(key, arena)
    return completed

  def _tick_arena(self, key, arena) -> int:
    """One tick of one arena: the fused chunk launch and the eviction sweep,
    with the launch accounting and completion the batch path shares."""
    label = bucket_label(key)
    rids = [r.request_id for r in arena.live_requests()]
    if not rids:
      return 0
    t0 = self._clock()
    launched = []  # the clock when the chunk launch returned

    def run():
      arena.tick()
      launched.append(self._clock())
      return arena.sweep()  # blocks on the tick's device flags

    try:
      evictions = self._launch(run, label, _ARENA_ARM[0], rids)
    except Exception as e:  # noqa: BLE001 — classified + retried below
      self._arena_tick_failed(key, arena, e)
      return 0
    t1 = self._clock()
    self._arm_ok(key, _ARENA_ARM, label)
    with self._lock:
      self._arena_failures[key] = 0
      self._count_launch_locked(key, label, _ARENA_ARM[0], _ARENA_ARM[2],
                                host_s=0.0, device_s=t1 - t0, h2d_bytes=0)
    finish = None
    done = []
    completed = 0
    if evictions:
      f0 = self._clock()
      completed = self._finish_evictions(key, evictions, label, done)
      finish = (f0, self._clock())
    if self.tracer.enabled:
      # one emission per tick: its phases, the answered evictions and the
      # finish, after the futures are fulfilled
      self.tracer.arena_tick(label, live=len(rids), evicted=len(evictions),
                             g=arena.g, t0_s=t0, t1_s=t1,
                             launched_s=launched[0] if launched else None,
                             flags_s=arena.flags_s, finish=finish, done=done)
    return completed

  def _arena_tick_failed(self, key, arena, exc) -> None:
    """Tick failure recovery: slots stay resident under the transient-retry
    budget (the next step retries the whole tick); once the budget is spent
    every resident request fails together and the arena resets.  There is
    no bisection here — per-slot state already isolates poisoned requests
    (a NaN slot fails alone at eviction), so a tick-level failure is by
    construction arm-wide, not request-specific."""
    label = bucket_label(key)
    self._arm_failed(key, _ARENA_ARM, label, exc, "execute")
    with self._lock:
      failures = self._arena_failures.get(key, 0) + 1
      self._arena_failures[key] = failures
    if failures <= self.transient_retries:
      self._retry_backoff(failures - 1)
      return
    with self._lock:
      self._arena_failures[key] = 0
    victims = arena.reset()
    if self.tracer.enabled:
      for r in victims:
        self.tracer.request_end(r.request_id, "failed", executing=True)
      self.tracer.instant("batch_fail", cat="batch",
                          args={"bucket": label, "batch": len(victims),
                                "error": type(exc).__name__})
    self._fail_requests(key, victims, exc)

  def _finish_evictions(self, key, evictions, label, done) -> int:
    """Turn evictions into results: per-request validation, estimator
    feedback, and completion.  The estimator observes measured slot-seconds
    (admit → evict, rb=1) — the per-request residency QoS predictions
    price — plus the measured iteration count, mirroring the batch path's
    two feedback signals.  Appends (request id, slot, iterations,
    completion time) of each answered request to ``done``, for the tick's
    trace emission."""
    answered = []
    for ev in evictions:
      r = ev.request
      value = ev.value
      poisoned = False
      if self.faults is not None:
        nf = self.faults.check("nonfinite", label=label, backend="arena",
                               request_ids=[r.request_id])
        if nf is not None:
          poisoned = True
          if np.issubdtype(value.dtype, np.floating):
            value = np.full_like(value, np.nan)
      bad = (self.validate_results
             and np.issubdtype(value.dtype, np.floating)
             and bool(np.isnan(value).any()))
      if poisoned or bad:
        # garbage fails THIS slot's future alone; neighbors complete —
        # the isolation the batch path needs bisection for
        exc = NonFiniteResultError(label, [ev.slot])
        self._arm_failed(key, _ARENA_ARM, label, exc, "execute")
        if self.tracer.enabled:
          self.tracer.request_end(r.request_id, "failed", executing=True,
                                  args={"slot": ev.slot})
        self._fail_requests(key, [r], exc)
        continue
      now = self._clock()
      res = MMOResult(value=value,
                      extras={"iterations": int(ev.iterations)})
      self.estimator.observe_iterations(key, [int(ev.iterations)])
      self.estimator.observe_batch(key, _ARENA_ARM[0], _ARENA_ARM[2], 1,
                                   now - ev.admit_s)
      done.append((r.request_id, ev.slot, int(ev.iterations), now))
      answered.append((r, res, ev.admit_s, now, 1))
    return self._complete(key, answered)

  # -- batch mode --------------------------------------------------------------

  def _serve_batch(self, key, reqs, scheduled_s: float) -> int:
    """The recovery driver: execute the picked batch, isolating failures by
    bounded retry + bisection so innocent co-batched requests complete.

    A LIFO stack of (sub-batch, retries left, attempt index) starts with
    the whole batch.  A failed sub-batch is retried whole under its
    ``transient_retries`` budget (exponential backoff — a transient blip
    usually clears); once the budget is spent it is *bisected* and each
    half re-enters the stack with a fresh budget.  A single poisoned
    request in a batch of B therefore costs O(log B) extra launches — it
    keeps landing in ever-smaller failing halves until it fails alone —
    and total attempts are bounded by (retries+1)·(2B−1).  Every sub-batch
    size is re-bucketed to its own padded size, so bisection launches hit
    existing executable-cache entries (prewarm compiles every batch size
    ``placement.padded_batch`` can give).

    Accounting across attempts is once-per-request for final outcomes
    (``on_complete`` / ``on_fail`` / admission / futures), per-attempt for
    attempt-scoped telemetry (failure kinds, breaker transitions, batch
    phase spans), and first-fixpoint-only for iteration observations
    (``observed`` below) — a retried closure batch must not double-feed
    the estimator.  Returns #requests completed (innocents complete even
    when a poisoned sibling fails)."""
    label = bucket_label(key)
    observed: set = set()   # rids whose measured iterations were recorded
    stack = [(list(reqs), self.transient_retries, 0)]
    completed = 0
    while stack:
      sub, retries_left, attempt = stack.pop()
      if attempt > 0 and self.tracer.enabled:
        # a fresh execute slice per retried/bisected attempt — the failed
        # attempt closed the previous one with outcome 'retried'
        self.tracer.batch_attempt_begin([r.request_id for r in sub])
      try:
        results, info = self._attempt(
            key, sub, observed, scheduled_s if attempt == 0 else None)
      except Exception as e:  # noqa: BLE001 — classified + counted in _attempt
        will_retry = retries_left > 0
        will_bisect = not will_retry and self.bisect and len(sub) > 1
        if self.tracer.enabled:
          self.tracer.batch_attempt_fail(
              [r.request_id for r in sub],
              outcome="retried" if (will_retry or will_bisect) else "failed",
              picked_t_s=scheduled_s if attempt == 0 else None,
              args={"error": type(e).__name__})
        if will_retry:
          self._retry_backoff(attempt)
          stack.append((sub, retries_left - 1, attempt + 1))
        elif will_bisect:
          mid = len(sub) // 2
          self.metrics.on_retry(2)
          if self.tracer.enabled:
            self.tracer.instant(
                "batch_bisect", cat="resilience",
                args={"bucket": label, "batch": len(sub),
                      "halves": [mid, len(sub) - mid],
                      "error": type(e).__name__})
          # each half gets the full transient budget (a rate-mode fault can
          # hit an innocent half; one unlucky draw must not fail it), and
          # the left half runs first (LIFO)
          stack.append((sub[mid:], self.transient_retries, attempt + 1))
          stack.append((sub[:mid], self.transient_retries, attempt + 1))
        else:
          self._fail_requests(key, sub, e)
          self.tracer.instant("batch_fail", cat="batch",
                              args={"bucket": label, "batch": len(sub),
                                    "error": type(e).__name__})
        continue
      completed += self._complete_sub(key, sub, results, info, scheduled_s,
                                      emit_pick=attempt == 0)
    return completed

  def _attempt(self, key, reqs, observed: set, start_s: Optional[float]):
    """Execute one sub-batch once on the best currently-available arm.
    Returns (results, info dict); raises the (already classified, counted,
    and breaker-fed) failure otherwise.  ``start_s`` is the batch pick time
    for the first attempt (so the fast path's spans match the historical
    trace exactly); retries stamp their own start."""
    label = bucket_label(key)
    rids = [r.request_id for r in reqs]
    placement = self.placement
    arm, probe = self.resilience.pick(key, placement.resolve(key),
                                      lambda: placement.fallback_arms(key))
    backend, block, schedule = arm
    rb = placement.padded_batch(len(reqs), schedule)
    if self.tracer.enabled and probe:
      self.tracer.instant("breaker_probe", cat="resilience",
                          args={"bucket": label, "backend": backend,
                                "schedule": schedule})
    faults = self.faults
    attempt_s = self._clock() if start_s is None else start_s
    phase = "stack"
    try:
      # the padding slots that keep the executable set bounded are inert:
      # live size 0, operands already the answer, so a closure slot (and a
      # dp shard holding only padding) leaves its fixpoint at the first
      # check instead of recomputing a request
      stacked = batching.stack_batch(key, reqs, inert=rb - len(reqs))
      zero_copy = batching.zero_copy(key, reqs, rb - len(reqs))
      h2d_bytes = batching.stacked_nbytes(stacked)
      stacked_s = self._clock()
      phase = "compile"
      if faults is not None and faults.check("compile", label=label,
                                             backend=backend,
                                             request_ids=rids):
        # raised BEFORE the cache is consulted: an injected compile failure
        # must never poison the executable cache with a broken entry
        raise InjectedFault("compile", label)
      misses_before = self.cache.misses
      compiled = placement.program(key, rb, backend, block, schedule,
                                   stacked)
      cache_hit = self.cache.misses == misses_before
      # estimator observations start AFTER compilation: a cache-miss batch
      # must not feed trace+compile time (orders of magnitude above steady
      # service) into the EWMA as if it were device latency
      executed_s = self._clock()
      phase = "execute"
      dispatched = []  # the clock when the compiled call returned

      def run():
        out = compiled(*stacked)
        dispatched.append(self._clock())
        # block on the device result here so the device-compute window
        # (executed_s → device_s) is honest: jax dispatch is async, and
        # without the sync the first np.asarray below would absorb the
        # whole device time into the host-side split span
        jax.block_until_ready(out)
        return out

      out = self._launch(run, label, backend, rids)
      device_s = self._clock()
      # one D2H conversion for validation + split (np.asarray on numpy is
      # free downstream)
      out = (tuple(np.asarray(x) for x in out)
             if isinstance(out, (tuple, list)) else np.asarray(out))
      fetched_s = self._clock()
      if faults is not None:
        nf = faults.check("nonfinite", label=label, backend=backend,
                          request_ids=rids)
        if nf is not None:
          out = batching.poison_output(
              key, out,
              [i for i, r in enumerate(reqs)
               if not nf.request_ids or r.request_id in nf.request_ids])
      iters_live = None
      if key.kind == "closure":
        # record measured convergence counts the moment the fixpoint has
        # run — BEFORE validation/splitting/fulfilling, so a batch that
        # fails later in this attempt still feeds the estimator what the
        # device actually measured.  Live slots only (padded slots are
        # inert), and only rids not observed by an earlier attempt — a re-executed fixpoint measures
        # the same convergence and must not double-feed the EWMA.
        iters_live = np.asarray(out[1])[:len(reqs)]
        fresh = [i for i, r in enumerate(reqs)
                 if r.request_id not in observed]
        if fresh:
          self.estimator.observe_iterations(key, iters_live[fresh])
          observed.update(reqs[i].request_id for i in fresh)
      if self.validate_results:
        bad = batching.validate_finite(key, out, len(reqs))
        if bad:
          # garbage must fail the batch, not reach callers: NaN means the
          # kernel arm misbehaved (±inf is legitimate tropical output)
          raise NonFiniteResultError(label, bad)
      phase = "split"
      results = batching.split_results(key, reqs, out)
      if len(results) != len(reqs):
        # a short/long result list would silently wedge the unzipped
        # futures forever; fail the batch loudly instead
        raise RuntimeError(
            f"split_results returned {len(results)} results for "
            f"{len(reqs)} requests in {label}")
    except Exception as e:  # noqa: BLE001 — classify, count, feed the breaker
      self._arm_failed(key, arm, label, e, phase)
      raise
    completed_s = self._clock()
    self._arm_ok(key, arm, label)
    # live service-latency feedback: the same signal that fills the metrics
    # windows (minus compile time — see executed_s above), normalized per
    # live request: inert padding slots leave their fixpoint at once (and
    # under dp run on other chips), so they do not dilute a request's cost.
    # Keyed by the arm that ACTUALLY executed — which the breaker may have
    # re-dispatched to 'local' — so a dp cell never averages in local-path
    # latencies and a fallback arm's cell prices itself.
    self.estimator.observe_batch(key, backend, schedule, len(reqs),
                                 completed_s - executed_s)
    info = {"start_s": attempt_s, "stacked_s": stacked_s,
            "executed_s": executed_s, "device_s": device_s,
            "dispatched_s": dispatched[0], "fetched_s": fetched_s,
            "completed_s": completed_s, "rb": rb, "h2d_bytes": h2d_bytes,
            "zero_copy": zero_copy,
            "cache_hit": cache_hit, "backend": backend,
            "schedule": schedule, "iters_live": iters_live}
    return results, info

  def _complete_sub(self, key, reqs, results, info, scheduled_s: float,
                    *, emit_pick: bool) -> int:
    """Complete one successful sub-batch attempt: trace emission, launch
    accounting, and the once-per-request final accounting.  ``scheduled_s``
    stays the ORIGINAL batch pick time — queue/service windows and request
    records measure what the caller experienced (service includes retry
    time), while the batch phase spans use the attempt's own timestamps."""
    label = bucket_label(key)
    completed_s = info["completed_s"]
    schedule, rb = info["schedule"], info["rb"]
    if self.tracer.enabled:
      sharding = None if schedule == "local" else {
          "schedule": schedule, "rb": rb, "live": len(reqs),
          "chips_live": self.placement.chips_live(schedule, len(reqs), rb)}
      # one call carries the whole attempt's event set (phase spans and
      # their children, member picks + dones) so the steady-state tracing
      # cost is one lock acquisition per batch, not per request
      self.tracer.batch_complete(
          label=label, scheduled_s=info["start_s"],
          stacked_s=info["stacked_s"], executed_s=info["executed_s"],
          device_s=info["device_s"], completed_s=completed_s,
          backend=info["backend"], schedule=schedule,
          batch=len(reqs), padded=rb,
          h2d_bytes=info["h2d_bytes"], zero_copy=info["zero_copy"],
          cache_hit=info["cache_hit"],
          request_ids=[r.request_id for r in reqs],
          arrivals_s=[r.arrival_s for r in reqs],
          iterations=info["iters_live"], emit_pick=emit_pick,
          dispatched_s=info["dispatched_s"], fetched_s=info["fetched_s"],
          sharding=sharding)
    with self._lock:
      self._count_launch_locked(
          key, label, info["backend"], schedule,
          host_s=((info["stacked_s"] - info["start_s"])
                  + (completed_s - info["device_s"])),
          device_s=info["device_s"] - info["executed_s"],
          h2d_bytes=info["h2d_bytes"])
      if schedule == "dp":
        self._dp_live += len(reqs)
        self._dp_inert += rb - len(reqs)
      if (key.kind == "closure" and not self.placement.megakernel_serves(key)
          and (self.mode == "arena" or self.placement.backend == "megakernel")):
        self._over_cap += len(reqs)
      return self._complete(key, [(r, res, scheduled_s, completed_s, len(reqs))
                                  for r, res in zip(reqs, results)])

  def run_until_idle(self) -> int:
    """Drain the queue synchronously; returns total requests completed."""
    total = 0
    while True:
      done = self.step()
      with self._lock:
        drained = (len(self.scheduler) == 0
                   and not self._arena_live_locked())
      if done == 0 and drained:
        return total
      total += done

  def _check_dropped(self, fut: MMOFuture):
    """Raise if the scheduler lost this request: still pending, but neither
    queued (scheduler fully drained) nor inside an executing batch.  Pop +
    fulfill and pick + mark-inflight are each atomic under the engine lock,
    so this three-way state read is consistent — a positive is a real
    engine bug, never a request merely waiting behind other buckets."""
    rid = fut.request.request_id
    with self._lock:
      dropped = (rid in self._pending and rid not in self._inflight
                 and len(self.scheduler) == 0)
    if dropped:
      raise RuntimeError(
          f"request {rid} ({fut.request.kind}/{fut.request.op}) was "
          f"dropped: the queue drained without completing it — engine bug")

  def _drive(self, fut: MMOFuture, timeout: Optional[float]):
    """Future.result() plumbing: wait on the loop, or step synchronously."""
    deadline = None if timeout is None else time.perf_counter() + timeout
    while (self._thread is not None and self._thread.is_alive()
           and not fut.done()):
      # bounded waits, re-checking for a scheduler-lost request each lap —
      # result(timeout=None) must surface the engine bug as a RuntimeError,
      # not block forever on an event nobody will ever set
      self._check_dropped(fut)
      if deadline is not None and time.perf_counter() > deadline:
        return
      wait = 0.05 if deadline is None else max(
          0.0, min(0.05, deadline - time.perf_counter()))
      if fut._event.wait(wait):
        return
    # no background loop (or it died mid-wait): step synchronously
    while not fut.done():
      if deadline is not None and time.perf_counter() > deadline:
        return
      if self.step() == 0 and not fut.done():
        self._check_dropped(fut)
        # another thread's step() holds (or just finished) this request's
        # batch, or its bucket sits behind one that just failed — wait for
        # the completion event, then loop back into step()
        wait = 0.005 if deadline is None else max(
            0.0, min(0.005, deadline - time.perf_counter()))
        fut._event.wait(wait)

  # -- live metrics ----------------------------------------------------------

  def metrics_snapshot(self) -> dict:
    """Point-in-time QoS view (rolling-window per-bucket p50/p99 queue +
    service latency, counters, queue depth, admission state).  Safe to call
    from any thread while the background loop is serving — it reads the
    gauges under the engine lock for one moment, then aggregates outside the
    serving path."""
    with self._lock:
      depth = len(self.scheduler)
      executing = len(self._inflight)
      adm = self.admission.snapshot()
    return self.metrics.snapshot(queue_depth=depth, executing=executing,
                                 admission=adm,
                                 estimator=self.estimator.snapshot())

  def observability_state(self) -> dict:
    """Everything the Prometheus renderer (serve_mmo/exposition.py) emits,
    in one point-in-time document: metrics counters + histogram state,
    queue/executing gauges, admission + cache + scheduler counters, the
    estimator's cells with their drift against the static cost model
    (measured EWMA / static prediction — the model-vs-reality gauge), and
    flight-recorder stats.  Gauges are read under the engine lock; the
    per-cell drift math runs outside it."""
    with self._lock:
      depth = len(self.scheduler)
      executing = len(self._inflight)
      adm = self.admission.snapshot()
      sched = {"picks": self.scheduler.picks,
               "pick_seconds": self.scheduler.pick_seconds}
    cells = []
    for key, backend, schedule, seconds, count in self.estimator.cells_raw():
      contraction_s, trips = self._static_point(key)
      static_s = contraction_s * trips
      cells.append({
          "bucket": bucket_label(key), "backend": backend,
          "schedule": schedule, "seconds": seconds, "observations": count,
          "drift": (seconds / static_s) if static_s > 0.0 else None,
      })
    return {
        "metrics": self.metrics.exposition_state(),
        "queue_depth": depth,
        "executing": executing,
        "admission": adm,
        "cache": self.cache.stats(),
        "scheduler": sched,
        "estimator_cells": cells,
        "breakers": self.resilience.snapshot(),
        "trace": self.tracer.stats(),
    }

  def export_trace(self) -> dict:
    """The flight recorder's Chrome trace-event JSON (load in Perfetto or
    about://tracing) — per-request lifecycle spans plus per-batch
    host/device phase breakdown.  See serve_mmo/observability.py."""
    return self.tracer.export()

  def prewarm(self, sample_reqs) -> int:
    """Compile every (bucket, padded batch) executable the sample's buckets
    can produce, without executing anything.  Returns #programs compiled.
    After ``prewarm``, traffic confined to those buckets causes zero
    recompiles."""
    with self._lock:  # scheduler config is engine-lock guarded state
      max_batch = self.scheduler.max_batch
    seen = {request_bucket(req) for req in sample_reqs}
    before = self.cache.misses
    for key in seen:
      if self._arena_serves(key):
        # arena buckets compile their three slot programs instead of the
        # pow2 batch ladder — after this, admissions/ticks/evictions replay
        # stored executables (the zero-retrace guarantee test_arena pins)
        with self._lock:
          arena = self._arena_for_locked(key)
        arena.prewarm()
        continue
      backend, block, schedule = self.placement.resolve(key)
      sizes = {self.placement.padded_batch(min(1 << i, max_batch), schedule)
               for i in range(max_batch.bit_length() + 1)}
      for rb in sorted(sizes):
        self.placement.program(key, rb, backend, block, schedule,
                               batching.abstract_batch(key, rb))
    return self.cache.misses - before

  # -- background serving loop -----------------------------------------------

  def start(self):
    """Spawn the background serving thread (idempotent; re-arms submit
    after a stop()).  With tracing on, garbage collections are recorded as
    ``gc_pause`` spans until ``stop()``."""
    with self._lock:
      self._stopped = False
      if self._running:
        return
      self._running = True
    self.tracer.watch_gc()
    self._thread = threading.Thread(target=self._loop, name="mmo-serve",
                                    daemon=True)
    self._thread.start()

  def stop(self, *, drain: bool = True):
    """Stop the loop; with ``drain`` finish everything queued first (if the
    loop is not running, drain synchronously instead of spinning).  Stopped
    is a terminal accepting state: later ``submit`` calls raise until
    ``start()`` is called again (pinned in tests/test_serve_mmo.py)."""
    with self._lock:
      self._stopped = True
    if drain:
      if self._thread is not None and self._thread.is_alive():
        # step() notifies _idle the moment _pending empties, so drain wakes
        # immediately and burns no CPU; the timeout is only a liveness
        # backstop should the serving thread die without notifying.
        with self._idle:
          while self._pending and self._thread.is_alive():
            self._idle.wait(timeout=0.5)
      else:
        self.run_until_idle()
    with self._work:
      self._running = False
      self._work.notify_all()
    if self._thread is not None:
      self._thread.join()
      self._thread = None
    self.tracer.unwatch_gc()

  def _loop(self):
    while True:
      with self._work:
        t0 = None  # set when the loop blocks for want of work
        while (self._running and len(self.scheduler) == 0
               and not self._arena_live_locked()):
          if t0 is None:
            t0 = self._clock()
          self._work.wait()
        if not self._running:
          return
        t1 = self._clock() if t0 is not None else None
      if t0 is not None and self.tracer.enabled:
        self.tracer.span("loop_wait", cat="engine", t0_s=t0, t1_s=t1)
      self.step()

  # -- stats -----------------------------------------------------------------

  def stats(self) -> EngineStats:
    with self._lock:
      recs = list(self._records)
      batches = self._batches
      rejected, expired = self._rejected, self._expired
      over_cap, arms = self._over_cap, dict(self._arms)
      dp_live, dp_inert = self._dp_live, self._dp_inert
    lat = np.asarray([r.latency_s for r in recs], dtype=np.float64)
    return EngineStats(
        completed=len(recs),
        batches=batches,
        mean_batch=(len(recs) / batches) if batches else 0.0,
        latencies_s=lat,
        cache=self.cache.stats(),
        rejected=rejected,
        expired=expired,
        over_cap=over_cap,
        arms=arms,
        dp_live_slots=dp_live,
        dp_inert_slots=dp_inert,
    )

  def reset_stats(self):
    with self._lock:
      self._records.clear()
      self._batches = 0
      self._rejected = 0
      self._expired = 0
      self._over_cap = 0
      self._arms.clear()
      self._dp_live = 0
      self._dp_inert = 0
