"""MMO serving engine — shape-bucketed continuous batching for semiring
workloads.

The paper's eight SIMD² applications are all *small-matrix, high-rate*
problems (a routing query, a KNN lookup, a reachability probe), which makes
them serving workloads, not one-shot library calls.  This package turns the
``core.mmo`` / ``core.closure`` stack into a request-driven service:

  api.py        — problem requests (apsp / knn / reachability / raw mmo)
                  with QoS fields (tenant, priority, deadline_s) and result
                  futures with rejected/expired terminal states,
  scheduler.py  — request queue bucketed by (kind, op, padded shape, dtype,
                  static params); bucket picking delegates to a policy,
  policy.py     — scheduling policies: FIFO (default), deadline-aware
                  (earliest feasible deadline, priority tiers, fail-fast),
                  fair share (weighted round-robin across tenants),
  admission.py  — admission control: bounded queue depth, per-tenant
                  in-flight quotas, predicted-backlog-seconds rejection,
  metrics.py    — lock-cheap rolling-window metrics (per-bucket p50/p99
                  queue + service latency), snapshotable mid-run,
  estimator.py  — adaptive QoS: per-(bucket, backend, schedule) EWMA over
                  measured batch latencies + measured closure convergence
                  counts; corrects the cost-table predictions that drive
                  deadline feasibility, backlog admission, and the
                  service-time batch cap (``adaptive=True``),
  batching.py   — pad-and-stack micro-batcher: one compiled program per
                  bucket executes a whole request batch (per-request
                  convergence masks for closures),
  cache.py      — AOT executable cache keyed by (bucket, batch, backend) so
                  steady-state traffic never retraces,
  placement.py  — per-bucket placement: backend, mesh schedule, padded
                  batch size, breaker fallback arms, the one compile path,
  arena.py      — device-resident request arena: slot-based continuous
                  batching for closure fixpoints (``mode="arena"``) —
                  admit/tick/evict slot lifecycle, bit-identical to the
                  batch path,
  engine.py     — the engine: submit()/futures, synchronous step() or a
                  background serving loop, per-request latency stats, and
                  the batch-recovery driver (bounded retries, bisection,
                  watchdog, result validation),
  faults.py     — deterministic, seedable fault injection (compile /
                  execute / nonfinite / slow points; persistent, transient
                  and seeded-rate schedules) threaded through engine hooks,
  resilience.py — per-(bucket, backend, schedule) circuit breakers with
                  cost-ranked fallback arms and half-open probe recovery,
  observability.py — request-lifecycle tracer: a bounded ring-buffer flight
                  recorder of per-request/per-batch spans, exportable as
                  Chrome trace-event JSON (Perfetto / about://tracing),
  exposition.py — dependency-free Prometheus text exposition over the
                  engine's counters, log-bucketed latency histograms,
                  gauges, and estimator-vs-static drift,
  httpd.py      — stdlib HTTP endpoint serving /metrics /healthz /snapshot
                  /trace next to a live engine (``--http-port`` in
                  launch/serve_mmo.py).

Quickstart::

    from repro.serve_mmo import MMOEngine, apsp_request, knn_request

    eng = MMOEngine(backend="xla", max_batch=8,
                    policy="deadline", max_queue=1024)
    futs = [eng.submit(apsp_request(w, deadline_s=0.2))
            for w in weight_matrices]
    eng.run_until_idle()
    dist = futs[0].result().value
    print(eng.metrics_snapshot())
"""
from repro.serve_mmo.admission import AdmissionController
from repro.serve_mmo.api import (DeadlineExceededError, MMOFuture, MMOResult,
                                 ProblemRequest, RejectedError, apsp_request,
                                 closure_request, knn_request, mmo_request,
                                 reachability_request)
from repro.serve_mmo.arena import Eviction, RequestArena
from repro.serve_mmo.cache import ExecutableCache
from repro.serve_mmo.engine import EngineStats, MMOEngine
from repro.serve_mmo.estimator import Estimate, ServiceEstimator
from repro.serve_mmo.faults import (BatchTimeoutError, FaultInjector,
                                    FaultRule, InjectedFault,
                                    NonFiniteResultError, parse_fault_spec)
from repro.serve_mmo.exposition import LogHistogram, render_prometheus
from repro.serve_mmo.httpd import ObservabilityServer
from repro.serve_mmo.metrics import RollingWindow, ServeMetrics, bucket_label
from repro.serve_mmo.observability import FlightRecorder
from repro.serve_mmo.policy import (DeadlinePolicy, FairSharePolicy,
                                    FifoPolicy, SchedulingPolicy, make_policy)
from repro.serve_mmo.resilience import CircuitBreaker, ResilienceManager
from repro.serve_mmo.scheduler import BucketKey, BucketScheduler

__all__ = [
    "ProblemRequest",
    "MMOFuture",
    "MMOResult",
    "MMOEngine",
    "EngineStats",
    "RequestArena",
    "Eviction",
    "ExecutableCache",
    "BucketKey",
    "BucketScheduler",
    "SchedulingPolicy",
    "FifoPolicy",
    "DeadlinePolicy",
    "FairSharePolicy",
    "make_policy",
    "AdmissionController",
    "ServiceEstimator",
    "Estimate",
    "ServeMetrics",
    "RollingWindow",
    "bucket_label",
    "FlightRecorder",
    "ObservabilityServer",
    "LogHistogram",
    "render_prometheus",
    "FaultInjector",
    "FaultRule",
    "parse_fault_spec",
    "InjectedFault",
    "NonFiniteResultError",
    "BatchTimeoutError",
    "ResilienceManager",
    "CircuitBreaker",
    "RejectedError",
    "DeadlineExceededError",
    "mmo_request",
    "closure_request",
    "apsp_request",
    "reachability_request",
    "knn_request",
]
