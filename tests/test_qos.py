"""QoS scheduling subsystem: policies, admission control, deadlines, metrics."""
import threading
import time

import numpy as np
import pytest

from repro.apps import graphs
from repro.serve_mmo import (AdmissionController, DeadlineExceededError,
                             DeadlinePolicy, FairSharePolicy, MMOEngine,
                             RejectedError, RollingWindow, apsp_request,
                             make_policy, mmo_request)
from repro.serve_mmo.scheduler import BucketScheduler, request_bucket

from conftest import FakeClock

RNG = np.random.default_rng(0)


def _mmo(n, **qos):
  a = RNG.standard_normal((n, n)).astype(np.float32)
  b = RNG.standard_normal((n, n)).astype(np.float32)
  return mmo_request(a, b, op="mma", **qos)


# ---------------------------------------------------------------------------
# policies (scheduler-level)
# ---------------------------------------------------------------------------


def test_make_policy_rejects_unknown():
  with pytest.raises(ValueError, match="unknown policy"):
    make_policy("lifo")
  p = DeadlinePolicy()
  assert make_policy(p) is p


def test_deadline_policy_prefers_deadline_bucket_over_older_bulk():
  """A younger bucket whose head carries a deadline preempts an older
  no-deadline bulk bucket — the whole point of the policy."""
  sched = BucketScheduler(policy="deadline", max_batch=4)
  bulk = [apsp_request(graphs.weighted_digraph(12, 0.3, seed=i))
          for i in range(3)]
  for r in bulk:
    sched.add(r)
  urgent = _mmo(12, deadline_s=10.0)
  sched.add(urgent)
  key, batch = sched.next_batch()
  assert batch == [urgent]
  _, batch2 = sched.next_batch()
  assert batch2 == bulk  # then the bulk bucket, FIFO within


def test_deadline_policy_priority_tiers_break_ties():
  """Among no-deadline requests, a higher priority tier serves first even
  though it arrived later."""
  sched = BucketScheduler(policy="deadline", max_batch=4)
  low = apsp_request(graphs.weighted_digraph(12, 0.3, seed=0))
  high = _mmo(12, priority=5)
  sched.add(low)
  sched.add(high)
  _, batch = sched.next_batch()
  assert batch == [high]
  _, batch2 = sched.next_batch()
  assert batch2 == [low]


def test_deadline_policy_orders_by_deadline_within_bucket():
  clock = FakeClock()
  sched = BucketScheduler(policy="deadline", max_batch=1, clock=clock)
  late = _mmo(12, deadline_s=50.0)
  soon = _mmo(12, deadline_s=5.0)
  sched.add(late)
  sched.add(soon)  # same bucket, tighter deadline → must jump the queue
  assert sched.next_batch(now=0.0)[1] == [soon]
  assert sched.next_batch(now=0.0)[1] == [late]


def test_deadline_policy_fails_fast_hopeless_requests():
  """A head whose deadline cannot be met even if served right now is
  diverted to the expired channel, never into a batch."""
  clock = FakeClock()
  sched = BucketScheduler(policy="deadline", max_batch=4, clock=clock)
  sched.predict_seconds = lambda key: 100.0  # every batch predicts 100s
  hopeless = _mmo(12, deadline_s=1.0)
  fine = _mmo(12)  # no deadline — always feasible
  sched.add(hopeless)
  sched.add(fine)
  key, batch = sched.next_batch(now=0.0)
  assert batch == [fine]
  assert sched.take_expired() == [hopeless]
  assert len(sched) == 0


def test_already_expired_requests_diverted_under_fifo_too():
  """Deadline expiry is an engine-level guarantee, not a policy feature:
  even the FIFO scheduler refuses to batch a request whose deadline passed
  while it was queued."""
  clock = FakeClock()
  sched = BucketScheduler(policy="fifo", max_batch=4, clock=clock)
  doomed = _mmo(12, deadline_s=1.0)
  ok = _mmo(12)
  sched.add(doomed)
  sched.add(ok)
  clock.t = 2.0  # the deadline lapses in the queue
  key, batch = sched.next_batch()
  assert batch == [ok]
  assert sched.take_expired() == [doomed]


def test_fair_share_weighted_round_robin_across_tenants():
  """weight 2:1 → tenant a gets two picks per b pick while both have work;
  an idle tenant is skipped without burning the turn."""
  sched = BucketScheduler(policy=FairSharePolicy(weights={"a": 2, "b": 1}),
                          max_batch=1)
  for i in range(4):
    sched.add(_mmo(12, tenant="a"))
  for i in range(4):
    sched.add(_mmo(24, tenant="b"))  # distinct bucket per tenant
  order = []
  while True:
    picked = sched.next_batch()
    if picked is None:
      break
    order.append(picked[1][0].tenant)
  assert order == ["a", "a", "b", "a", "a", "b", "b", "b"]


def test_fair_share_batch_may_carry_other_tenants():
  """Tenants sharing a shape bucket ride each other's batches — batching is
  a shape property, and a free ride is not a fairness violation."""
  sched = BucketScheduler(policy="fair", max_batch=4)
  mine = _mmo(12, tenant="a")
  theirs = _mmo(12, tenant="b")
  sched.add(mine)
  sched.add(theirs)
  _, batch = sched.next_batch()
  assert batch == [mine, theirs]
  assert sched.next_batch() is None


def test_fair_share_refunds_turns_that_serve_the_tenant_nothing():
  """A tenant whose oldest entry sits behind >= max_batch other-tenant
  requests in a shared bucket keeps its turn (credit refunded) until a
  batch actually carries its work — the turn is for service, not for
  draining someone else's backlog."""
  sched = BucketScheduler(policy="fair", max_batch=2)
  for _ in range(4):
    sched.add(_mmo(12, tenant="a"))
  sched.add(_mmo(12, tenant="b"))   # same bucket, behind all of a's
  for _ in range(3):
    sched.add(_mmo(24, tenant="c"))  # its own bucket
  served = []
  while True:
    picked = sched.next_batch()
    if picked is None:
      break
    served.append([r.tenant for r in picked[1]])
  # b's turn at batch 2 served only a's work → refunded, b keeps the turn
  # and lands batch 3; without the refund c would cut in first
  assert served == [["a", "a"], ["a", "a"], ["b"], ["c", "c"], ["c"]]


def test_fair_share_drops_drained_tenants_from_the_ring():
  """Unbounded tenant churn must not accrete ring state: a drained tenant
  leaves _order/_queues entirely and re-registers on its next submit."""
  policy = FairSharePolicy()
  sched = BucketScheduler(policy=policy, max_batch=8)
  for i in range(5):
    sched.add(_mmo(12, tenant=f"user-{i}"))
  while sched.next_batch() is not None:
    pass
  assert sched.next_batch() is None
  assert policy._order == [] and policy._queues == {}
  sched.add(_mmo(12, tenant="user-3"))  # re-registers cleanly
  assert [r.tenant for r in sched.next_batch()[1]] == ["user-3"]


def test_fair_share_survives_externally_cleared_buckets():
  """Orphaned entries (bucket dict cleared without popping) must not
  livelock next_batch — the lost-request simulation the engine tests use."""
  sched = BucketScheduler(policy="fair", max_batch=2)
  sched.add(_mmo(12, tenant="a"))
  sched.add(_mmo(24, tenant="b"))
  sched._buckets.clear()
  assert sched.next_batch() is None and len(sched) == 0


def test_heap_pick_matches_linear_scan_reference():
  """The lazy-heap bucket picker must agree with the O(buckets) linear scan
  it replaced, across a random add/pick interleaving."""
  rng = np.random.default_rng(42)
  sched = BucketScheduler(policy="fifo", max_batch=2)

  def linear_reference():
    best_key, best_seq = None, None
    for key, q in sched._buckets.items():
      if q and (best_seq is None or q[0].seq < best_seq):
        best_key, best_seq = key, q[0].seq
    return best_key

  for _ in range(300):
    if rng.random() < 0.6 or len(sched) == 0:
      sched.add(_mmo(int(rng.integers(8, 80))))
    else:
      expect = linear_reference()
      key, _ = sched.next_batch()
      assert key == expect
  while len(sched):
    expect = linear_reference()
    key, _ = sched.next_batch()
    assert key == expect


# ---------------------------------------------------------------------------
# service-time batch cap (max_batch_seconds) — preemption across batches
# ---------------------------------------------------------------------------


def _bulk_sched(clock, max_batch_seconds, per_request_s=1.0, **kw):
  sched = BucketScheduler(policy="deadline", max_batch=8, clock=clock,
                          max_batch_seconds=max_batch_seconds, **kw)
  sched.predict_seconds = lambda key: per_request_s
  return sched


def test_batch_cap_inactive_without_deadline_traffic():
  """Pure-bulk workloads keep full batches: the cap only binds while
  deadline-tagged traffic is queued or recent."""
  clock = FakeClock()
  sched = _bulk_sched(clock, max_batch_seconds=2.0)
  for i in range(8):
    sched.add(_mmo(12))
  _, batch = sched.next_batch()
  assert len(batch) == 8


def test_batch_cap_bounds_bulk_batches_while_deadline_traffic_queued():
  """With deadline traffic queued, a bulk batch is bounded to
  ~max_batch_seconds of predicted work, floored to a power of two (the
  engine pads batches up to the next power of two and computes every
  slot, so un-floored caps would overshoot the budget they claim)."""
  clock = FakeClock()
  sched = _bulk_sched(clock, max_batch_seconds=3.0)  # 3s / 1s each → 3 → 2
  for i in range(8):
    sched.add(_mmo(12))
  sched.add(_mmo(24, deadline_s=60.0))  # deadline bucket, served first
  _, urgent_batch = sched.next_batch()
  assert [r.shape[0] for r in urgent_batch] == [24]
  _, bulk_batch = sched.next_batch()
  assert len(bulk_batch) == 2  # pow2 floor of 3
  # a sub-second budget still serves one request per batch, never zero
  sched2 = _bulk_sched(clock, max_batch_seconds=0.5)
  for i in range(4):
    sched2.add(_mmo(12))
  sched2.add(_mmo(24, deadline_s=60.0))
  sched2.next_batch()  # urgent
  _, bulk = sched2.next_batch()
  assert len(bulk) == 1


def test_batch_cap_recency_window_expires():
  """An ongoing deadline stream keeps bulk batches short *between* urgent
  arrivals; once the stream stops (no deadline-tagged submit within the
  lookback), bulk batching returns to full size."""
  clock = FakeClock()
  sched = _bulk_sched(clock, max_batch_seconds=2.0, deadline_lookback_s=1.0)
  sched.add(_mmo(24, deadline_s=60.0))
  sched.next_batch()  # drain the urgent bucket; none queued now
  for i in range(8):
    sched.add(_mmo(12))
  clock.t = 0.5  # within the lookback → still capped
  _, batch = sched.next_batch()
  assert len(batch) == 2
  clock.t = 2.0  # lookback expired → full batches again
  _, batch = sched.next_batch()
  assert len(batch) == 6


def test_batch_cap_survives_bad_predictions():
  """A predictor that answers 0 / inf / None must disable the cap, not
  divide by zero or cap everything to nothing."""
  clock = FakeClock()
  for bad in (lambda k: 0.0, lambda k: float("inf"), None):
    sched = BucketScheduler(policy="deadline", max_batch=4, clock=clock,
                            max_batch_seconds=1.0)
    sched.predict_seconds = bad
    sched.add(_mmo(24, deadline_s=60.0))
    sched.next_batch()
    for i in range(4):
      sched.add(_mmo(12))
    _, batch = sched.next_batch()
    assert len(batch) == 4


def test_preemption_deadline_met_with_cap_missed_without():
  """The ROADMAP scenario, end to end through the engine with an injectable
  clock (no real sleeps): an urgent request arriving mid-bulk-burst meets
  its deadline under service-time batch capping and misses it without.

  The cost table prices one bulk closure request at 1s (0.25s/contraction ×
  lg(16)=4 squarings); execution time is *simulated* by advancing the fake
  clock by the batch's predicted duration after each step.  Uncapped, the
  first bulk batch holds all 8 requests → the urgent arrival (deadline 3.0s
  absolute) next gets a pick at t=8 and expires.  Capped at 2s of predicted
  work, batches hold 2 requests → the urgent arrival is picked at t=2 and
  completes inside its budget."""
  from repro.tuning import CostTable
  table = CostTable(device="test")
  table.record("minplus", (16, 16, 16), "float32", "xla", (512,), 0.25)
  table.record("mma", (16, 16, 16), "float32", "xla", (512,), 0.01)

  def run(max_batch_seconds):
    clock = FakeClock()
    eng = MMOEngine(backend="xla", max_batch=8, policy="deadline",
                    cost_table=table, clock=clock,
                    max_batch_seconds=max_batch_seconds,
                    deadline_lookback_s=60.0)
    # an earlier urgent request establishes the deadline stream (the cap
    # protects the *next* arrival, which is not queued yet by definition)
    first = eng.submit(_mmo(12, deadline_s=10.0, priority=1))
    bulk = [eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=i),
                                    tenant="bulk")) for i in range(8)]
    assert eng.step() == 1 and first.state == "done"  # urgent bucket first
    # bulk batch begins at t=0; the urgent request arrives mid-execution
    served = eng.step()
    clock.t = 0.5
    urgent = eng.submit(_mmo(12, deadline_s=2.5, priority=1))
    clock.t = float(served) * 1.0  # the batch's simulated service time
    eng.step()  # first pick the urgent arrival can get
    eng.run_until_idle()
    assert all(f.state == "done" for f in bulk)
    return served, urgent

  served, urgent = run(max_batch_seconds=None)
  assert served == 8  # uncapped: the whole burst in one batch
  assert urgent.state == "expired"
  with pytest.raises(DeadlineExceededError):
    urgent.result()

  served, urgent = run(max_batch_seconds=2.0)
  assert served == 2  # capped: ~2s of predicted work per batch
  assert urgent.state == "done"
  assert urgent.result().value.shape == (12, 12)


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_max_queue_bounds_depth():
  eng = MMOEngine(backend="xla", max_batch=4, max_queue=4)
  futs = [eng.submit(_mmo(12)) for _ in range(10)]
  rejected = [f for f in futs if f.state == "rejected"]
  assert len(rejected) == 6 and len(eng.scheduler) == 4
  assert eng.admission.queued == 4
  for f in rejected:
    with pytest.raises(RejectedError, match="queue full"):
      f.result()
  assert eng.run_until_idle() == 4
  assert all(f.result().value.shape == (12, 12)
             for f in futs if f.state != "rejected")
  st = eng.stats()
  assert st.rejected == 6 and st.completed == 4
  # queue drained → admission slots free again
  assert eng.submit(_mmo(12)).state == "pending"


def test_admission_tenant_quota_in_flight():
  eng = MMOEngine(backend="xla", max_batch=4, tenant_quota={"noisy": 2})
  f1 = eng.submit(_mmo(12, tenant="noisy"))
  f2 = eng.submit(_mmo(12, tenant="noisy"))
  f3 = eng.submit(_mmo(12, tenant="noisy"))
  quiet = eng.submit(_mmo(12, tenant="quiet"))  # other tenants unaffected
  assert f3.state == "rejected" and quiet.state == "pending"
  with pytest.raises(RejectedError, match="over quota"):
    f3.result()
  eng.run_until_idle()
  assert f1.result().value.shape == (12, 12)
  # completions release the in-flight slots
  assert eng.submit(_mmo(12, tenant="noisy")).state == "pending"
  assert eng.admission.rejections == {"tenant_quota": 1}


def test_admission_predicted_backlog_seconds():
  """Backlog admission is denominated in predicted seconds of work from the
  cost table, not queue length: cheap requests fit where one expensive one
  would not, and closures are charged their worst-case trip count."""
  from repro.tuning import CostTable
  table = CostTable(device="test")
  table.record("mma", (16, 16, 16), "float32", "xla", (512,), 10.0)   # slow
  table.record("minplus", (16, 16, 16), "float32", "xla", (512,), 1e-4)
  eng = MMOEngine(backend="auto", max_batch=4, cost_table=table,
                  max_backlog_s=15.0)
  # per-request charge = measured 10s × 1 contraction → one fits, two do not
  f1 = eng.submit(_mmo(12))
  f2 = eng.submit(_mmo(12))
  assert f1.state == "pending" and f2.state == "rejected"
  with pytest.raises(RejectedError, match="predicted backlog"):
    f2.result()
  # cheap closure: 1e-4 × lg(16)=4 squarings — fits the remaining budget
  cheap = eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=0)))
  assert cheap.state == "pending"
  assert eng.admission.backlog_s == pytest.approx(10.0 + 4e-4, rel=1e-6)


def test_predict_request_seconds_fixed_backend_reads_table():
  """A fixed-backend engine must still price admission off the table's
  measured row for that backend, not the idealized roofline prior."""
  from repro.tuning import CostTable
  table = CostTable(device="test")
  table.record("mma", (16, 16, 16), "float32", "vector", (128,), 7.0)
  eng = MMOEngine(backend="vector", cost_table=table)
  key = request_bucket(_mmo(12))
  assert eng.predict_request_seconds(key) == pytest.approx(7.0)
  # and a closure bucket multiplies by the solver's worst-case trip count
  table.record("minplus", (16, 16, 16), "float32", "vector", (128,), 2.0)
  ck = request_bucket(apsp_request(graphs.weighted_digraph(12, 0.3, seed=0)))
  assert eng.predict_request_seconds(ck) == pytest.approx(2.0 * 4)  # lg(16)


def test_admission_controller_unbounded_admits_everything():
  adm = AdmissionController()
  assert adm.unbounded
  req = _mmo(12)
  assert adm.try_admit(req) is None
  adm.on_dequeue(req)
  adm.on_done(req)
  assert adm.queued == 0 and dict(adm.inflight) == {}


# ---------------------------------------------------------------------------
# deadline expiry through the engine (synthetic clock)
# ---------------------------------------------------------------------------


def test_engine_expires_queued_request_past_deadline():
  clock = FakeClock()
  eng = MMOEngine(backend="xla", max_batch=4, clock=clock)
  doomed = eng.submit(_mmo(12, deadline_s=1.0))
  ok = eng.submit(_mmo(12))
  clock.t = 5.0  # deadline lapses while queued
  eng.run_until_idle()
  assert doomed.state == "expired"
  with pytest.raises(DeadlineExceededError, match="missed its 1s deadline"):
    doomed.result()
  assert ok.result().value.shape == (12, 12)
  st = eng.stats()
  assert st.expired == 1 and st.completed == 1
  assert eng.pending() == 0 and eng.admission.queued == 0
  assert dict(eng.admission.inflight) == {}
  snap = eng.metrics_snapshot()
  assert snap["counters"]["expired"] == 1
  assert snap["counters"]["completed"] == 1


def test_engine_deadline_policy_fails_fast_infeasible():
  """With the deadline policy, a request whose deadline cannot be met (cost
  table predicts service longer than the remaining budget) fails fast even
  though the deadline has not lapsed yet."""
  from repro.tuning import CostTable
  table = CostTable(device="test")
  table.record("mma", (16, 16, 16), "float32", "xla", (512,), 100.0)
  clock = FakeClock()
  eng = MMOEngine(backend="auto", max_batch=4, policy="deadline",
                  cost_table=table, clock=clock)
  hopeless = eng.submit(_mmo(12, deadline_s=1.0))
  eng.run_until_idle()
  assert hopeless.state == "expired"
  with pytest.raises(DeadlineExceededError):
    hopeless.result()


def test_deadline_met_requests_execute_normally():
  eng = MMOEngine(backend="xla", max_batch=4, policy="deadline")
  fut = eng.submit(_mmo(12, deadline_s=600.0))
  eng.run_until_idle()
  assert fut.state == "done" and fut.result().value.shape == (12, 12)


# ---------------------------------------------------------------------------
# deadline policy beats FIFO under bulk interference (the BENCH_qos claim)
# ---------------------------------------------------------------------------


def _interference_p99(policy):
  """p99 latency of small deadline-tagged traffic submitted *behind* a burst
  of bulk closure work, per policy.  Both engines are prewarmed so compile
  time never pollutes the comparison."""
  eng = MMOEngine(backend="xla", max_batch=4, policy=policy)
  eng.prewarm([apsp_request(graphs.weighted_digraph(40, 0.3, seed=0)),
               _mmo(12)])
  bulk = [eng.submit(apsp_request(
      graphs.weighted_digraph(40 + (i % 3), 0.3, seed=i), tenant="bulk"))
      for i in range(12)]
  urgent = [eng.submit(_mmo(12, deadline_s=60.0, priority=1,
                            tenant="interactive")) for _ in range(8)]
  eng.run_until_idle()
  recs = {r.request_id: r for r in eng._records}
  lat = [recs[f.request.request_id].latency_s for f in urgent]
  assert all(f.state == "done" for f in bulk + urgent)
  return float(np.percentile(lat, 99))


def test_deadline_p99_at_least_2x_better_than_fifo_under_bulk():
  fifo = _interference_p99("fifo")
  deadline = _interference_p99("deadline")
  assert deadline * 2.0 <= fifo, (
      f"deadline-policy p99 {deadline * 1e3:.1f}ms not 2x better than "
      f"FIFO {fifo * 1e3:.1f}ms under bulk interference")


# ---------------------------------------------------------------------------
# live metrics
# ---------------------------------------------------------------------------


def test_rolling_window_percentiles_and_eviction():
  w = RollingWindow(size=4)
  assert w.percentile(50) is None  # empty window: None, never NaN
  for v in (1.0, 2.0, 3.0, 4.0, 100.0):  # 1.0 evicted by 100.0
    w.add(v)
  assert w.count == 5
  assert sorted(w.values()) == [2.0, 3.0, 4.0, 100.0]
  assert w.percentile(0) == 2.0
  assert w.percentile(100) == 100.0
  with pytest.raises(ValueError):
    RollingWindow(size=0)


def test_metrics_snapshot_midrun_under_background_loop():
  """The whole point of metrics.py: a consistent snapshot while the
  background loop is actively serving — no stop, no drain."""
  eng = MMOEngine(backend="xla", max_batch=4)
  eng.prewarm([apsp_request(graphs.weighted_digraph(12, 0.3, seed=0))])
  eng.start()
  try:
    futs = [eng.submit(apsp_request(
        graphs.weighted_digraph(10 + (i % 4), 0.3, seed=i)))
        for i in range(24)]
    mid = eng.metrics_snapshot()  # taken while the loop is mid-drain
    assert mid["counters"]["submitted"] == 24
    assert mid["counters"]["rejected"] == 0
    assert 0 <= mid["queue_depth"] <= 24
    assert mid["admission"]["queued"] == mid["queue_depth"]
    for f in futs:
      f.result(timeout=120)
  finally:
    eng.stop()
  done = eng.metrics_snapshot()
  assert done["counters"]["completed"] == 24 and done["queue_depth"] == 0
  (label,) = [k for k in done["buckets"] if k.startswith("closure/minplus")]
  b = done["buckets"][label]
  assert b["completed"] == 24
  assert b["service_ms"]["p50"] <= b["service_ms"]["p99"]
  assert b["queue_ms"]["p99"] >= 0.0


def test_metrics_snapshot_concurrent_with_serving_is_safe():
  """Hammer snapshot from a second thread while the loop serves: no
  exceptions, monotone counters."""
  eng = MMOEngine(backend="xla", max_batch=4)
  eng.prewarm([_mmo(12)])
  eng.start()
  seen, errs = [], []

  def poll():
    try:
      for _ in range(50):
        seen.append(eng.metrics_snapshot()["counters"]["completed"])
        time.sleep(0.002)
    except Exception as e:  # noqa: BLE001
      errs.append(e)

  t = threading.Thread(target=poll)
  t.start()
  futs = [eng.submit(_mmo(12)) for _ in range(32)]
  for f in futs:
    f.result(timeout=120)
  t.join()
  eng.stop()
  assert not errs
  assert seen == sorted(seen)  # completed counter never goes backwards


# ---------------------------------------------------------------------------
# end-to-end: policies through the engine produce correct results
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["fifo", "deadline", "fair"])
def test_engine_results_correct_under_every_policy(policy):
  from repro.apps import solvers
  eng = MMOEngine(backend="xla", max_batch=4, policy=policy)
  ws = {n: graphs.weighted_digraph(n, 0.3, seed=n) for n in (9, 11, 13)}
  futs = {n: eng.submit(apsp_request(w, tenant=f"t{n % 2}", deadline_s=600.0))
          for n, w in ws.items()}
  eng.run_until_idle()
  for n, w in ws.items():
    ref, _ = solvers.apsp(w)
    np.testing.assert_allclose(futs[n].result().value, np.asarray(ref),
                               atol=1e-5)


def test_engine_rejects_unknown_policy():
  with pytest.raises(ValueError, match="unknown policy"):
    MMOEngine(backend="xla", policy="lifo")


def test_request_bucket_ignores_qos_fields():
  """QoS fields must not fragment buckets: a tagged and an untagged request
  of the same shape share one executable."""
  w = graphs.weighted_digraph(12, 0.3, seed=0)
  assert (request_bucket(apsp_request(w))
          == request_bucket(apsp_request(w, tenant="x", priority=3,
                                         deadline_s=1.0)))
