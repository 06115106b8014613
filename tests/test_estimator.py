"""Adaptive service-time estimator: EWMA convergence, cold-start fallback,
iteration-count feedback, and thread safety under concurrent observe/predict."""
import threading

import numpy as np
import pytest

from repro.serve_mmo import Estimate, MMOEngine, ServiceEstimator, apsp_request
from repro.serve_mmo.scheduler import request_bucket
from repro.apps import graphs

from conftest import FakeClock

RNG = np.random.default_rng(0)


def _mmo_key(n=12):
  from repro.serve_mmo import mmo_request
  a = RNG.standard_normal((n, n)).astype(np.float32)
  return request_bucket(mmo_request(a, a, op="mma"))


def _closure_key(n=12):
  return request_bucket(apsp_request(graphs.weighted_digraph(n, 0.3, seed=0)))


# ---------------------------------------------------------------------------
# EWMA mechanics
# ---------------------------------------------------------------------------


def test_ewma_pins_exact_update_rule():
  """The decay is per-observation with alpha = 1 − 2^(−1/half_life); pin the
  arithmetic so a silent reformulation (time-based decay, different alpha)
  cannot slip in and shift every admission decision."""
  est = ServiceEstimator(half_life=1.0, min_observations=1)
  key = _mmo_key()
  est.observe_batch(key, "xla", "local", 1, 1.0)
  assert est.predict(key, "xla", "local", 99.0, 1.0).seconds == 1.0
  # half_life=1 → alpha = 0.5: each new reading moves halfway to the target
  est.observe_batch(key, "xla", "local", 1, 3.0)
  assert est.predict(key, "xla", "local", 99.0, 1.0).seconds == \
      pytest.approx(2.0)
  est.observe_batch(key, "xla", "local", 1, 3.0)
  assert est.predict(key, "xla", "local", 99.0, 1.0).seconds == \
      pytest.approx(2.5)


def test_ewma_converges_to_shifted_load_within_half_lives():
  """After a load shift, the estimate crosses within 10% of the new level in
  ~4 half-lives of observations — the property that makes predictions track
  the device instead of the cold-start prior forever."""
  est = ServiceEstimator(half_life=8.0, min_observations=1)
  key = _mmo_key()
  for _ in range(50):
    est.observe_batch(key, "xla", "local", 1, 0.001)  # unloaded device
  for _ in range(32):  # 4 half-lives at the loaded level
    est.observe_batch(key, "xla", "local", 1, 0.1)    # device now loaded
  got = est.predict(key, "xla", "local", 1e-6, 1.0).seconds
  assert got == pytest.approx(0.1, rel=0.10)
  # and the old level no longer dominates
  assert got > 0.05


def test_observations_normalized_per_padded_slot():
  """A batch's seconds are divided by the live requests it served (its
  padding slots are inert): per-request cost, the unit every consumer
  (admission backlog, deadline feasibility, batch cap) is denominated in."""
  est = ServiceEstimator(min_observations=1)
  key = _mmo_key()
  est.observe_batch(key, "xla", "local", 8, 0.8)
  assert est.predict(key, "xla", "local", 9.9, 1.0) == Estimate(0.1, "ewma")


def test_bogus_observations_are_dropped():
  est = ServiceEstimator(min_observations=1)
  key = _mmo_key()
  est.observe_batch(key, "xla", "local", 0, 1.0)           # zero slots
  est.observe_batch(key, "xla", "local", 1, float("nan"))  # NaN seconds
  est.observe_batch(key, "xla", "local", 1, float("inf"))
  assert est.observations(key, "xla", "local") == 0
  assert est.predict(key, "xla", "local", 7.0, 1.0) == Estimate(7.0, "static")


def test_constructor_validation():
  with pytest.raises(ValueError, match="half_life"):
    ServiceEstimator(half_life=0.0)
  with pytest.raises(ValueError, match="min_observations"):
    ServiceEstimator(min_observations=0)


# ---------------------------------------------------------------------------
# cold start + precedence
# ---------------------------------------------------------------------------


def test_cold_start_falls_back_to_static_prior():
  """Below min_observations the static prediction answers verbatim — one
  outlier first batch must not steer admission."""
  est = ServiceEstimator(min_observations=3)
  key = _mmo_key()
  assert est.predict(key, "xla", "local", 2.0, 3.0) == Estimate(6.0, "static")
  est.observe_batch(key, "xla", "local", 1, 100.0)
  est.observe_batch(key, "xla", "local", 1, 100.0)
  assert est.predict(key, "xla", "local", 2.0, 3.0).source == "static"
  est.observe_batch(key, "xla", "local", 1, 100.0)  # third reading → warm
  got = est.predict(key, "xla", "local", 2.0, 3.0)
  assert got.source == "ewma" and got.seconds == pytest.approx(100.0)


def test_cells_keyed_by_backend_and_schedule():
  """A bucket re-routed to another backend must not inherit the old route's
  latency readings; schedules keep separate cells (dp and local latencies
  are never averaged), but a cold *distributed* cell falls back to the
  bucket's measured local cell — per-batch placement can downgrade dp
  batches to 'local' (rb not divisible over the mesh), and measured local
  latency beats the static prior for a bucket that is mostly executing
  locally anyway."""
  est = ServiceEstimator(min_observations=1)
  key = _mmo_key()
  est.observe_batch(key, "pallas", "local", 1, 5.0)
  assert est.predict(key, "pallas", "local", 1.0, 1.0).source == "ewma"
  assert est.predict(key, "xla", "local", 1.0, 1.0).source == "static"
  # cold dp cell → the local cell answers ...
  assert est.predict(key, "pallas", "dp", 1.0, 1.0) == Estimate(5.0, "ewma")
  # ... until the dp cell itself warms, which then takes precedence
  est.observe_batch(key, "pallas", "dp", 1, 2.0)
  assert est.predict(key, "pallas", "dp", 1.0, 1.0) == Estimate(2.0, "ewma")
  # the fallback is one-way: 'local' never reads a distributed cell
  est2 = ServiceEstimator(min_observations=1)
  est2.observe_batch(key, "xla", "dp", 1, 2.0)
  assert est2.predict(key, "xla", "local", 1.0, 1.0).source == "static"


def test_measured_iterations_replace_worst_case_trip_count():
  """Closure cold start: with measured convergence counts but no warm
  seconds cell, the prediction is static per-contraction cost × the
  measured iteration EWMA, clamped to [1, worst_trips]."""
  est = ServiceEstimator(min_observations=3)
  key = _closure_key()
  # worst case for an nb=16 Leyzorek bucket is lg(16) = 4 squarings; the
  # traffic actually converges in 2
  est.observe_iterations(key, [2, 2, 2])
  assert est.iteration_estimate(key, 4.0) == pytest.approx(2.0)
  got = est.predict(key, "xla", "local", 1.0, 4.0)
  assert got.source == "iterations" and got.seconds == pytest.approx(2.0)
  # a noise reading above the worst case clamps to the bound
  est2 = ServiceEstimator()
  est2.observe_iterations(key, [9.0])
  assert est2.iteration_estimate(key, 4.0) == 4.0
  # and below 1 clamps up (a fixpoint runs at least one contraction)
  est3 = ServiceEstimator()
  est3.observe_iterations(key, [0.0])
  assert est3.iteration_estimate(key, 4.0) == 1.0


def test_warm_ewma_beats_iterations_beats_static():
  est = ServiceEstimator(min_observations=1)
  key = _closure_key()
  assert est.predict(key, "xla", "local", 1.0, 4.0).source == "static"
  est.observe_iterations(key, [2])
  assert est.predict(key, "xla", "local", 1.0, 4.0).source == "iterations"
  est.observe_batch(key, "xla", "local", 1, 0.5)
  got = est.predict(key, "xla", "local", 1.0, 4.0)
  assert got == Estimate(0.5, "ewma")


def test_snapshot_is_jsonable_and_labeled():
  import json
  est = ServiceEstimator()
  est.observe_batch(_mmo_key(), "xla", "local", 2, 0.2)
  est.observe_iterations(_closure_key(), [3])
  snap = est.snapshot()
  json.dumps(snap)  # must not raise
  (cell_label,) = snap["cells"]
  assert cell_label.endswith("|xla|local")
  assert snap["cells"][cell_label] == {"seconds": 0.1, "observations": 1}
  (it_label,) = snap["iterations"]
  assert it_label.startswith("closure/minplus")


# ---------------------------------------------------------------------------
# thread safety: observe on the serving loop, predict on submit threads
# ---------------------------------------------------------------------------


def test_concurrent_observe_predict_is_safe():
  """Hammer observe/observe_iterations/predict/snapshot from 8 threads: no
  exceptions, counts exact, and the final estimate sits inside the observed
  value range (no torn float reads)."""
  est = ServiceEstimator(half_life=4.0, min_observations=1)
  keys = [_mmo_key(), _closure_key()]
  errs, n_per_thread = [], 200
  barrier = threading.Barrier(8)

  def writer(i):
    try:
      barrier.wait()
      for j in range(n_per_thread):
        est.observe_batch(keys[0], "xla", "local", 1, 0.01 + 0.01 * (j % 3))
        est.observe_iterations(keys[1], [1 + (j % 4)])
    except Exception as e:  # noqa: BLE001
      errs.append(e)

  def reader(i):
    try:
      barrier.wait()
      for _ in range(n_per_thread):
        got = est.predict(keys[0], "xla", "local", 1.0, 1.0)
        assert got.seconds >= 0.0
        est.snapshot()
        est.iteration_estimate(keys[1], 8.0)
    except Exception as e:  # noqa: BLE001
      errs.append(e)

  threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
  threads += [threading.Thread(target=reader, args=(i,)) for i in range(4)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  assert not errs
  assert est.observations(keys[0], "xla", "local") == 4 * n_per_thread
  final = est.predict(keys[0], "xla", "local", 1.0, 1.0)
  assert final.source == "ewma" and 0.01 <= final.seconds <= 0.03
  assert 1.0 <= est.iteration_estimate(keys[1], 8.0) <= 4.0


# ---------------------------------------------------------------------------
# engine integration: live feedback corrects static predictions
# ---------------------------------------------------------------------------


def test_adaptive_engine_corrects_wrong_static_prediction():
  """A cost table that is wildly wrong (measured row says 100s for a
  millisecond bucket) poisons static predictions; after serving a few
  batches the adaptive engine's prediction collapses to measured reality.
  The non-adaptive engine keeps trusting the table — the drift this PR
  exists to close."""
  from repro.tuning import CostTable
  table = CostTable(device="test")
  table.record("mma", (16, 16, 16), "float32", "xla", (512,), 100.0)

  def run(adaptive):
    eng = MMOEngine(backend="xla", max_batch=2, cost_table=table,
                    adaptive=adaptive)
    key = None
    for i in range(8):
      a = RNG.standard_normal((12, 12)).astype(np.float32)
      from repro.serve_mmo import mmo_request
      req = mmo_request(a, a, op="mma")
      key = key or request_bucket(req)
      eng.submit(req)
    eng.run_until_idle()
    return eng.predict_request(key)

  static = run(adaptive=False)
  assert static == Estimate(100.0, "static")
  live = run(adaptive=True)
  assert live.source == "ewma"
  assert live.seconds < 1.0  # a 12×12 mma batch is not 100 seconds


def test_estimator_observations_exclude_compile_time():
  """A cache-miss batch must not feed trace+compile latency into the EWMA
  as device service time: compile is orders of magnitude above steady
  service and carries ~84% of the cell's weight when min_observations is
  reached, which would expire feasible deadlines and collapse batch caps
  for the next ~half-life of batches."""
  clock = FakeClock()
  eng = MMOEngine(backend="xla", max_batch=2, clock=clock)
  real = eng.cache.get_or_compile

  def slow_compile(*a, **kw):
    clock.t += 100.0  # a compile hiding inside the first batch
    return real(*a, **kw)

  eng.cache.get_or_compile = slow_compile
  from repro.serve_mmo import mmo_request
  a = RNG.standard_normal((12, 12)).astype(np.float32)
  eng.submit(mmo_request(a, a, op="mma"))
  eng.run_until_idle()
  snap = eng.estimator.snapshot()
  (label,) = snap["cells"]
  # the fake clock only moved during "compilation" — observed service is 0
  assert snap["cells"][label] == {"seconds": 0.0, "observations": 1}


def test_adaptive_engine_uses_measured_closure_iterations_cold():
  """Before the seconds cell warms, a closure bucket's prediction uses the
  measured convergence EWMA instead of the worst-case trip count."""
  from repro.tuning import CostTable
  table = CostTable(device="test")
  table.record("minplus", (16, 16, 16), "float32", "xla", (512,), 2.0)
  eng = MMOEngine(backend="xla", max_batch=4, cost_table=table, adaptive=True,
                  estimator=ServiceEstimator(min_observations=100))
  # dense graph → tiny diameter → converges below the lg(16) worst case
  w = graphs.weighted_digraph(12, 0.9, seed=0)
  key = request_bucket(apsp_request(w))
  assert eng.predict_request(key) == Estimate(8.0, "static")  # 2.0 × lg(16)
  fut = eng.submit(apsp_request(w))
  eng.run_until_idle()
  measured_iters = fut.result().extras["iterations"]
  got = eng.predict_request(key)
  assert got.source == "iterations"
  assert got.seconds == pytest.approx(2.0 * min(max(measured_iters, 1), 4))
