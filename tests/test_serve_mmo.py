"""MMO serving engine: batched semiring execution, scheduler, cache, e2e."""
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro.apps import graphs, solvers
from repro.core import (batched_bellman_ford_closure, batched_leyzorek_closure,
                        bellman_ford_closure, leyzorek_closure, mmo_batched,
                        mmo_reference, pad_adjacency, prepare_adjacency)
from repro.serve_mmo import (MMOEngine, apsp_request, closure_request,
                             knn_request, mmo_request, reachability_request)
from repro.serve_mmo.scheduler import (BucketScheduler, bucket_dim,
                                       request_bucket)

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# batched semiring execution: vmapped mmo parity across backends, with C
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", ["mma", "minplus", "maxmin", "addnorm",
                                "maxmul", "orand"])
@pytest.mark.parametrize("backend", ["vector", "xla", "pallas"])
def test_mmo_batched_backend_parity(op, backend):
  r, m, k, n = 3, 7, 11, 5
  a = RNG.standard_normal((r, m, k)).astype(np.float32)
  b = RNG.standard_normal((r, k, n)).astype(np.float32)
  c = RNG.standard_normal((r, m, n)).astype(np.float32)
  if op == "orand":
    a, b, c = a > 0.3, b > 0.3, c > 0.8
  kw = {"interpret": True} if backend == "pallas" else {}
  got = mmo_batched(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c), op=op,
                    backend=backend, **kw)
  ref = mmo_reference(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c), op=op)
  np.testing.assert_allclose(np.asarray(got, np.float64),
                             np.asarray(ref, np.float64), atol=1e-4)


def test_mmo_batched_rejects_2d():
  a = jnp.zeros((3, 4))
  with pytest.raises(ValueError):
    mmo_batched(a, a)


def test_mmo_batched_rejects_2d_c():
  a = jnp.zeros((2, 3, 4))
  b = jnp.zeros((2, 4, 5))
  with pytest.raises(ValueError, match=r"\(R, M, N\) for c"):
    mmo_batched(a, b, jnp.zeros((3, 5)))
  with pytest.raises(ValueError, match="request-axis mismatch"):
    mmo_batched(a, b, jnp.zeros((3, 3, 5)))


@pytest.mark.parametrize("op", ["minplus", "maxmin", "orand"])
@pytest.mark.parametrize("algorithm", ["leyzorek", "bellman_ford"])
def test_ragged_masked_k_closure_matches_padded(op, algorithm):
  """valid_n work skipping changes which K-blocks execute, never the result
  (padded lanes are algebraic no-ops, converged requests are frozen)."""
  sizes = [6, 9, 13, 16]
  nb = 16
  if op == "orand":
    ws = [graphs.boolean_digraph(n, 0.15, seed=n) for n in sizes]
  elif op == "maxmin":
    ws = [graphs.capacity_graph(n, 0.3, seed=n) for n in sizes]
  else:
    ws = [graphs.weighted_digraph(n, 0.3, seed=n) for n in sizes]
  prepared = [prepare_adjacency(jnp.asarray(w), op=op) for w in ws]
  stack = jnp.stack([pad_adjacency(p, nb, op=op) for p in prepared])
  solver = (batched_leyzorek_closure if algorithm == "leyzorek"
            else batched_bellman_ford_closure)
  valid = jnp.asarray(sizes, jnp.int32)
  # small block_k so ragged skipping actually partitions the K axis
  def mmo_fn(a, b, c, op_, bk, k_valid=None):
    from repro.core.mmo import mmo
    return mmo(a, b, c, op=op_, backend=bk, block_k=4, k_valid=k_valid)

  padded, it_p = solver(stack, op=op, backend="xla", mmo_fn=mmo_fn)
  ragged, it_r = solver(stack, op=op, backend="xla", mmo_fn=mmo_fn,
                        valid_n=valid)
  np.testing.assert_allclose(np.asarray(ragged, np.float64),
                             np.asarray(padded, np.float64), atol=1e-5)
  np.testing.assert_array_equal(np.asarray(it_r), np.asarray(it_p))


@pytest.mark.parametrize("op", ["minplus", "maxmin", "orand"])
def test_batched_closure_matches_unbatched(op):
  """Padded (R, nb, nb) batched closure == per-request closure, and the
  per-request convergence mask reports sane iteration counts."""
  sizes = [6, 9, 13, 16]
  nb = 16
  if op == "orand":
    ws = [graphs.boolean_digraph(n, 0.15, seed=n) for n in sizes]
  elif op == "maxmin":
    ws = [graphs.capacity_graph(n, 0.3, seed=n) for n in sizes]
  else:
    ws = [graphs.weighted_digraph(n, 0.3, seed=n) for n in sizes]
  prepared = [prepare_adjacency(jnp.asarray(w), op=op) for w in ws]
  stack = jnp.stack([pad_adjacency(p, nb, op=op) for p in prepared])

  out, iters = batched_leyzorek_closure(stack, op=op)
  assert iters.shape == (len(sizes),)
  for i, (n, p) in enumerate(zip(sizes, prepared)):
    ref, ref_it = leyzorek_closure(p, op=op)
    np.testing.assert_allclose(np.asarray(out[i, :n, :n], np.float64),
                               np.asarray(ref, np.float64), atol=1e-5)
    assert int(iters[i]) >= int(ref_it)  # padded run can't converge sooner

  out_bf, _ = batched_bellman_ford_closure(stack, op=op)
  for i, (n, p) in enumerate(zip(sizes, prepared)):
    ref_bf, _ = bellman_ford_closure(p, op=op)
    np.testing.assert_allclose(np.asarray(out_bf[i, :n, :n], np.float64),
                               np.asarray(ref_bf, np.float64), atol=1e-5)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def test_bucket_dim():
  assert bucket_dim(1) == 8 and bucket_dim(8) == 8
  assert bucket_dim(9) == 16 and bucket_dim(16) == 16
  assert bucket_dim(100) == 128
  with pytest.raises(ValueError):
    bucket_dim(0)


def test_bucketing_determinism():
  """Equal-spec requests always map to the same bucket; different static
  params or dtypes split buckets."""
  w = graphs.weighted_digraph(11, 0.3, seed=1)
  k1 = request_bucket(apsp_request(w))
  k2 = request_bucket(apsp_request(graphs.weighted_digraph(13, 0.4, seed=9)))
  assert k1 == k2  # 11 and 13 both pad to 16, same ring/kind/dtype
  assert k1 != request_bucket(closure_request(w, op="minplus",
                                              algorithm="bellman_ford"))
  assert k1 != request_bucket(reachability_request(w > 5.0))  # bool / orand
  q, r = graphs.knn_points(20, 6, 4, seed=0)
  assert (request_bucket(knn_request(q[:6], r, k=3))
          != request_bucket(knn_request(q[:6], r, k=4)))  # k is static


def test_scheduler_fifo_within_bucket_and_oldest_bucket_first():
  sched = BucketScheduler(policy="fifo", max_batch=2)
  small = [apsp_request(graphs.weighted_digraph(10, 0.3, seed=i))
           for i in range(3)]
  big = apsp_request(graphs.weighted_digraph(40, 0.3, seed=7))
  sched.add(small[0])
  sched.add(small[1])
  sched.add(big)
  sched.add(small[2])
  key1, batch1 = sched.next_batch()
  assert [r is s for r, s in zip(batch1, small[:2])] == [True, True]  # FIFO
  key2, batch2 = sched.next_batch()
  assert batch2 == [big]  # big arrived before small[2] → its bucket goes next
  _, batch3 = sched.next_batch()
  assert batch3 == [small[2]]
  assert sched.next_batch() is None and len(sched) == 0


def test_engine_completion_order_fifo():
  eng = MMOEngine(backend="xla", max_batch=2)
  ws = [graphs.weighted_digraph(12, 0.3, seed=i) for i in range(5)]
  futs = [eng.submit(apsp_request(w)) for w in ws]
  eng.run_until_idle()
  order = [r.request_id for r in eng._records]
  assert order == sorted(order)  # same-bucket completion order == submit order


# ---------------------------------------------------------------------------
# padding correctness through the full engine path (odd shapes, all kinds)
# ---------------------------------------------------------------------------


def test_engine_padding_correctness_mixed():
  eng = MMOEngine(backend="xla", max_batch=4)
  futs = {}

  ws = {n: graphs.weighted_digraph(n, 0.3, seed=n) for n in (9, 11, 13, 17)}
  for n, w in ws.items():
    futs[("apsp", n)] = eng.submit(apsp_request(w))

  adj = graphs.boolean_digraph(10, 0.15, seed=5)
  futs["reach"] = eng.submit(reachability_request(adj))

  ref_pts, qry_pts = graphs.knn_points(21, 7, 5, seed=3)
  futs["knn"] = eng.submit(knn_request(qry_pts, ref_pts, k=4))

  a = RNG.standard_normal((5, 9)).astype(np.float32)
  b = RNG.standard_normal((9, 6)).astype(np.float32)
  c = RNG.standard_normal((5, 6)).astype(np.float32)
  futs["mmo"] = eng.submit(mmo_request(a, b, c, op="maxmin"))

  assert eng.run_until_idle() == len(futs)

  for n, w in ws.items():
    ref, _ = solvers.apsp(w)
    np.testing.assert_allclose(futs[("apsp", n)].result().value,
                               np.asarray(ref), atol=1e-5)
  ref, _ = solvers.gtc(adj)
  np.testing.assert_array_equal(futs["reach"].result().value, np.asarray(ref))
  d2, idx = solvers.knn(ref_pts, qry_pts, k=4)
  res = futs["knn"].result()
  np.testing.assert_allclose(res.value, np.asarray(d2), atol=1e-3)
  np.testing.assert_array_equal(res.extras["indices"], np.asarray(idx))
  ref = mmo_reference(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c),
                      op="maxmin")
  np.testing.assert_allclose(futs["mmo"].result().value, np.asarray(ref),
                             atol=1e-5)


def test_knn_large_coordinates_ignore_padded_rows():
  """Padded corpus rows are masked by the valid-row count, so results stay
  correct for data at any magnitude (no far-away sentinel to collide with)."""
  ref_pts, qry_pts = graphs.knn_points(21, 7, 5, seed=3)
  ref_pts = ref_pts + 1.0e6   # sit right where a magic pad point would
  qry_pts = qry_pts + 1.0e6
  eng = MMOEngine(backend="xla")
  res = eng.submit(knn_request(qry_pts, ref_pts, k=4)).result()
  assert res.extras["indices"].max() < 21  # never a padded row
  _, idx = solvers.knn(ref_pts, qry_pts, k=4)
  np.testing.assert_array_equal(res.extras["indices"], np.asarray(idx))


def test_stop_without_loop_drains_synchronously():
  eng = MMOEngine(backend="xla")
  fut = eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)))
  eng.stop()  # no background loop ever started — must not hang
  assert fut.done() and fut.result().value.shape == (10, 10)


def test_submit_after_stop_raises_cleanly():
  """Pinned decision: stop() is a terminal accepting state — submit raises
  a RuntimeError instead of queueing onto a loop nobody will run; start()
  re-arms the engine."""
  eng = MMOEngine(backend="xla")
  eng.start()
  eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)))
  eng.stop()
  with pytest.raises(RuntimeError, match="stopped engine"):
    eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=1)))
  eng.start()  # restart re-arms submission
  fut = eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=2)))
  eng.stop()
  assert fut.result().value.shape == (10, 10)


def test_stats_summary_on_idle_engine_does_not_crash():
  """EngineStats.summary() on an engine that served zero requests must stay
  printable (no division by zero, no empty-percentile blowup) and must
  carry the rejected/expired counters."""
  eng = MMOEngine(backend="xla")
  st = eng.stats()
  s = st.summary()
  assert "completed=0" in s and "p50=n/a" in s
  assert "rejected=0" in s and "expired=0" in s
  assert st.mean_batch == 0.0 and np.isnan(st.percentile(99))


def test_engine_closure_reports_iterations():
  eng = MMOEngine(backend="xla")
  w = graphs.weighted_digraph(12, 0.3, seed=0)
  res = eng.submit(apsp_request(w)).result()
  _, it = solvers.apsp(w)
  assert res.extras["iterations"] >= int(it) >= 1


# ---------------------------------------------------------------------------
# executable cache: steady-state traffic never retraces
# ---------------------------------------------------------------------------


def test_cache_zero_recompiles_on_repeat_traffic():
  eng = MMOEngine(backend="xla", max_batch=4)
  def traffic():
    futs = [eng.submit(apsp_request(graphs.weighted_digraph(n, 0.3, seed=n)))
            for n in (9, 10, 12, 14)]
    futs.append(eng.submit(reachability_request(
        graphs.boolean_digraph(11, 0.15, seed=1))))
    eng.run_until_idle()
    return futs

  traffic()
  misses = eng.cache.misses
  assert misses > 0
  futs = traffic()  # identical shapes → identical buckets → pure cache hits
  assert eng.cache.misses == misses
  assert all(f.done() for f in futs)


def test_mixed_backend_buckets_zero_retraces():
  """Steady-state serving with *per-bucket* backend selection: two buckets
  resolved to different backends replay their executables with zero cache
  misses after warmup — the dispatch decision is part of the cache key."""
  from repro.tuning import CostTable
  table = CostTable(device="test")
  nb = (16, 16, 16)
  table.record("minplus", nb, "float32", "vector", (128,), 1e-6)
  table.record("minplus", nb, "float32", "xla", (512,), 1.0)
  table.record("orand", nb, "bool", "xla", (512,), 1e-6)
  table.record("orand", nb, "bool", "vector", (128,), 1.0)
  eng = MMOEngine(backend="auto", max_batch=4, cost_table=table)

  def traffic():
    futs = [eng.submit(apsp_request(graphs.weighted_digraph(n, 0.3, seed=n)))
            for n in (9, 11, 13)]
    futs.append(eng.submit(reachability_request(
        graphs.boolean_digraph(10, 0.15, seed=1))))
    eng.run_until_idle()
    return futs

  futs = traffic()
  assert {b for b, _ in eng.placement._decisions.values()} == {"vector", "xla"}
  misses = eng.cache.misses
  assert misses > 0
  futs2 = traffic()  # steady state: mixed backends, zero retraces
  assert eng.cache.misses == misses
  assert all(f.done() for f in futs + futs2)
  for fut, n in zip(futs, (9, 11, 13)):
    ref, _ = solvers.apsp(graphs.weighted_digraph(n, 0.3, seed=n))
    np.testing.assert_allclose(fut.result().value, np.asarray(ref), atol=1e-5)
  ref, _ = solvers.gtc(graphs.boolean_digraph(10, 0.15, seed=1))
  np.testing.assert_array_equal(futs[-1].result().value, np.asarray(ref))


def test_prewarm_resolves_like_step():
  """prewarm and step must agree on the (backend, block) part of the cache
  key, or warmed engines would recompile on first real traffic."""
  from repro.tuning import CostTable
  table = CostTable(device="test")
  table.record("minplus", (16, 16, 16), "float32", "vector", (128,), 1e-6)
  eng = MMOEngine(backend="auto", max_batch=2, cost_table=table)
  eng.prewarm([apsp_request(graphs.weighted_digraph(10, 0.3, seed=0))])
  misses = eng.cache.misses
  eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=1)))
  eng.run_until_idle()
  assert eng.cache.misses == misses


def test_prewarm_covers_batch_variants():
  eng = MMOEngine(backend="xla", max_batch=4)
  sample = [apsp_request(graphs.weighted_digraph(10, 0.3, seed=0))]
  compiled = eng.prewarm(sample)
  assert compiled == 3  # batch buckets 1, 2, 4
  misses = eng.cache.misses
  for i in range(3):  # batch of 3 → rounds up to the prewarmed 4
    eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=i)))
  eng.run_until_idle()
  assert eng.cache.misses == misses


# ---------------------------------------------------------------------------
# futures / background loop
# ---------------------------------------------------------------------------


def test_future_lazy_result_drives_engine():
  eng = MMOEngine(backend="xla")
  fut = eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=2)))
  assert not fut.done()
  res = fut.result()  # drives step() internally
  assert fut.done() and res.value.shape == (10, 10)


def test_background_loop_serves():
  eng = MMOEngine(backend="xla", max_batch=4)
  eng.start()
  futs = [eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=i)))
          for i in range(6)]
  results = [f.result(timeout=120) for f in futs]
  eng.stop()
  assert all(r.value.shape == (10, 10) for r in results)


def test_request_validation():
  with pytest.raises(ValueError):
    mmo_request(np.zeros((3, 4)), np.zeros((5, 6)))  # contraction mismatch
  with pytest.raises(ValueError):
    closure_request(np.zeros((3, 4)), op="minplus")  # non-square
  with pytest.raises(ValueError):
    knn_request(np.zeros((2, 3)), np.zeros((4, 3)), k=9)  # k > corpus
  with pytest.raises(ValueError):
    closure_request(np.zeros((3, 3)), op="nope")  # unknown ring


# ---------------------------------------------------------------------------
# engine concurrency seams (the PR-3 bugfix sweep)
# ---------------------------------------------------------------------------


def test_dropped_request_raises_runtime_error_not_timeout():
  """A request the scheduler loses is an engine bug: result() must say so
  (naming the request), not claim it timed out 'within Nones'."""
  eng = MMOEngine(backend="xla")
  fut = eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)))
  eng.scheduler._buckets.clear()  # simulate the engine losing the request
  with pytest.raises(RuntimeError, match=rf"request {fut.request.request_id}"
                                         r".*dropped"):
    fut.result()


def test_dropped_request_raises_in_background_loop_mode():
  """Same engine bug with the background loop running: result(timeout=None)
  must raise instead of blocking forever on an event nobody will set."""
  eng = MMOEngine(backend="xla")
  fut = eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)))
  eng.scheduler._buckets.clear()  # lose it before the loop can serve it
  eng.start()
  try:
    with pytest.raises(RuntimeError, match=r"dropped"):
      fut.result()
  finally:
    eng.stop(drain=False)


def test_timeout_message_formats_seconds():
  eng = MMOEngine(backend="xla")
  fut = eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)))
  # a zero timeout expires before the first step; the message must carry a
  # readable duration (the old f-string printed 'within Nones' for None)
  with pytest.raises(TimeoutError, match=r"not done within 0s"):
    fut.result(timeout=0.0)
  assert fut.result().value.shape == (10, 10)  # still servable afterwards


def test_resolve_backend_is_threadsafe(monkeypatch):
  """prewarm() on the caller thread races step() on the loop thread into
  placement.resolve_backend; the memoization must be atomic so every caller sees one
  decision even when the cost table's answer changes between calls."""
  import threading as th
  from repro.tuning import Decision
  from repro.tuning import dispatch as dsp

  calls = []

  def slow_resolve(op, m, k, n, dtype, **kw):
    calls.append(None)
    time.sleep(0.005)  # widen the check-then-memoize window
    return Decision(f"backend-{len(calls)}", (), 1.0, "measured")

  monkeypatch.setattr(dsp, "resolve", slow_resolve)
  eng = MMOEngine(backend="auto")
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)))

  out, barrier = [], th.Barrier(8)

  def hammer():
    barrier.wait()
    for _ in range(10):
      out.append(eng.placement.resolve_backend(key))

  threads = [th.Thread(target=hammer) for _ in range(8)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  assert len(set(out)) == 1, f"divergent memoized decisions: {set(out)}"
  assert len(calls) == 1  # resolved exactly once, under the placement lock


def test_stop_drain_wakes_on_empty_pending():
  """stop(drain=True) must return promptly once the loop empties the queue
  (condition-variable wait, not a sleep-poll) and leave everything done."""
  eng = MMOEngine(backend="xla", max_batch=4)
  eng.start()
  futs = [eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=i)))
          for i in range(8)]
  eng.stop(drain=True)
  assert eng.pending() == 0
  assert all(f.done() for f in futs)
  assert all(f.result().value.shape == (10, 10) for f in futs)


def test_batch_failure_fails_futures_and_keeps_serving(monkeypatch):
  """step()'s except branch: a poisoned batch fails every future in it,
  leaves _inflight/_pending clean, and the engine keeps serving."""
  from repro.serve_mmo import batching as batching_mod

  eng = MMOEngine(backend="xla", max_batch=4)
  futs = [eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=i)))
          for i in range(3)]

  boom = RuntimeError("poisoned operands")
  real_stack = batching_mod.stack_batch
  monkeypatch.setattr(batching_mod, "stack_batch",
                      lambda *a, **kw: (_ for _ in ()).throw(boom))
  assert eng.step() == 0  # the whole batch fails, step reports 0 completions
  assert eng._inflight == set() and eng.pending() == 0
  for f in futs:
    assert f.done()
    with pytest.raises(RuntimeError, match="poisoned operands"):
      f.result()

  monkeypatch.setattr(batching_mod, "stack_batch", real_stack)
  ok = eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=9)))
  assert eng.run_until_idle() == 1
  ref, _ = solvers.apsp(graphs.weighted_digraph(12, 0.3, seed=9))
  np.testing.assert_allclose(ok.result().value, np.asarray(ref), atol=1e-5)
  assert eng._inflight == set() and eng.pending() == 0


def test_poisoned_batch_still_records_measured_iterations(monkeypatch):
  """Regression: measured closure convergence counts feed the adaptive
  estimator the moment the fixpoint has run — a batch that fails *after*
  execution (poisoned split_results) must still contribute its iteration
  observations, or serving pathologies would systematically starve the
  estimator exactly when the device is misbehaving.  Failed batches must
  NOT contribute service-seconds observations (no result was produced to
  time)."""
  from repro.serve_mmo import batching as batching_mod

  eng = MMOEngine(backend="xla", max_batch=4)
  key = None
  for i in range(3):
    req = apsp_request(graphs.weighted_digraph(12, 0.3, seed=i))
    key = key or request_bucket(req)
    eng.submit(req)

  real_split = batching_mod.split_results
  monkeypatch.setattr(
      batching_mod, "split_results",
      lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("poisoned split")))
  assert eng.step() == 0  # the fixpoint ran; splitting its results failed
  snap = eng.estimator.snapshot()
  (label,) = snap["iterations"]
  assert label.startswith("closure/minplus")
  it = snap["iterations"][label]
  assert it["observations"] == 1 and 1.0 <= it["iterations"] <= 4.0
  # only the live slots count — a padded 4-batch of 3 requests must not
  # average the 4th (copied) slot's convergence into the estimate
  assert snap["cells"] == {}  # no seconds observation from a failed batch

  # and the estimator keeps accumulating once the engine recovers
  monkeypatch.setattr(batching_mod, "split_results", real_split)
  ok = eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=9)))
  assert eng.run_until_idle() == 1 and ok.state == "done"
  snap = eng.estimator.snapshot()
  assert snap["iterations"][label]["observations"] == 2
  assert any(lab.startswith("closure/minplus") for lab in snap["cells"])


def test_split_count_mismatch_fails_loudly_not_wedged(monkeypatch):
  """A split_results that returns the wrong number of results must fail the
  batch (every future resolves with an error) rather than silently leaving
  the unzipped tail pending forever — and the engine keeps serving."""
  from repro.serve_mmo import batching as batching_mod

  eng = MMOEngine(backend="xla", max_batch=4, transient_retries=0,
                  bisect=False, retry_backoff_s=0.0)
  futs = [eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=i)))
          for i in range(3)]

  real_split = batching_mod.split_results
  monkeypatch.setattr(
      batching_mod, "split_results",
      lambda key, reqs, out: real_split(key, reqs, out)[:-1])  # drop one
  assert eng.step() == 0
  assert eng._inflight == set() and eng.pending() == 0
  for f in futs:
    assert f.done()
    with pytest.raises(RuntimeError, match="split_results returned 2"):
      f.result()

  monkeypatch.setattr(batching_mod, "split_results", real_split)
  ok = eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=9)))
  assert eng.run_until_idle() == 1
  assert ok.result().value.shape == (12, 12)
  assert eng._inflight == set() and eng.pending() == 0


@pytest.mark.parametrize("mode", ["batch", "arena"])
def test_future_callback_error_does_not_kill_serving(mode):
  """A consumer hook that raises out of future fulfillment must not take
  down the batch's siblings or the serving loop: the result is already
  delivered (state set before the hook ran), the error is traced, and the
  request still counts completed — for a batch and for arena evictions."""
  eng = MMOEngine(backend="xla", max_batch=4, mode=mode)
  futs = [eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=i)))
          for i in range(3)]

  orig = futs[1]._fulfill
  def exploding_fulfill(res):
    orig(res)  # state is set first — then the consumer-side hook blows up
    raise RuntimeError("consumer callback boom")
  futs[1]._fulfill = exploding_fulfill

  assert eng.step() == 3  # the raising callback's request still completes
  assert eng._inflight == set() and eng.pending() == 0
  for f in futs:
    assert f.done() and f.result().value.shape == (12, 12)
  snap = eng.metrics_snapshot()
  assert snap["counters"]["completed"] == 3
  assert snap["counters"]["failed"] == 0
  names = [ev["name"] for ev in eng.export_trace()["traceEvents"]
           if ev.get("ph") == "i"]
  assert "future_callback_error" in names

  ok = eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=9)))
  assert eng.run_until_idle() == 1 and ok.state == "done"


# --- executable-cache thread-safety (repro.analysis lock-discipline fix) ----


def test_cache_concurrent_get_or_compile_consistent_accounting():
  """N threads hammer a handful of keys with a slow build; accounting must
  balance (hits + compile-losses == calls - executables) and every thread
  must receive a working executable.  Before the cache grew its lock this
  raced: concurrent first-misses corrupted the entry dict and the counters.
  """
  import threading

  from repro.serve_mmo.cache import ExecutableCache

  cache = ExecutableCache()
  keys = [("k", i) for i in range(3)]
  calls_per_thread, n_threads = 8, 6
  args = (np.zeros((4, 4), np.float32),)
  errors = []
  barrier = threading.Barrier(n_threads)

  def make_fn():
    time.sleep(0.01)  # widen the miss→insert window
    return lambda x: x + 1

  def worker(seed):
    rng = np.random.default_rng(seed)
    barrier.wait()
    try:
      for _ in range(calls_per_thread):
        key = keys[rng.integers(len(keys))]
        fn = cache.get_or_compile(key, make_fn, args)
        out = fn(args[0])
        assert out.shape == (4, 4)
        cache.stats()  # concurrent reader on the counters
    except Exception as e:  # noqa: BLE001
      errors.append(e)

  threads = [threading.Thread(target=worker, args=(s,))
             for s in range(n_threads)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  assert errors == []
  stats = cache.stats()
  total_calls = calls_per_thread * n_threads
  assert stats["executables"] == len(keys)
  # misses counts compile attempts; a loser of a compile race counts as a
  # miss AND lands on the winner's entry as a hit, so the exact invariant
  # is hits + inserted executables == total calls
  assert stats["misses"] >= stats["executables"]
  assert stats["hits"] + stats["executables"] == total_calls
  assert len(cache) == len(keys)


# ---------------------------------------------------------------------------
# cross-path parity: the batch path against the shared closure corpus
# ---------------------------------------------------------------------------

from fixtures import closure_corpus as corpus  # noqa: E402


@pytest.mark.parametrize("case",
                         [c for c in corpus.CORPUS if c.engine_ok],
                         ids=[c.name for c in corpus.CORPUS if c.engine_ok])
def test_corpus_parity_engine_batch_mode(case):
  """The batched per-iteration path (mode='batch', backend='xla') must be
  bit-identical — outputs AND iteration counts — to the corpus reference.
  test_closure_megakernel.py and test_arena.py assert the same corpus for
  the fused and arena paths, so all three execution paths are pinned to
  one set of numbers (validation off: the NaN-edge case is data here)."""
  ref_out, ref_it = corpus.reference(case)
  eng = MMOEngine(backend="xla", validate_results=False)
  futs = [eng.submit(closure_request(g, op=case.op, algorithm=case.algorithm,
                                     prepared=True))
          for g in case.graphs]
  eng.run_until_idle()
  for i, f in enumerate(futs):
    res = f.result()
    n = case.sizes[i]
    np.testing.assert_array_equal(res.value, ref_out[i, :n, :n])
    assert res.extras["iterations"] == int(ref_it[i])
