"""Device-resident request arena: parity, lifecycle, and chaos pins.

The arena's contract (serve_mmo/arena.py) is *bit-identity* with the
batched per-iteration path — outputs AND per-request iteration counts —
for every case in the shared parity corpus, regardless of when requests
are admitted or evicted relative to each other.  Plus the structural
guarantees the mode exists for: a mid-flight arrival joins a running
fixpoint with ZERO retraces after prewarm, a NaN-poisoned slot fails alone
without corrupting neighbors, and tick-failure retry/breaker accounting
matches the batch path's.
"""
import numpy as np
import pytest

from fixtures import closure_corpus as corpus

from repro.core import closure as cl_mod
from repro.serve_mmo import (FaultInjector, FaultRule, InjectedFault,
                             MMOEngine, NonFiniteResultError, RequestArena,
                             apsp_request, closure_request)
from repro.serve_mmo.cache import ExecutableCache
from repro.serve_mmo.scheduler import BucketKey, request_bucket

# one cache across the module: arenas with the same (bucket, capacity, g,
# max_iters) replay each other's executables, so the whole file compiles
# each program once
_CACHE = ExecutableCache()


def _requests(case):
  return [closure_request(g, op=case.op, algorithm=case.algorithm,
                          prepared=True) for g in case.graphs]


def _drain(arena, pending):
  """Admit-when-free / tick / sweep until everything evicts."""
  done = {}
  pending = list(pending)
  while pending or arena.live_slots():
    while pending and arena.free_slots():
      arena.admit(pending.pop(0))
    arena.tick()
    for ev in arena.sweep():
      assert id(ev.request) not in done, "request evicted twice"
      done[id(ev.request)] = ev
  return done


# ---------------------------------------------------------------------------
# corpus parity — standalone arena and engine arena mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", corpus.CORPUS, ids=corpus.CASE_IDS)
def test_corpus_parity_arena(case):
  """Every corpus case through the slot lifecycle, bit-identical to the
  batched reference — with capacity 2, so some requests wait for an
  eviction and enter an arena whose other slots are mid-fixpoint."""
  ref_out, ref_it = corpus.reference(case)
  reqs = _requests(case)
  arena = RequestArena(request_bucket(reqs[0]), capacity=2, g=3,
                       cache=_CACHE, max_iters=case.max_iters,
                       interpret=True)
  done = _drain(arena, reqs)
  for i, r in enumerate(reqs):
    ev = done[id(r)]
    n = case.sizes[i]
    np.testing.assert_array_equal(ev.value, ref_out[i, :n, :n])
    assert ev.iterations == int(ref_it[i])


@pytest.mark.parametrize("case",
                         [c for c in corpus.CORPUS if c.engine_ok],
                         ids=[c.name for c in corpus.CORPUS if c.engine_ok])
def test_corpus_parity_engine_arena_mode(case):
  """The same corpus through the full engine in mode='arena': scheduler →
  admission → slots → futures, still bit-identical (validation off so the
  NaN-edge case flows through as data, matching the reference run)."""
  ref_out, ref_it = corpus.reference(case)
  eng = MMOEngine(backend="xla", mode="arena", arena_capacity=2, arena_g=3,
                  validate_results=False)
  eng.cache = _CACHE
  futs = [eng.submit(r) for r in _requests(case)]
  eng.run_until_idle()
  for i, f in enumerate(futs):
    res = f.result()
    n = case.sizes[i]
    np.testing.assert_array_equal(res.value, ref_out[i, :n, :n])
    assert res.extras["iterations"] == int(ref_it[i])


# ---------------------------------------------------------------------------
# the structural guarantee: mid-flight admission, zero retraces
# ---------------------------------------------------------------------------


def _line(n, seed):
  rng = np.random.default_rng(seed)
  w = np.full((n, n), np.inf, np.float32)
  w[np.arange(n - 1), np.arange(1, n)] = rng.uniform(
      0.5, 1.5, n - 1).astype(np.float32)
  return w


def test_midflight_admission_zero_retraces():
  """After prewarm, a request arriving while the arena is mid-fixpoint is
  admitted into the RUNNING buffer at the next tick boundary — no new
  compilation (the cache miss counter is flat), and its result is still
  bit-identical to the batched reference."""
  eng = MMOEngine(backend="xla", mode="arena", arena_capacity=4, arena_g=2)
  compiled = eng.prewarm([apsp_request(_line(14, 0),
                                       algorithm="bellman_ford")])
  assert compiled == 3  # admit / tick / read
  misses0 = eng.cache.misses

  fa = eng.submit(apsp_request(_line(14, 1), algorithm="bellman_ford"))
  eng.step()  # admit A + first tick: the fixpoint is now running
  arena = next(iter(eng._arenas.values()))
  assert arena.live_slots() == 1 and not fa.done()
  fb = eng.submit(apsp_request(_line(13, 2), algorithm="bellman_ford"))
  eng.run_until_idle()

  assert eng.cache.misses == misses0, "mid-flight admission retraced"
  prepared = cl_mod.prepare_adjacency(np.asarray(_line(13, 2)), op="minplus")
  stack = np.asarray(cl_mod.pad_adjacency(prepared, 16, op="minplus"))[None]
  ref, it = cl_mod.batched_bellman_ford_closure(
      stack, op="minplus", backend="xla",
      valid_n=np.asarray([13], np.int32))
  np.testing.assert_array_equal(fb.result().value,
                                np.asarray(ref[0])[:13, :13])
  assert fb.result().extras["iterations"] == int(it[0])
  assert fa.result().extras["iterations"] > 0


def test_arena_trace_slot_lifecycle():
  """The flight recorder carries the admit → tick×k → evict span: an
  execute slice opening with the slot index, arena_tick X-events, and the
  eviction closing the slice with the measured iteration count."""
  eng = MMOEngine(backend="xla", mode="arena", arena_capacity=2, arena_g=2)
  fut = eng.submit(apsp_request(_line(10, 3), algorithm="bellman_ford"))
  eng.run_until_idle()
  fut.result()
  ev = eng.export_trace()["traceEvents"]
  begins = [e for e in ev if e.get("ph") == "b" and e["name"] == "execute"]
  assert begins and "slot" in begins[0]["args"]
  ticks = [e for e in ev if e.get("name") == "arena_tick"]
  assert len(ticks) >= 2  # bellman_ford on a 10-line at g=2 needs several
  ends = [e for e in ev if e.get("ph") == "e" and e["name"] == "execute"]
  assert ends and ends[-1]["args"]["outcome"] == "done"
  assert ends[-1]["args"]["iterations"] == fut.result().extras["iterations"]


class _StepClock:
  """A clock that advances 1 µs at every reading: every span the engine
  stamps has a length, and equal edges mean one shared reading."""

  def __init__(self):
    self.t = 0.0

  def __call__(self):
    self.t += 1e-6
    return self.t


def test_arena_tick_phases_admit_spans_and_compiles():
  """On the engine: each arena_tick is tiled exactly by its launch, wait
  and (on an evicting tick) readout children, emitted just before it;
  arena_finish follows every evicting tick and only those; one
  arena_admit span per admission; one compile span per cache miss."""
  eng = MMOEngine(backend="xla", mode="arena", arena_capacity=2, arena_g=2,
                  clock=_StepClock())
  futs = [eng.submit(apsp_request(_line(10, s), algorithm="bellman_ford"))
          for s in (4, 5, 6)]
  eng.run_until_idle()
  for f in futs:
    f.result()
  xs = [e for e in eng.tracer.events() if e["ph"] == "X"]
  ticks = [i for i, e in enumerate(xs) if e["name"] == "arena_tick"]
  assert len(ticks) >= 3
  evicting = 0
  for i in ticks:
    tick = xs[i]
    evicted = tick["args"]["evicted"]
    kids = ["arena_launch", "arena_wait"] + (["arena_readout"] if evicted
                                             else [])
    parts = xs[i - len(kids):i]
    assert [p["name"] for p in parts] == kids
    assert parts[0]["ts"] == tick["ts"]
    for a, b in zip(parts, parts[1:]):
      assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-6)
      assert a["dur"] > 0
    assert parts[-1]["ts"] + parts[-1]["dur"] == pytest.approx(
        tick["ts"] + tick["dur"], abs=1e-6)
    after = xs[i + 1] if i + 1 < len(xs) else None
    finished = after is not None and after["name"] == "arena_finish"
    assert finished == (evicted > 0)
    if finished:
      evicting += 1
      assert after["ts"] >= tick["ts"] + tick["dur"]
  assert evicting >= 2  # capacity 2: the third request waits for a slot
  assert not [e for e in xs if e["name"] == "arena_readout"
              and e["args"]["evicted"] == 0]
  admits = [e for e in xs if e["name"] == "arena_admit"]
  assert len(admits) == 3 and all(e["dur"] > 0 for e in admits)
  compiles = [e for e in xs if e["name"] == "compile"]
  assert len(compiles) == eng.cache.misses == 3
  assert sorted(e["args"]["key"].split("/")[-2] for e in compiles) == [
      "admit", "read", "tick"]


# ---------------------------------------------------------------------------
# chaos pins — fault injection through the arena path
# ---------------------------------------------------------------------------


def test_nan_poisoned_slot_fails_alone():
  """A NaN-poisoned slot is evicted as FAILED without freezing or
  corrupting its live neighbors — the isolation the batch path needs
  bisection for, free here from per-slot state."""
  faults = FaultInjector([FaultRule(point="nonfinite", backend="arena",
                                    request_ids={0})])
  eng = MMOEngine(backend="xla", mode="arena", arena_capacity=4, arena_g=3,
                  faults=faults)
  poisoned = eng.submit(apsp_request(_line(12, 4),
                                     algorithm="bellman_ford"))
  neighbor = eng.submit(apsp_request(_line(12, 5),
                                     algorithm="bellman_ford"))
  eng.run_until_idle()
  with pytest.raises(NonFiniteResultError):
    poisoned.result()
  prepared = cl_mod.prepare_adjacency(np.asarray(_line(12, 5)), op="minplus")
  stack = np.asarray(cl_mod.pad_adjacency(prepared, 16, op="minplus"))[None]
  ref, it = cl_mod.batched_bellman_ford_closure(stack, op="minplus",
                                                backend="xla",
                                                valid_n=np.asarray(
                                                    [12], np.int32))
  np.testing.assert_array_equal(neighbor.result().value,
                                np.asarray(ref[0])[:12, :12])
  assert neighbor.result().extras["iterations"] == int(it[0])
  snap = eng.metrics_snapshot()
  assert snap["counters"]["failed"] == 1
  assert snap["counters"]["completed"] == 1


def test_arena_tick_retry_accounting():
  """A transient execute fault on one tick: the slots stay resident, the
  next step retries the tick whole, everything completes — and the retry
  and breaker accounting from the batch path holds (counted retry, breaker
  failure recorded then cleared by success)."""
  faults = FaultInjector([FaultRule(point="execute", backend="arena",
                                    mode="transient", count=1)])
  eng = MMOEngine(backend="xla", mode="arena", arena_capacity=2, arena_g=4,
                  faults=faults, transient_retries=1, retry_backoff_s=0.0)
  fut = eng.submit(apsp_request(_line(10, 6), algorithm="bellman_ford"))
  eng.run_until_idle()
  assert fut.result().extras["iterations"] > 0
  snap = eng.metrics_snapshot()
  assert snap["counters"]["retries"] >= 1
  assert snap["counters"]["completed"] == 1
  assert snap["counters"]["failed"] == 0


def test_arena_tick_failure_budget_fails_residents():
  """A persistent execute fault exhausts the transient budget: every
  resident request fails together (there is no sibling arm to re-dispatch
  a device-resident buffer to), the arena resets, and the engine is not
  wedged — traffic after the fault clears completes normally."""
  faults = FaultInjector([FaultRule(point="execute", backend="arena")])
  eng = MMOEngine(backend="xla", mode="arena", arena_capacity=2, arena_g=4,
                  faults=faults, transient_retries=1, retry_backoff_s=0.0)
  fut = eng.submit(apsp_request(_line(10, 7), algorithm="bellman_ford"))
  eng.run_until_idle()
  with pytest.raises(InjectedFault):
    fut.result()
  assert next(iter(eng._arenas.values())).live_slots() == 0
  faults.clear("execute")
  ok = eng.submit(apsp_request(_line(10, 8), algorithm="bellman_ford"))
  eng.run_until_idle()
  assert ok.result().extras["iterations"] > 0


# ---------------------------------------------------------------------------
# slot-lifecycle unit pins
# ---------------------------------------------------------------------------


def test_arena_refuses_non_closure_and_bad_params():
  key = BucketKey(kind="mmo", op="minplus", shape=(8, 8, 8),
                  dtypes=("float32",), params=(False,))
  with pytest.raises(ValueError, match="closure"):
    RequestArena(key)
  ckey = request_bucket(apsp_request(_line(8, 0)))
  with pytest.raises(ValueError, match="capacity"):
    RequestArena(ckey, capacity=0)
  with pytest.raises(ValueError, match="g must"):
    RequestArena(ckey, g=0)


def test_arena_full_refuses_and_backfills():
  """Capacity is a hard bound: admit past it raises (the engine bounds
  admissions by free_slots); an eviction frees the slot for reuse."""
  req = apsp_request(_line(8, 1))
  arena = RequestArena(request_bucket(req), capacity=1, g=8, cache=_CACHE,
                       interpret=True)
  slot = arena.admit(req)
  assert arena.free_slots() == 0
  with pytest.raises(RuntimeError, match="arena full"):
    arena.admit(apsp_request(_line(8, 2)))
  arena.tick()
  (ev,) = arena.sweep()
  assert ev.slot == slot and arena.free_slots() == 1
  # backfill reuses the freed slot and reseeds its stale flags
  again = apsp_request(_line(7, 3))
  assert arena.admit(again) == slot
  arena.tick()
  (ev2,) = arena.sweep()
  assert ev2.request is again and ev2.iterations > 0


def test_arena_reset_returns_residents():
  reqs = [apsp_request(_line(8, s)) for s in (4, 5)]
  arena = RequestArena(request_bucket(reqs[0]), capacity=4, g=1,
                       cache=_CACHE, interpret=True)
  for r in reqs:
    arena.admit(r)
  arena.tick()
  victims = arena.reset()
  assert set(map(id, victims)) == set(map(id, reqs))
  assert arena.live_slots() == 0 and arena.free_slots() == 4
  # the arena still serves after a reset
  done = _drain(arena, [apsp_request(_line(8, 6))])
  assert len(done) == 1
