"""Fault tolerance: injection harness, batch bisection, circuit breakers.

The two ISSUE acceptance pins live here:

  * a persistent single-request poison in a 16-request batch fails exactly
    that one future — the 15 innocents complete (bisection isolation),
  * a persistently-failing backend opens its breaker within a few batches,
    traffic re-dispatches to the fallback arm with bit-identical results,
    and a half-open probe closes the breaker after the fault clears — all
    visible in /metrics, /healthz and the exported trace.
"""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from conftest import FakeClock
from repro.apps import graphs, solvers
from repro.serve_mmo import (BatchTimeoutError, FaultInjector, FaultRule,
                             InjectedFault, MMOEngine, NonFiniteResultError,
                             ObservabilityServer, ResilienceManager,
                             apsp_request, parse_fault_spec)
from repro.serve_mmo import batching
from repro.serve_mmo.faults import classify_failure
from repro.serve_mmo.scheduler import request_bucket


def _engine(**kw):
  kw.setdefault("backend", "vector")
  kw.setdefault("retry_backoff_s", 0.0)
  return MMOEngine(**kw)


def _submit_apsp(eng, n_reqs, *, nodes=10, **req_kw):
  return [eng.submit(apsp_request(
      graphs.weighted_digraph(nodes, 0.3, seed=i), **req_kw))
      for i in range(n_reqs)]


def _trace_events(eng):
  return eng.export_trace()["traceEvents"]


def _http_get(url):
  """(status, body) — urllib raises on 503, which is a valid answer here."""
  try:
    with urllib.request.urlopen(url, timeout=10) as resp:
      return resp.status, resp.read().decode("utf-8")
  except urllib.error.HTTPError as e:
    return e.code, e.read().decode("utf-8")


# ---------------------------------------------------------------------------
# fault injector: rules, schedules, determinism, spec grammar
# ---------------------------------------------------------------------------


def test_fault_rule_validation():
  with pytest.raises(ValueError, match="point"):
    FaultRule(point="nope")
  with pytest.raises(ValueError, match="mode"):
    FaultRule(point="execute", mode="sometimes")
  with pytest.raises(ValueError, match="rate"):
    FaultRule(point="execute", mode="rate", rate=1.5)
  with pytest.raises(ValueError, match="count"):
    FaultRule(point="execute", mode="transient", count=0)


def test_transient_rule_exhausts():
  inj = FaultInjector([FaultRule(point="execute", mode="transient", count=2)])
  assert inj.check("execute") is not None
  assert inj.check("execute") is not None
  assert inj.check("execute") is None  # budget spent
  assert inj.stats()["fired"]["execute"] == 2


def test_persistent_rule_fires_until_cleared():
  inj = FaultInjector([FaultRule(point="compile", mode="persistent")])
  for _ in range(5):
    assert inj.check("compile") is not None
  assert inj.check("execute") is None  # other points untouched
  assert inj.clear("execute") == 0     # nothing armed there
  assert inj.clear() == 1              # "the fault cleared"
  assert inj.check("compile") is None


def test_rate_rule_is_deterministic_under_seed():
  def pattern(seed):
    inj = FaultInjector(
        [FaultRule(point="execute", mode="rate", rate=0.3)], seed=seed)
    return [inj.check("execute") is not None for _ in range(200)]

  p = pattern(7)
  assert p == pattern(7)          # same seed → identical chaos, replayable
  assert 0 < sum(p) < 200         # actually probabilistic, not all/none


def test_rule_scoping_filters():
  inj = FaultInjector([
      FaultRule(point="execute", mode="persistent", backend="xla"),
      FaultRule(point="compile", mode="persistent", match="closure"),
      FaultRule(point="nonfinite", mode="persistent",
                request_ids=frozenset({7})),
  ])
  assert inj.check("execute", backend="vector") is None
  assert inj.check("execute", backend="xla") is not None
  assert inj.check("compile", label="mmo/minplus") is None
  assert inj.check("compile", label="closure/minplus/n16") is not None
  assert inj.check("nonfinite", request_ids=[1, 2]) is None
  assert inj.check("nonfinite", request_ids=[2, 7]) is not None


def test_parse_fault_spec_grammar():
  inj = parse_fault_spec(
      "execute:rate:0.02;slow:transient:1:delay=0.2;"
      "execute:persistent:backend=xla;nonfinite:persistent:rid=3,5@closure")
  rules = inj.rules()
  assert [r.point for r in rules] == ["execute", "slow", "execute",
                                      "nonfinite"]
  assert rules[0].mode == "rate" and rules[0].rate == 0.02
  assert rules[1].count == 1 and rules[1].delay_s == 0.2
  assert rules[2].backend == "xla"
  assert rules[3].request_ids == frozenset({3, 5})
  assert rules[3].match == "closure"


def test_parse_fault_spec_rejects_garbage():
  with pytest.raises(ValueError, match="point"):
    parse_fault_spec("frobnicate:persistent")
  with pytest.raises(ValueError, match="unknown fault rule key"):
    parse_fault_spec("execute:persistent:color=red")
  with pytest.raises(ValueError, match="too many positional"):
    parse_fault_spec("execute:transient:1:2")
  with pytest.raises(ValueError, match="no rules"):
    parse_fault_spec(" ; ")


def test_classify_failure_taxonomy():
  assert classify_failure(NonFiniteResultError("b", [0]), "split") == "nonfinite"
  assert classify_failure(BatchTimeoutError("b", 0.1), "execute") == "timeout"
  assert classify_failure(InjectedFault("compile"), "execute") == "compile"
  assert classify_failure(RuntimeError("x"), "stack") == "stack"
  assert classify_failure(RuntimeError("x"), "weird-phase") == "other"


# ---------------------------------------------------------------------------
# result validation primitives
# ---------------------------------------------------------------------------


def test_validate_finite_flags_nan_not_inf():
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)),
                       8)
  out = np.zeros((4, 16, 16), np.float32)
  out[3] = np.inf          # legitimate tropical output (unreachable pair)
  assert batching.validate_finite(key, out, 4) == []
  out[1, 5, 5] = np.nan
  out[3, 0, 0] = np.nan    # padded-slot NaN beyond live must be ignored too
  assert batching.validate_finite(key, out, 2) == [1]
  assert batching.validate_finite(key, out, 4) == [1, 3]
  # tuple outputs (closure results carry iteration counts) check out[0]
  iters = np.array([2, 2, 2, 2], np.int32)
  assert batching.validate_finite(key, (out, iters), 4) == [1, 3]
  # non-float payloads (boolean reachability) have no NaN to find
  assert batching.validate_finite(key, out.astype(bool), 4) == []


def test_poison_output_corrupts_requested_slots():
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)),
                       8)
  out = np.zeros((3, 4, 4), np.float32)
  poisoned = batching.poison_output(key, (out, np.arange(3)), [1])
  assert np.isnan(poisoned[0][1]).all()
  assert not np.isnan(poisoned[0][0]).any()
  np.testing.assert_array_equal(poisoned[1], np.arange(3))


# ---------------------------------------------------------------------------
# circuit breaker state machine (unit level, fake clock)
# ---------------------------------------------------------------------------


def test_breaker_opens_probes_and_closes():
  fake_clock = FakeClock()
  mgr = ResilienceManager(threshold=2, probe_after_s=1.0, clock=fake_clock)
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)),
                       8)
  primary = ("xla", (), "local")
  fallbacks = lambda: (("vector", (), "local"),)

  assert mgr.pick(key, primary, fallbacks) == (primary, False)
  assert mgr.on_failure(key, primary) is None          # 1 of 2
  assert mgr.pick(key, primary, fallbacks) == (primary, False)
  assert mgr.on_failure(key, primary) == "open"        # threshold hit
  # open: picks fall through to the fallback arm
  assert mgr.pick(key, primary, fallbacks) == (("vector", (), "local"), False)
  assert mgr.open_arms()[0]["backend"] == "xla"
  # cooldown elapses on the injected clock → next pick is the probe
  fake_clock.t += 1.5
  arm, probe = mgr.pick(key, primary, fallbacks)
  assert arm == primary and probe
  # probe failure re-opens and restarts the cooldown
  assert mgr.on_failure(key, primary) == "open"
  assert mgr.pick(key, primary, fallbacks)[0] == ("vector", (), "local")
  fake_clock.t += 1.5
  arm, probe = mgr.pick(key, primary, fallbacks)
  assert probe
  assert mgr.on_success(key, primary) == "close"       # probe recovered it
  assert mgr.pick(key, primary, fallbacks) == (primary, False)
  snap = mgr.snapshot()
  assert len(snap) == 1
  cell = snap[0]
  assert (cell["state"], cell["opens"], cell["closes"], cell["probes"]) == (
      "closed", 2, 1, 2)
  assert mgr.open_arms() == []


def test_breaker_success_resets_consecutive_count():
  mgr = ResilienceManager(threshold=3, clock=FakeClock())
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)),
                       8)
  arm = ("xla", (), "local")
  mgr.on_failure(key, arm)
  mgr.on_failure(key, arm)
  assert mgr.on_success(key, arm) is None   # plain success, not a probe
  mgr.on_failure(key, arm)
  mgr.on_failure(key, arm)
  assert mgr.snapshot()[0]["state"] == "closed"  # never 3 consecutive


def test_breaker_all_arms_open_serves_last():
  mgr = ResilienceManager(threshold=1, probe_after_s=100.0, clock=FakeClock())
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)),
                       8)
  primary = ("xla", (), "local")
  last = ("vector", (), "local")
  mgr.on_failure(key, primary)
  mgr.on_failure(key, last)
  # both broken, no cooldown elapsed: serve on the terminal arm anyway
  assert mgr.pick(key, primary, lambda: (last,)) == (last, False)


def test_breaker_threshold_none_disables():
  mgr = ResilienceManager(threshold=None)
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)),
                       8)
  arm = ("xla", (), "local")
  for _ in range(50):
    assert mgr.on_failure(key, arm) is None
  assert mgr.pick(key, arm, lambda: ()) == (arm, False)
  assert mgr.snapshot() == []


def test_breaker_threshold_validation():
  with pytest.raises(ValueError, match="threshold"):
    ResilienceManager(threshold=0)
  with pytest.raises(ValueError, match="transient_retries"):
    MMOEngine(backend="vector", transient_retries=-1)


# ---------------------------------------------------------------------------
# engine fault matrix: every injection point × transient / persistent
# ---------------------------------------------------------------------------

_MATRIX = [
    ("compile", "compile", InjectedFault),
    ("execute", "execute", InjectedFault),
    ("nonfinite", "nonfinite", NonFiniteResultError),
    ("slow", "timeout", BatchTimeoutError),
]


@pytest.mark.parametrize("point,kind,_exc", _MATRIX,
                         ids=[m[0] for m in _MATRIX])
def test_transient_fault_is_ridden_out(point, kind, _exc):
  """A blip at any injection point is absorbed by the retry budget: every
  request completes, the retry counter moves, the failure is classified."""
  inj = FaultInjector([FaultRule(point=point, mode="transient", count=1,
                                 delay_s=0.5)])
  eng = _engine(max_batch=2, faults=inj, transient_retries=2,
                breaker_threshold=None,
                watchdog_s=0.1 if point == "slow" else None)
  futs = _submit_apsp(eng, 2)
  assert eng.run_until_idle() == 2
  for i, fut in enumerate(futs):
    ref, _ = solvers.apsp(graphs.weighted_digraph(10, 0.3, seed=i))
    np.testing.assert_allclose(fut.result().value, np.asarray(ref), atol=1e-5)
  snap = eng.metrics_snapshot()
  assert snap["counters"]["retries"] >= 1
  assert snap["counters"]["failed"] == 0
  assert snap["batch_failures_by_kind"] == {kind: 1}


@pytest.mark.parametrize("point,kind,exc", _MATRIX,
                         ids=[m[0] for m in _MATRIX])
def test_persistent_fault_exhausts_budget_and_fails(point, kind, exc):
  """A persistent fault burns retries and bisection, then fails every
  poisoned request with the *typed* failure — and the loop keeps serving."""
  inj = FaultInjector([FaultRule(point=point, mode="persistent",
                                 delay_s=0.5)])
  eng = _engine(max_batch=2, faults=inj, transient_retries=1,
                breaker_threshold=None,
                watchdog_s=0.1 if point == "slow" else None)
  futs = _submit_apsp(eng, 2)
  assert eng.run_until_idle() == 0
  for fut in futs:
    with pytest.raises(exc):
      fut.result()
  snap = eng.metrics_snapshot()
  assert snap["counters"]["failed"] == 2
  assert snap["counters"]["completed"] == 0
  assert set(snap["batch_failures_by_kind"]) == {kind}
  assert not eng._inflight
  # the fault clearing restores service on the same engine
  inj.clear()
  fut = eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=9)))
  eng.run_until_idle()
  assert fut.result().value.shape == (10, 10)


# ---------------------------------------------------------------------------
# acceptance pin 1: bisection isolates a single poisoned request
# ---------------------------------------------------------------------------


def test_single_poisoned_request_in_16_batch_fails_alone():
  inj = FaultInjector()
  eng = _engine(max_batch=16, faults=inj, transient_retries=1,
                breaker_threshold=None)
  futs = _submit_apsp(eng, 16, nodes=12)
  poisoned_rid = futs[5].request.request_id
  inj.arm(FaultRule(point="execute", mode="persistent",
                    request_ids=frozenset({poisoned_rid})))
  assert eng.run_until_idle() == 15

  for i, fut in enumerate(futs):
    if i == 5:
      with pytest.raises(InjectedFault):
        fut.result()
    else:
      ref, _ = solvers.apsp(graphs.weighted_digraph(12, 0.3, seed=i))
      np.testing.assert_allclose(fut.result().value, np.asarray(ref),
                                 atol=1e-5)
  snap = eng.metrics_snapshot()
  assert snap["counters"]["completed"] == 15
  assert snap["counters"]["failed"] == 1
  assert snap["counters"]["retries"] > 0
  assert not eng._inflight

  events = _trace_events(eng)
  names = [ev["name"] for ev in events if ev.get("ph") == "i"]
  assert "batch_bisect" in names         # isolation visible in the trace
  assert "batch_fail" in names
  # O(log B) isolation: a 16-wide poison needs ~log2(16)=4 bisections, far
  # fewer than the 15 a linear per-request scan would cost
  assert 4 <= names.count("batch_bisect") <= 8


def test_bisect_disabled_fails_whole_batch():
  inj = FaultInjector()
  eng = _engine(max_batch=4, faults=inj, transient_retries=1, bisect=False,
                breaker_threshold=None)
  futs = _submit_apsp(eng, 4)
  inj.arm(FaultRule(point="execute", mode="persistent",
                    request_ids=frozenset({futs[0].request.request_id})))
  assert eng.run_until_idle() == 0    # historical fail-whole-batch behavior
  for fut in futs:
    with pytest.raises(InjectedFault):
      fut.result()


def test_rate_faults_never_fail_innocents():
  """Chaos mode: a 20% execute fault rate with bisection + fresh per-half
  retry budgets completes every request (nobody is actually poisoned)."""
  inj = FaultInjector([FaultRule(point="execute", mode="rate", rate=0.2)],
                      seed=3)
  eng = _engine(max_batch=8, faults=inj, transient_retries=2,
                breaker_threshold=None)
  futs = _submit_apsp(eng, 16)
  eng.run_until_idle()
  assert all(f.result().value.shape == (10, 10) for f in futs)
  snap = eng.metrics_snapshot()
  assert snap["counters"]["failed"] == 0
  assert snap["counters"]["completed"] == 16


# ---------------------------------------------------------------------------
# retry accounting: once-per-request outcomes, balanced spans, no re-stamp
# ---------------------------------------------------------------------------


def test_retry_does_not_double_count_or_restamp_deadlines():
  inj = FaultInjector([FaultRule(point="execute", mode="transient", count=1)])
  eng = _engine(max_batch=4, faults=inj, transient_retries=1,
                breaker_threshold=None)
  futs = _submit_apsp(eng, 4, deadline_s=30.0)
  deadlines = [f.request.deadline_at for f in futs]
  assert eng.run_until_idle() == 4
  # deadlines are stamped at submit and never re-stamped by the retry path
  assert [f.request.deadline_at for f in futs] == deadlines
  snap = eng.metrics_snapshot()
  assert snap["counters"]["completed"] == 4   # once per request, not per try
  assert snap["counters"]["submitted"] == 4
  assert snap["counters"]["retries"] == 1
  # admission quota drained exactly once per request
  assert eng.admission.snapshot()["inflight"] == {}

  # balanced spans per request: queued is exactly one b/e pair; every
  # execute 'b' (one per attempt) has a matching 'e'
  events = _trace_events(eng)
  for fut in futs:
    rid = fut.request.request_id
    mine = [ev for ev in events
            if ev.get("ph") in ("b", "e") and ev.get("id") == rid]
    queued = [ev["ph"] for ev in mine if ev["name"] == "queued"]
    execute = [ev["ph"] for ev in mine if ev["name"] == "execute"]
    assert queued == ["b", "e"]
    assert len(execute) % 2 == 0
    assert execute == ["b", "e"] * (len(execute) // 2)
  # the retried attempt closed its first execute slice as 'retried'
  outcomes = [ev["args"]["outcome"] for ev in events
              if ev.get("name") == "execute" and ev.get("ph") == "e"
              and "outcome" in ev.get("args", {})]
  assert "retried" in outcomes and "done" in outcomes


def test_service_window_includes_retry_time():
  """queue/service metrics measure what the caller experienced: the service
  window spans from the ORIGINAL batch pick through the final successful
  attempt, retries and backoff included."""
  inj = FaultInjector([FaultRule(point="execute", mode="transient", count=1)])
  eng = MMOEngine(backend="vector", max_batch=2, faults=inj,
                  transient_retries=1, breaker_threshold=None,
                  retry_backoff_s=0.05)
  futs = _submit_apsp(eng, 2)
  eng.run_until_idle()
  assert all(f.done() for f in futs)
  snap = eng.metrics_snapshot()
  svc = snap["buckets"][next(iter(snap["buckets"]))]["service_ms"]
  assert svc["p50"] >= 50.0   # the 50ms backoff is part of service latency


# ---------------------------------------------------------------------------
# watchdog: a hung batch fails instead of wedging the loop
# ---------------------------------------------------------------------------


def test_watchdog_times_out_hung_batch():
  inj = FaultInjector([FaultRule(point="slow", mode="persistent",
                                 delay_s=1.0)])
  eng = _engine(max_batch=2, faults=inj, transient_retries=0, bisect=False,
                breaker_threshold=None, watchdog_s=0.05)
  futs = _submit_apsp(eng, 2)
  t0 = time.perf_counter()
  assert eng.run_until_idle() == 0
  assert time.perf_counter() - t0 < 0.9   # did not serve the full stall
  for fut in futs:
    with pytest.raises(BatchTimeoutError, match="watchdog"):
      fut.result()
  assert eng.metrics_snapshot()["batch_failures_by_kind"] == {"timeout": 1}


def test_watchdog_disabled_runs_inline():
  inj = FaultInjector([FaultRule(point="slow", mode="persistent",
                                 delay_s=0.02)])
  eng = _engine(max_batch=2, faults=inj, breaker_threshold=None)
  futs = _submit_apsp(eng, 2)
  assert eng.run_until_idle() == 2         # slow but correct, no timeout
  assert all(f.result().value.shape == (10, 10) for f in futs)


# ---------------------------------------------------------------------------
# acceptance pin 2: breaker re-dispatch, bit-identical results, probe close
# ---------------------------------------------------------------------------


def test_breaker_cycle_redispatch_probe_and_health():
  inj = parse_fault_spec("execute:persistent:backend=xla")
  eng = MMOEngine(backend="xla", max_batch=4, faults=inj,
                  fallback_backends=("vector",), breaker_threshold=2,
                  transient_retries=1, retry_backoff_s=0.0,
                  breaker_probe_s=0.05)
  futs = _submit_apsp(eng, 8)
  assert eng.run_until_idle() == 8   # breaker opened mid-recovery; innocents
                                     # (all 8) completed on the fallback arm

  # bit-identical to the fallback arm computed standalone (the SIMD²
  # property: sibling arms share the substrate, results are exchangeable)
  ref_eng = MMOEngine(backend="vector", max_batch=4)
  ref_futs = _submit_apsp(ref_eng, 8)
  ref_eng.run_until_idle()
  for fut, ref in zip(futs, ref_futs):
    np.testing.assert_array_equal(fut.result().value, ref.result().value)

  snap = eng.observability_state()
  cells = {(c["backend"], c["state"]) for c in snap["breakers"]}
  assert ("xla", "open") in cells
  assert eng.resilience.open_arms()

  with ObservabilityServer(eng) as srv:
    status, body = _http_get(srv.url + "/healthz")
    assert status == 503
    health = json.loads(body)
    assert health["status"] == "degraded"
    assert health["open_breakers"][0]["backend"] == "xla"
    status, text = _http_get(srv.url + "/metrics")
    assert status == 200
    assert 'serve_breaker_state{' in text and 'backend="xla"' in text
    assert 'serve_batch_failures_total{kind="execute"}' in text
    assert "serve_retries_total" in text

    # the fault clears; after the cooldown the next pick probes the primary
    # arm, the probe succeeds, and the breaker closes
    inj.clear()
    time.sleep(0.06)
    fut = eng.submit(apsp_request(graphs.weighted_digraph(10, 0.3, seed=42)))
    eng.run_until_idle()
    assert fut.result().value.shape == (10, 10)
    cell = [c for c in eng.resilience.snapshot() if c["backend"] == "xla"][0]
    assert cell["state"] == "closed"
    assert cell["closes"] >= 1 and cell["probes"] >= 1
    status, body = _http_get(srv.url + "/healthz")
    assert status == 200
    assert json.loads(body)["status"] == "ok"
    assert json.loads(body)["open_breakers"] == []

  names = [ev["name"] for ev in _trace_events(eng) if ev.get("ph") == "i"]
  assert "breaker_open" in names
  assert "breaker_probe" in names
  assert "breaker_close" in names


def test_fallback_chain_ends_at_reference_backend():
  """Auto-ranked fallbacks (no fallback_backends override) terminate at the
  reference dense backend, and a dead primary still serves through it."""
  inj = parse_fault_spec("execute:persistent:backend=xla;"
                         "execute:persistent:backend=pallas")
  eng = MMOEngine(backend="xla", max_batch=2, faults=inj,
                  breaker_threshold=1, transient_retries=2,
                  retry_backoff_s=0.0, breaker_probe_s=60.0, interpret=True)
  futs = _submit_apsp(eng, 2)
  eng.run_until_idle()
  for i, fut in enumerate(futs):
    ref, _ = solvers.apsp(graphs.weighted_digraph(10, 0.3, seed=i))
    np.testing.assert_allclose(fut.result().value, np.asarray(ref), atol=1e-5)
  key = next(iter(eng._fallback_arms_memo))
  arms = eng._fallback_arms(key)
  assert arms[-1][0] == "vector"   # terminal arm is the reference backend


def test_breaker_disabled_keeps_failing_in_place():
  """threshold=None is the historical behavior: no fallback, the poisoned
  arm's failures land on callers."""
  inj = parse_fault_spec("execute:persistent:backend=vector")
  eng = _engine(max_batch=2, faults=inj, transient_retries=0, bisect=False,
                breaker_threshold=None)
  futs = _submit_apsp(eng, 2)
  assert eng.run_until_idle() == 0
  for fut in futs:
    with pytest.raises(InjectedFault):
      fut.result()
  assert eng.observability_state()["breakers"] == []


def test_serve_launcher_exit_code_reports_failed_requests(monkeypatch):
  """The open-loop launcher with breakers, retries and bisection off: a fault
  injector wired in behind the CLI fails every request, and the run exits 1
  — undeclared failures must not look healthy.  The same faults declared
  with --inject-faults are chaos the run asked for, and it exits 0."""
  from repro import compile_cache
  from repro.launch import serve_mmo
  from repro.serve_mmo import MMOEngine, parse_fault_spec
  monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")

  def rigged(**kw):
    kw["faults"] = parse_fault_spec("execute:persistent")
    return MMOEngine(**kw)

  argv = ["--rate", "20", "--duration", "0.3", "--sizes", "8",
          "--no-warmup", "--transient-retries", "0", "--no-bisect",
          "--breaker-threshold", "0"]
  monkeypatch.setattr(serve_mmo, "MMOEngine", rigged)
  assert serve_mmo.main(argv) == 1
  monkeypatch.setattr(serve_mmo, "MMOEngine", MMOEngine)
  assert serve_mmo.main(argv + ["--inject-faults", "execute:persistent"]) == 0
