"""Observability layer: flight-recorder ring, Chrome trace validity across
every request outcome, Prometheus exposition grammar + golden rendering,
thread safety under live serving, and the HTTP endpoint."""
import json
import os
import re
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.apps import graphs
from repro.serve_mmo import (DeadlineExceededError, MMOEngine, RejectedError,
                             apsp_request, mmo_request)
from repro.serve_mmo.exposition import (HISTOGRAM_BOUNDS_S, LogHistogram,
                                        escape_label_value, render_prometheus)
from repro.serve_mmo.httpd import PROMETHEUS_CONTENT_TYPE, ObservabilityServer
from repro.serve_mmo.metrics import RollingWindow, ServeMetrics, bucket_label
from repro.serve_mmo.cache import ExecutableCache
from repro.serve_mmo.observability import FlightRecorder
from repro.serve_mmo.scheduler import (BucketKey, contract_shape,
                                      request_bucket)

from conftest import FakeClock

RNG = np.random.default_rng(0)


def _mmo_req(n=12):
  a = RNG.standard_normal((n, n)).astype(np.float32)
  b = RNG.standard_normal((n, n)).astype(np.float32)
  return mmo_request(a, b, op="minplus")


def _apsp_req(n=12, seed=0):
  return apsp_request(graphs.weighted_digraph(n, 0.3, seed=seed))


def _async_request_events(events):
  """The trace's nestable async request events, grouped (id, name) → phs."""
  grouped = {}
  for ev in events:
    if ev.get("cat") == "request" and ev["ph"] in ("b", "e"):
      grouped.setdefault((ev["id"], ev["name"]), []).append(ev)
  return grouped


def _assert_balanced(events):
  """Every async request slice must alternate open/close (b,e,b,e,... in
  ring order), equal counts, each end at or after its begin — the invariant
  Perfetto needs to nest them.  A request that was never retried has
  exactly one pair; the recovery path opens one ``execute`` pair per
  attempt (one ``e`` per ``b``)."""
  for (rid, name), evs in _async_request_events(events).items():
    phs = [ev["ph"] for ev in evs]
    assert phs.count("b") == phs.count("e"), \
        f"request {rid} slice {name!r} unbalanced: {phs}"
    assert phs == ["b", "e"] * (len(phs) // 2), \
        f"request {rid} slice {name!r} does not alternate: {phs}"
    for b, e in zip(evs[::2], evs[1::2]):
      assert b["ts"] <= e["ts"]
    # queued happens once; only execute may re-open (retries/bisection)
    if name == "queued":
      assert phs == ["b", "e"], \
          f"request {rid} queued slice re-opened: {phs}"


# ---------------------------------------------------------------------------
# flight recorder mechanics
# ---------------------------------------------------------------------------


def test_ring_bounds_memory_and_reports_drops():
  rec = FlightRecorder(capacity=10, clock=FakeClock())
  for i in range(25):
    rec.instant(f"ev{i}")
  st = rec.stats()
  assert st["live"] == 10 and st["recorded"] == 25 and st["dropped"] == 15
  # oldest events fell off the back, newest survived
  assert [ev["name"] for ev in rec.events()] == \
      [f"ev{i}" for i in range(15, 25)]
  rec.clear()
  assert rec.stats() == {"enabled": True, "capacity": 10, "recorded": 0,
                         "live": 0, "dropped": 0}


def test_disabled_recorder_records_nothing():
  rec = FlightRecorder(capacity=16, clock=FakeClock(), enabled=False)
  rec.request_begin(1, kind="mmo", op="mma", tenant="t")
  rec.request_picked(1)
  rec.request_end(1, "done", executing=True)
  rec.request_rejected(2, "queue_full", kind="mmo", op="mma", tenant="t")
  rec.batch_complete(label="b", scheduled_s=0.0, stacked_s=0.1,
                     executed_s=0.2, device_s=0.3, completed_s=0.4,
                     backend="xla", schedule="local", batch=1, padded=1,
                     h2d_bytes=0, cache_hit=True, request_ids=[1],
                     arrivals_s=[0.0])
  rec.instant("nope")
  assert rec.stats()["recorded"] == 0 and rec.events() == []


def test_recorder_rejects_nonpositive_capacity():
  with pytest.raises(ValueError):
    FlightRecorder(capacity=0)


def test_lifecycle_timestamps_come_from_injected_clock():
  """Spans stamp the engine clock in microseconds — a synthetic clock gives
  exact, deterministic traces."""
  clock = FakeClock(1.0)
  rec = FlightRecorder(clock=clock)
  rec.request_begin(7, kind="closure", op="minplus", tenant="alpha")
  clock.t = 1.5
  rec.request_picked(7)
  clock.t = 2.25
  rec.request_end(7, "done", executing=True)
  evs = rec.events()
  assert [ev["ts"] for ev in evs] == [1.0e6, 1.5e6, 1.5e6, 2.25e6]
  _assert_balanced(evs)
  begin = evs[0]
  assert begin["args"] == {"kind": "closure", "op": "minplus",
                           "tenant": "alpha"}
  assert evs[-1]["args"]["outcome"] == "done"


def test_batch_complete_emits_phases_requests_and_iterations():
  rec = FlightRecorder(clock=FakeClock())
  rec.request_begin(1, kind="closure", op="minplus", tenant="t", t_s=0.0)
  rec.request_begin(2, kind="closure", op="minplus", tenant="t", t_s=0.1)
  rec.batch_complete(label="closure/minplus/16/float32",
                     scheduled_s=1.0, stacked_s=1.1, executed_s=1.3,
                     device_s=1.7, completed_s=1.8, backend="xla",
                     schedule="local", batch=2, padded=2, h2d_bytes=2048,
                     cache_hit=True, request_ids=[1, 2],
                     arrivals_s=[0.0, 0.1], iterations=[3, 5])
  evs = rec.events()
  _assert_balanced(evs)
  phases = {ev["name"]: ev for ev in evs if ev["ph"] == "X"}
  assert set(phases) == {"pad_and_stack", "resolve_compile",
                         "device_compute", "split_results"}
  assert phases["pad_and_stack"]["ts"] == pytest.approx(1.0e6)
  assert phases["pad_and_stack"]["dur"] == pytest.approx(0.1e6)
  assert phases["resolve_compile"]["args"]["cache"] == "hit"
  assert phases["device_compute"]["dur"] == pytest.approx(0.4e6)
  assert phases["device_compute"]["args"]["iterations"] == [3, 5]
  assert phases["split_results"]["dur"] == pytest.approx(0.1e6)
  # per-request completion args carry the measured latency
  done = [ev for ev in evs if ev.get("cat") == "request"
          and ev["ph"] == "e" and ev["name"] == "execute"]
  assert {ev["id"]: ev["args"]["latency_ms"] for ev in done} == \
      {1: pytest.approx(1800.0), 2: pytest.approx(1700.0)}


def _parent_and_children(evs, parent, children):
  """The first ``parent`` X-span and the named X-spans emitted before it,
  in emission order."""
  xs = [ev for ev in evs if ev["ph"] == "X"]
  i = next(k for k, ev in enumerate(xs) if ev["name"] == parent)
  kids = [ev for ev in xs[:i] if ev["name"] in children]
  return xs[i], kids


def test_batch_children_partition_their_parents():
  """batch_dispatch + batch_wait tile device_compute exactly; batch_d2h
  opens split_results; each child comes before its parent."""
  rec = FlightRecorder(clock=FakeClock())
  rec.batch_complete(label="b", scheduled_s=1.0, stacked_s=1.25,
                     executed_s=1.5, device_s=2.5, completed_s=3.0,
                     backend="xla", schedule="local", batch=1, padded=1,
                     h2d_bytes=0, cache_hit=True, request_ids=[1],
                     arrivals_s=[0.0], dispatched_s=1.75, fetched_s=2.75)
  evs = rec.events()
  dev, kids = _parent_and_children(evs, "device_compute",
                                   ("batch_dispatch", "batch_wait"))
  assert [k["name"] for k in kids] == ["batch_dispatch", "batch_wait"]
  assert kids[0]["ts"] == dev["ts"] == 1.5e6
  assert kids[0]["ts"] + kids[0]["dur"] == kids[1]["ts"] == 1.75e6
  assert kids[1]["ts"] + kids[1]["dur"] == dev["ts"] + dev["dur"] == 2.5e6
  split, kids = _parent_and_children(evs, "split_results", ("batch_d2h",))
  assert kids[0]["ts"] == split["ts"] == 2.5e6
  assert kids[0]["dur"] == pytest.approx(0.25e6)
  # without the stamps the batch reads as before: four phases, no children
  rec.clear()
  rec.batch_complete(label="b", scheduled_s=1.0, stacked_s=1.25,
                     executed_s=1.5, device_s=2.5, completed_s=3.0,
                     backend="xla", schedule="local", batch=1, padded=1,
                     h2d_bytes=0, cache_hit=True, request_ids=[],
                     arrivals_s=[])
  assert [ev["name"] for ev in rec.events()] == [
      "pad_and_stack", "resolve_compile", "device_compute", "split_results"]


def _dispatch_args(mesh):
  """The ``batch_dispatch`` spans' args of three APSP requests served as one
  batch, by an engine on ``mesh`` (dp) or with none."""
  kw = {} if mesh is None else dict(mesh=mesh, schedule="dp",
                                    shard_flops=0.0)
  eng = MMOEngine(backend="xla", max_batch=4, **kw)
  for i in range(3):
    eng.submit(_apsp_req(10, seed=i))
  eng.run_until_idle()
  return [ev.get("args") for ev in eng.tracer.events()
          if ev["name"] == "batch_dispatch"]


def test_batch_dispatch_of_a_dp_batch_carries_its_placement():
  """A mesh-placed batch's ``batch_dispatch`` span says how it was laid out:
  schedule, padded size, live slots, devices holding one; a batch of an
  engine without a mesh keeps the span without args."""
  from repro.core.distributed import make_mesh
  assert _dispatch_args(make_mesh((1, 1))) == [
      {"schedule": "dp", "rb": 4, "live": 3, "chips_live": 1}]
  assert _dispatch_args(None) == [None]


def test_children_share_their_parents_edges_to_the_last_bit():
  """ts + dur of a child equals its parent's wherever they share an end,
  so a gap inside both overlaps them equally and the first of the two in
  the emitted list, the child, names it (these edges round apart when the
  length is scaled from the unscaled difference)."""
  rec = FlightRecorder(clock=FakeClock())
  t, u = 45093.332211, 4028.40832
  rec.batch_complete(label="b", scheduled_s=t, stacked_s=t + 0.07,
                     executed_s=t + 0.071, device_s=t + 1.5,
                     completed_s=t + 1.53, backend="xla", schedule="local",
                     batch=1, padded=1, h2d_bytes=0, cache_hit=True,
                     request_ids=[], arrivals_s=[], dispatched_s=t + 0.08,
                     fetched_s=t + 1.52)
  rec.arena_tick("a", live=1, evicted=0, g=4, t0_s=u + 2.0, t1_s=u + 2.1,
                 launched_s=u + 2.001, flags_s=u + 2.1)
  end = {ev["name"]: ev["ts"] + ev["dur"] for ev in rec.events()}
  assert end["batch_wait"] == end["device_compute"]
  assert end["arena_wait"] == end["arena_tick"]


def test_arena_tick_phases_partition_the_tick():
  """arena_launch / arena_wait / arena_readout tile arena_tick exactly and
  precede it; readout and arena_finish appear only on a tick that evicts,
  and the tick span keeps its extent [t0, t1]."""
  rec = FlightRecorder(clock=FakeClock())
  rec.arena_tick("a", live=2, evicted=1, g=4, t0_s=1.0, t1_s=2.0,
                 launched_s=1.25, flags_s=1.5, finish=(2.0, 2.5),
                 done=[(7, 0, 3, 2.25)])
  evs = rec.events()
  tick, kids = _parent_and_children(
      evs, "arena_tick", ("arena_launch", "arena_wait", "arena_readout"))
  assert [k["name"] for k in kids] == ["arena_launch", "arena_wait",
                                       "arena_readout"]
  assert (tick["ts"], tick["dur"]) == (1.0e6, 1.0e6)
  edges = [kids[0]["ts"]] + [k["ts"] + k["dur"] for k in kids]
  assert edges == [1.0e6, 1.25e6, 1.5e6, 2.0e6]
  assert [k["ts"] for k in kids[1:]] == edges[1:-1]
  end = next(ev for ev in evs if ev["ph"] == "e")
  assert end["id"] == 7 and end["ts"] == 2.25e6
  assert end["args"] == {"outcome": "done", "slot": 0, "iterations": 3}
  finish = evs[-1]
  assert finish["name"] == "arena_finish"
  assert (finish["ts"], finish["dur"]) == (2.0e6, 0.5e6)

  rec.clear()
  rec.arena_tick("a", live=2, evicted=0, g=4, t0_s=1.0, t1_s=2.0,
                 launched_s=1.25, flags_s=1.5)
  evs = rec.events()
  assert [ev["name"] for ev in evs] == ["arena_launch", "arena_wait",
                                        "arena_tick"]
  # nothing read out: the wait runs to the end of the tick
  assert evs[1]["ts"] + evs[1]["dur"] == 2.0e6


def test_arena_admit_is_a_span_beside_the_slot_transition():
  rec = FlightRecorder(clock=FakeClock())
  rec.request_begin(3, kind="closure", op="orand", tenant="t", t_s=0.5)
  rec.arena_admit(3, slot=1, bucket="a", t0_s=1.0, t_s=1.5)
  evs = rec.events()
  _assert_balanced(evs + [{"cat": "request", "ph": "e", "id": 3,
                           "name": "execute", "ts": 2.0e6}])
  admit = [ev for ev in evs if ev["name"] == "arena_admit"]
  assert len(admit) == 1 and admit[0]["ph"] == "X"
  assert (admit[0]["ts"], admit[0]["dur"]) == (1.0e6, 0.5e6)
  # the execute slice opens where the admission span ends
  begin = next(ev for ev in evs if ev["ph"] == "b" and ev["name"] == "execute")
  assert begin["ts"] == 1.5e6 and begin["args"] == {"bucket": "a", "slot": 1}


def test_compile_span_once_per_cache_miss_never_on_a_hit():
  import jax.numpy as jnp
  rec = FlightRecorder()
  cache = ExecutableCache(recorder=rec)
  arg = np.zeros((4,), np.float32)
  for _ in range(3):
    cache.get_or_compile("double", lambda: (lambda x: 2 * x), (arg,),
                         label="double/4")
  cache.get_or_compile(("neg", 4), lambda: jnp.negative, (arg,))
  spans = [ev for ev in rec.events() if ev["name"] == "compile"]
  assert cache.misses == 2 and len(spans) == 2
  assert [ev["args"]["key"] for ev in spans] == ["double/4", "('neg', 4)"]
  assert all(ev["ph"] == "X" and ev["args"]["seconds"] > 0 for ev in spans)
  # a disabled recorder, or none, leaves no span
  quiet = FlightRecorder(enabled=False)
  ExecutableCache(recorder=quiet).get_or_compile(
      "double", lambda: (lambda x: 2 * x), (arg,))
  assert quiet.events() == []


def test_compile_span_names_the_vpu_kernel_geometry():
  """A compile of a VPU-ring Pallas program records the kernel's geometry
  (the same one ``block_geometry`` picks for the bucket); an MXU ring's
  program, whose kernel states none, records no ``kernels`` arg."""
  import importlib
  sm = importlib.import_module("repro.kernels.semiring_mmo")
  engine = MMOEngine(backend="pallas", max_batch=1)
  a = RNG.standard_normal((12, 12)).astype(np.float32)
  futs = [engine.submit(mmo_request(a, a, op=op)) for op in ("minplus",
                                                              "mma")]
  engine.run_until_idle()
  for f in futs:
    f.result()
  spans = {ev["args"]["key"].split("/")[1]: ev["args"]
           for ev in engine.tracer.events() if ev["name"] == "compile"}
  assert set(spans) == {"minplus", "mma"}
  m, k, n = contract_shape(request_bucket(mmo_request(a, a, op="minplus")))
  g = sm.block_geometry("minplus", m, k, n)
  assert spans["minplus"]["kernels"] == {
      "simd2_minplus": {"block": f"{g.bm}x{g.bn}x{g.bk}",
                        "strip": str(g.strip)}}
  assert "kernels" not in spans["mma"]


def test_loop_wait_only_when_the_loop_blocked():
  """Work queued before the loop starts is served without a wait; a
  request arriving while the loop sleeps ends one loop_wait span."""
  engine = MMOEngine(backend="xla", max_batch=4)
  futs = [engine.submit(_mmo_req()) for _ in range(2)]
  engine.start()
  for f in futs:
    f.result(timeout=60)
  engine.stop()
  assert not [ev for ev in engine.tracer.events()
              if ev["name"] == "loop_wait"]

  engine.tracer.clear()
  engine.start()
  import time
  time.sleep(0.2)  # the loop finds the queue empty and blocks
  t_submit = time.perf_counter()
  engine.submit(_mmo_req()).result(timeout=60)
  engine.stop()
  evs = engine.tracer.events()
  waits = [ev for ev in evs if ev["name"] == "loop_wait"]
  assert len(waits) == 1
  wait_end = waits[0]["ts"] + waits[0]["dur"]
  assert wait_end >= t_submit * 1e6 and waits[0]["dur"] > 0
  stack = next(ev for ev in evs if ev["name"] == "pad_and_stack")
  assert wait_end <= stack["ts"]


def test_gc_pause_spans_while_the_engine_runs():
  import gc
  engine = MMOEngine(backend="xla")
  engine.start()
  assert engine.tracer._gc_hook in gc.callbacks
  gc.collect()
  engine.stop()
  assert engine.tracer._gc_hook not in gc.callbacks
  spans = [ev for ev in engine.tracer.events() if ev["name"] == "gc_pause"]
  assert spans and spans[-1]["ph"] == "X"
  assert spans[-1]["args"]["generation"] == 2
  assert spans[-1]["args"]["collected"] >= 0
  before = len(engine.tracer.events())
  gc.collect()  # unhooked: nothing more is recorded
  assert len(engine.tracer.events()) == before
  # a recorder that is off never hooks in
  off = MMOEngine(backend="xla", trace=False)
  off.start()
  assert off.tracer._gc_hook not in gc.callbacks
  off.stop()


def test_gc_during_an_emission_neither_blocks_nor_loses_the_event():
  """A collection can start in a thread that holds the ring's lock (inside
  an emission): gc.collect() must return, and its span reach the ring once
  the lock is free."""
  import gc
  rec = FlightRecorder()
  rec.watch_gc()
  finished = threading.Event()

  def collect_under_the_lock():
    with rec._lock:
      gc.collect()
    finished.set()

  try:
    t = threading.Thread(target=collect_under_the_lock, daemon=True)
    t.start()
    t.join(timeout=30)
    assert finished.is_set(), "gc.collect() blocked on the recorder's lock"
  finally:
    rec.unwatch_gc()
  st = rec.stats()
  spans = [ev for ev in rec.events() if ev["name"] == "gc_pause"]
  assert spans and st["recorded"] == st["live"] >= len(spans)
  assert st["dropped"] == 0


def test_export_is_json_serializable_chrome_trace():
  rec = FlightRecorder(clock=FakeClock())
  rec.instant("hello", args={"k": 1})
  doc = json.loads(json.dumps(rec.export()))
  assert doc["displayTimeUnit"] == "ms"
  assert doc["traceEvents"][0] == {
      "ph": "M", "pid": 1, "name": "process_name",
      "args": {"name": "serve_mmo engine"}}
  assert doc["traceEvents"][1]["name"] == "hello"


# ---------------------------------------------------------------------------
# engine integration: one trace per request outcome
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served_engine():
  """One engine that served a small mixed workload (mmo + closure buckets),
  shared by the trace/exposition assertions below."""
  engine = MMOEngine(backend="xla", max_batch=4)
  futs = [engine.submit(r) for r in
          [_mmo_req(), _mmo_req(), _apsp_req(seed=1), _apsp_req(seed=2)]]
  engine.run_until_idle()
  for f in futs:
    assert f.done()
  return engine


def test_live_trace_is_balanced_and_loads_as_json(served_engine):
  doc = json.loads(json.dumps(served_engine.export_trace()))
  evs = doc["traceEvents"]
  _assert_balanced(evs)
  for ev in evs:
    if ev["ph"] == "X":
      assert ev["dur"] >= 0.0
  names = {ev["name"] for ev in evs}
  assert {"pad_and_stack", "resolve_compile", "device_compute",
          "split_results", "queued", "execute"} <= names
  # the closure batches ran a measured fixpoint → measured iteration counts
  # on the device span
  closure_devs = [ev for ev in evs if ev["name"] == "device_compute"
                  and "iterations" in ev.get("args", {})]
  assert closure_devs and all(
      min(ev["args"]["iterations"]) >= 1 for ev in closure_devs)
  # every completed request closed its execute slice with outcome=done
  done = [ev for ev in evs if ev.get("cat") == "request"
          and ev["ph"] == "e" and ev["name"] == "execute"]
  assert len(done) == 4
  assert all(ev["args"]["outcome"] == "done" for ev in done)


def test_trace_records_expired_requests():
  clock = FakeClock()
  engine = MMOEngine(backend="xla", clock=clock)
  fut = engine.submit(_mmo_req())
  doomed = _mmo_req()
  doomed.deadline_s = 0.5
  fut2 = engine.submit(doomed)
  clock.t = 2.0  # past the deadline before any batch runs
  engine.run_until_idle()
  assert fut.done()
  with pytest.raises(DeadlineExceededError):
    fut2.result(timeout=5)
  evs = engine.export_trace()["traceEvents"]
  _assert_balanced(evs)
  ends = {ev["id"]: ev["args"]["outcome"] for ev in evs
          if ev.get("cat") == "request" and ev["ph"] == "e"
          and "args" in ev}
  assert "expired" in ends.values() and "done" in ends.values()
  # the expired request never executed: its queued slice closed directly
  expired_id = next(i for i, o in ends.items() if o == "expired")
  assert (expired_id, "execute") not in _async_request_events(evs)


def test_trace_records_failed_batches():
  engine = MMOEngine(backend="xla")

  def boom(*a, **kw):
    raise RuntimeError("poisoned compile")

  engine.cache.get_or_compile = boom
  fut = engine.submit(_mmo_req())
  engine.run_until_idle()
  with pytest.raises(RuntimeError):
    fut.result(timeout=5)
  evs = engine.export_trace()["traceEvents"]
  _assert_balanced(evs)
  fails = [ev for ev in evs if ev.get("cat") == "request"
           and ev["ph"] == "e" and ev["name"] == "execute"]
  # one execute end per attempt: retried attempts close 'retried', the
  # terminal attempt closes 'failed' with the error
  assert fails
  assert all(ev["args"]["outcome"] == "retried" for ev in fails[:-1])
  assert fails[-1]["args"] == {"outcome": "failed", "error": "RuntimeError"}
  assert any(ev["name"] == "batch_fail" for ev in evs)


def test_trace_records_rejections_as_instants():
  engine = MMOEngine(backend="xla", max_queue=1)
  kept = engine.submit(_mmo_req())
  with pytest.raises(RejectedError):
    engine.submit(_mmo_req()).result(timeout=5)
  engine.run_until_idle()
  assert kept.done()
  evs = engine.export_trace()["traceEvents"]
  _assert_balanced(evs)
  rejects = [ev for ev in evs if ev["name"] == "reject"]
  assert len(rejects) == 1
  assert rejects[0]["ph"] == "i"
  assert rejects[0]["args"]["reason"] == "queue_full"


def test_trace_off_engine_records_nothing(served_engine):
  engine = MMOEngine(backend="xla", trace=False)
  fut = engine.submit(_mmo_req())
  engine.run_until_idle()
  assert fut.done()
  assert engine.tracer.stats()["recorded"] == 0
  assert len(engine.export_trace()["traceEvents"]) == 1  # metadata only
  # ...and the exposition still renders, advertising tracing as off
  text = render_prometheus(engine.observability_state())
  assert "serve_trace_enabled 0" in text


# ---------------------------------------------------------------------------
# Prometheus exposition: grammar, histograms, golden rendering
# ---------------------------------------------------------------------------

_METRIC_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^{}]*)\})?"
    r" (?P<value>[^ ]+)$")
_LABEL_RE = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\["\\n])*"$')


def _parse_exposition(text: str):
  """Validate Prometheus text-format 0.0.4 line by line; returns
  (families, samples) where families maps name → type and samples is a list
  of (name, labels-dict, float-value)."""
  assert text.endswith("\n")
  families, helped, samples = {}, set(), []
  for line in text.splitlines():
    if line.startswith("# HELP "):
      name = line.split(" ", 3)[2]
      assert _METRIC_RE.match(name)
      assert name not in helped, f"duplicate HELP for {name}"
      helped.add(name)
    elif line.startswith("# TYPE "):
      _, _, name, mtype = line.split(" ", 3)
      assert _METRIC_RE.match(name)
      assert mtype in ("counter", "gauge", "histogram", "summary", "untyped")
      assert name not in families, f"duplicate TYPE for {name}"
      assert name in helped, f"TYPE for {name} precedes its HELP"
      families[name] = mtype
    else:
      m = _SAMPLE_RE.match(line)
      assert m, f"malformed sample line: {line!r}"
      labels = {}
      if m.group("labels"):
        for pair in re.split(r",(?=[a-zA-Z_])", m.group("labels")):
          assert _LABEL_RE.match(pair), f"malformed label: {pair!r}"
          k, v = pair.split("=", 1)
          labels[k] = v[1:-1]
      value = m.group("value")
      fval = {"+Inf": float("inf"), "-Inf": float("-inf")}.get(
          value, None)
      samples.append((m.group("name"), labels,
                      fval if fval is not None else float(value)))
  return families, samples


def test_live_exposition_parses_and_histograms_are_cumulative(served_engine):
  text = render_prometheus(served_engine.observability_state())
  families, samples = _parse_exposition(text)
  # every sample belongs to a declared family (histograms contribute
  # _bucket/_sum/_count children of the declared base name)
  for name, _, _ in samples:
    base = re.sub(r"_(bucket|sum|count)$", "", name)
    assert name in families or base in families, f"undeclared sample {name}"
  assert families["serve_submitted_total"] == "counter"
  assert families["serve_queue_depth"] == "gauge"
  assert families["serve_service_seconds"] == "histogram"
  by_name: dict = {}
  for name, labels, value in samples:
    by_name.setdefault(name, []).append((labels, value))
  assert by_name["serve_submitted_total"] == [({}, 4)]
  # per-(histogram, bucket-label) series: counts cumulative in le, and the
  # +Inf bucket equals _count
  hname = "serve_service_seconds"
  series: dict = {}
  for labels, value in by_name[f"{hname}_bucket"]:
    series.setdefault(labels["bucket"], []).append((labels["le"], value))
  counts = {labels["bucket"]: value
            for labels, value in by_name[f"{hname}_count"]}
  assert series and set(series) == set(counts)
  for blabel, buckets in series.items():
    values = [v for _, v in buckets]
    assert values == sorted(values), f"non-cumulative histogram {blabel}"
    assert dict(buckets)["+Inf"] == counts[blabel]
    # fixed fleet-wide boundaries: every series emits the same le labels
    assert len(buckets) == len(HISTOGRAM_BOUNDS_S) + 1


def test_exposition_includes_estimator_drift(served_engine):
  text = render_prometheus(served_engine.observability_state())
  _, samples = _parse_exposition(text)
  drift = [(labels, v) for name, labels, v in samples
           if name == "serve_estimator_drift_ratio"]
  assert drift, "served engine must report estimator drift cells"
  for labels, v in drift:
    assert {"bucket", "backend", "schedule"} <= set(labels)
    assert v > 0.0


def test_golden_exposition_rendering():
  """Pin the full rendered text for one synthetic state: any grammar change
  (family names, label sets, le spellings, ordering) shows up as a golden
  diff, not as a silently reshaped scrape."""
  q1 = [0] * 23
  q1[8], q1[10] = 3, 1
  s1 = [0] * 23
  s1[12] = 4
  q2 = [0] * 23
  q2[5] = 2
  state = {
      "metrics": {
          "uptime_s": 12.5,
          "counters": {"submitted": 9, "completed": 6, "rejected": 1,
                       "expired": 1, "failed": 1, "batches": 3,
                       "h2d_bytes": 4096, "retries": 3},
          "rejected_by_reason": {"queue_full": 1},
          "batch_failures_by_kind": {"execute": 2, "nonfinite": 1},
          "histogram_bounds_s": list(HISTOGRAM_BOUNDS_S),
          "buckets": {
              "closure/minplus/16/float32": {
                  "completed": 4, "expired": 1, "failed": 0,
                  "histograms": {"queue": (q1, 0.0421, 4),
                                 "service": (s1, 0.0631, 4)}},
              "mmo/mma/16x16x16/float32+float16": {
                  "completed": 2, "expired": 0, "failed": 1,
                  "histograms": {"queue": (q2, 0.0015, 2)}},
          },
      },
      "queue_depth": 2,
      "executing": 1,
      "admission": {"queued": 2, "backlog_s": 0.25, "evaluations": 9,
                    "inflight": {"alpha": 2, "beta": 1},
                    "rejections": {"queue_full": 1},
                    "limits": {"max_queue": 64, "tenant_quota": None,
                               "max_backlog_s": None}},
      "cache": {"executables": 5, "hits": 12, "misses": 5,
                "compile_s": 1.5},
      "scheduler": {"picks": 3, "pick_seconds": 0.004},
      "estimator_cells": [
          {"bucket": "closure/minplus/16/float32", "backend": "xla",
           "schedule": "local", "seconds": 0.002, "observations": 4,
           "drift": 1.25}],
      "breakers": [
          {"bucket": "closure/minplus/16/float32", "backend": "xla",
           "schedule": "local", "state": "open",
           "consecutive_failures": 5, "opens": 1, "closes": 0, "probes": 0},
          {"bucket": "closure/minplus/16/float32", "backend": "vector",
           "schedule": "local", "state": "closed",
           "consecutive_failures": 0, "opens": 0, "closes": 0, "probes": 1}],
      "trace": {"enabled": True, "capacity": 65536, "recorded": 120,
                "live": 120, "dropped": 0},
  }
  text = render_prometheus(state)
  _parse_exposition(text)  # golden must itself be grammatical
  golden_path = os.path.join(os.path.dirname(__file__), "data",
                             "golden_metrics.prom")
  with open(golden_path, encoding="utf-8") as f:
    assert text == f.read()


def test_log_histogram_drops_bogus_values():
  h = LogHistogram()
  for bad in (float("nan"), float("inf"), -1.0):
    h.add(bad)
  assert h.count == 0
  h.add(0.0)
  h.add(1e-5)   # at the first boundary → first bucket (le is inclusive)
  h.add(100.0)  # beyond the top bound → overflow slot
  counts, total, n = h.state()
  assert n == 3 and counts[0] == 2 and counts[-1] == 1
  assert total == pytest.approx(100.00001)


def test_escape_label_value():
  assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'


# ---------------------------------------------------------------------------
# metrics satellites: strict-JSON empty windows, mixed-dtype bucket labels
# ---------------------------------------------------------------------------


def test_empty_window_percentiles_are_null_not_nan():
  """A bucket created by on_expire alone has empty latency windows; its
  snapshot must be strict JSON (None → null), never bareword NaN."""
  assert RollingWindow().percentile(50) is None
  metrics = ServeMetrics()
  metrics.on_expire(request_bucket(_mmo_req()))
  snap = metrics.snapshot(queue_depth=0, executing=0)
  text = json.dumps(snap, allow_nan=False)  # raises on NaN/Inf
  (bucket,) = snap["buckets"].values()
  assert bucket["queue_ms"] == {"p50": None, "p99": None}
  assert json.loads(text)["counters"]["expired"] == 1


def test_bucket_label_spells_out_mixed_dtypes():
  uniform = BucketKey(kind="mmo", op="mma", shape=(16, 16, 16),
                      dtypes=("float32", "float32"), params=())
  mixed_a = BucketKey(kind="mmo", op="mma", shape=(16, 16, 16),
                      dtypes=("float32", "float16"), params=())
  mixed_b = BucketKey(kind="mmo", op="mma", shape=(16, 16, 16),
                      dtypes=("float32", "bfloat16"), params=())
  # historical single-dtype spelling for the uniform majority
  assert bucket_label(uniform) == "mmo/mma/16x16x16/float32"
  # two buckets differing only in a non-leading operand dtype cannot share
  # a label
  assert bucket_label(mixed_a) == "mmo/mma/16x16x16/float32+float16"
  assert bucket_label(mixed_a) != bucket_label(mixed_b)


# ---------------------------------------------------------------------------
# thread safety: snapshots + renders + trace exports against a live engine
# ---------------------------------------------------------------------------


def test_concurrent_observability_reads_during_serving():
  """Hammer every observability read path from 8 threads while the engine
  serves on its background loop: no exceptions, every read parseable, all
  traffic completes."""
  engine = MMOEngine(backend="xla", max_batch=4)
  reqs = [_mmo_req() for _ in range(12)] + \
         [_apsp_req(seed=s) for s in range(4)]
  engine.prewarm(reqs)
  engine.start()
  errs = []
  futures = []
  barrier = threading.Barrier(8)

  def submitter(i):
    try:
      barrier.wait()
      for r in reqs[i::4]:
        futures.append(engine.submit(r))
    except Exception as e:  # noqa: BLE001
      errs.append(e)

  def reader(i):
    try:
      barrier.wait()
      for _ in range(25):
        json.dumps(engine.metrics_snapshot(), default=float,
                   allow_nan=False)
        _parse_exposition(render_prometheus(engine.observability_state()))
        json.dumps(engine.export_trace())
    except Exception as e:  # noqa: BLE001
      errs.append(e)

  threads = [threading.Thread(target=submitter, args=(i,)) for i in range(4)]
  threads += [threading.Thread(target=reader, args=(i,)) for i in range(4)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  engine.stop()
  assert not errs
  assert len(futures) == len(reqs) and all(f.done() for f in futures)
  _assert_balanced(engine.export_trace()["traceEvents"])


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------


def test_http_endpoint_serves_all_routes(served_engine):
  with ObservabilityServer(served_engine, port=0) as srv:
    assert srv.port != 0

    def get(path):
      with urllib.request.urlopen(f"{srv.url}{path}", timeout=10) as resp:
        return resp.status, resp.headers.get("Content-Type"), \
            resp.read().decode("utf-8")

    status, ctype, body = get("/metrics")
    assert status == 200 and ctype == PROMETHEUS_CONTENT_TYPE
    families, _ = _parse_exposition(body)
    assert "serve_completed_total" in families

    status, ctype, body = get("/healthz")
    assert status == 200 and ctype == "application/json"
    health = json.loads(body)
    assert health["status"] == "ok" and health["pending"] == 0

    status, _, body = get("/snapshot")
    assert status == 200
    assert json.loads(body)["counters"]["completed"] == 4

    status, _, body = get("/trace")
    assert status == 200
    _assert_balanced(json.loads(body)["traceEvents"])

    with pytest.raises(urllib.error.HTTPError) as err:
      get("/nope")
    assert err.value.code == 404


# ---------------------------------------------------------------------------
# launch driver: the metrics ticker must never write to stdout
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_launch_metrics_ticker_goes_to_stderr(tmp_path):
  env = dict(os.environ, PYTHONPATH="src")
  proc = subprocess.run(
      [sys.executable, "-m", "repro.launch.serve_mmo", "--rate", "30",
       "--duration", "1.5", "--sizes", "12", "--max-batch", "4",
       "--metrics-every", "0.3", "--trace-out",
       str(tmp_path / "trace.json")],
      capture_output=True, text=True, timeout=600, env=env,
      cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
  assert proc.returncode == 0, proc.stderr
  assert "[serve_mmo][metrics]" not in proc.stdout
  ticks = [l for l in proc.stderr.splitlines()
           if l.startswith("[serve_mmo][metrics] ")]
  assert ticks, "ticker produced no stderr snapshots"
  for line in ticks:
    snap = json.loads(line.split(" ", 1)[1])
    assert "counters" in snap and "queue_depth" in snap
  trace = json.loads((tmp_path / "trace.json").read_text())
  _assert_balanced(trace["traceEvents"])
