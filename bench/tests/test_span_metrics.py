"""The readers of the flight recorder's phase and runtime spans on a
hand-made run: each returns its value, and None when its spans are
absent (as a program without them gives)."""
import types

import pytest

from bench import spec, trace_reduce

C1 = ("admit_ms.arena", "readout_ms.arena", "gc_ms.closures1024",
      "host_bound.closures1024", "compile_s.closures1024")
C2 = ("dispatch_ms.paths4096", "d2h_ms.paths4096", "host_bound.paths4096",
      "compile_s.paths4096")


def _x(name, t0, t1, **args):
  ev = {"ph": "X", "name": name, "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6}
  if args:
    ev["args"] = args
  return ev


def _done(rid, done_s):
  return types.SimpleNamespace(request=types.SimpleNamespace(request_id=rid),
                               future=object(), outcome="done",
                               done_s=done_s)


@pytest.fixture
def run():
  """Window [10, 20] s; two requests answered in it; device idle over
  [10, 12], [14, 15] and [18, 20]; the loop waited over [11, 14.5] and
  [19, 25], so the gaps straddle both edges of each wait."""
  events = [
      _x("compile", 1.0, 3.0, key="a", seconds=2.0),
      _x("compile", 4.0, 4.5, key="b", seconds=0.5),
      _x("compile", 12.0, 13.0, key="c", seconds=1.0),  # in the window
      _x("arena_admit", 10.5, 10.502),
      _x("arena_admit", 12.5, 12.504),
      _x("arena_launch", 13.0, 13.001),
      _x("arena_readout", 13.01, 13.02, evicted=1),
      _x("arena_readout", 16.0, 16.03, evicted=1),
      _x("batch_dispatch", 11.0, 11.2),
      _x("batch_dispatch", 15.0, 15.1),
      _x("batch_d2h", 12.0, 12.05),
      _x("gc_pause", 9.0, 9.5, generation=2, collected=0),  # before
      _x("gc_pause", 14.0, 14.01, generation=0, collected=3),
      _x("gc_pause", 17.0, 17.03, generation=2, collected=9),
      _x("loop_wait", 11.0, 14.5),
      _x("loop_wait", 19.0, 25.0),
  ]
  trace = trace_reduce.Reduced(window_s=10.0, busy_s={0: 5.0},
                               op_seconds={},
                               gaps=[(10.0, 12.0), (14.0, 15.0),
                                     (18.0, 20.0)])
  return types.SimpleNamespace(served=[_done(1, 13.0), _done(2, 16.5)],
                               events=events, t0=10.0, t1=20.0, trace=trace)


def _read(name, run):
  return spec.metric_reader(name)(run)


def test_arena_and_batching_span_readers(run):
  assert _read("admit_ms.arena", run) == pytest.approx(3.0)
  # 10 ms + 30 ms of readout over two answered requests
  assert _read("readout_ms.arena", run) == pytest.approx(20.0)
  assert _read("dispatch_ms.paths4096", run) == pytest.approx(150.0)
  assert _read("d2h_ms.paths4096", run) == pytest.approx(25.0)


def test_gc_ms_counts_pauses_starting_in_the_window(run):
  assert _read("gc_ms.closures1024", run) == pytest.approx(4.0)


@pytest.mark.parametrize("name", ["host_bound.closures1024",
                                  "host_bound.paths4096"])
def test_host_bound_leaves_out_idle_time_the_loop_waited(run, name):
  # idle outside loop_wait: [10, 11] + [14.5, 15] + [18, 19] = 2.5 s of 10
  assert _read(name, run) == pytest.approx(25.0)


def test_host_bound_is_the_whole_idle_share_when_waits_miss_the_gaps(run):
  run.events = [e for e in run.events if e["name"] != "loop_wait"]
  run.events.append(_x("loop_wait", 30.0, 31.0))
  assert _read("host_bound.closures1024", run) == pytest.approx(50.0)


@pytest.mark.parametrize("name", ["compile_s.closures1024",
                                  "compile_s.paths4096"])
def test_compile_s_sums_the_set_up_compiles(run, name):
  assert _read(name, run) == pytest.approx(2.5)


@pytest.mark.parametrize("name", C1 + C2)
def test_every_reader_is_silent_without_its_spans(run, name):
  run.events = [e for e in run.events if e["name"] in ("arena_tick",)]
  assert _read(name, run) is None


def test_existing_span_readers_keep_their_spans(run):
  """The phases nest inside the spans the accepted readers read; those
  readers still see the parents alone."""
  run.events += [_x("arena_tick", 13.0, 13.02), _x("pad_and_stack", 11.0,
                                                   11.1),
                 _x("split_results", 12.0, 12.1)]
  assert _read("tick_ms.arena", run) == pytest.approx(20.0)
  assert _read("host_ms.paths4096", run) == pytest.approx(100.0)


def test_the_innermost_phase_names_a_gap_inside_it():
  """Spans as the flight recorder emits them: a gap that lies inside both
  ``batch_wait`` and its parent ``device_compute`` (which share their end)
  is named by the child, as is one inside ``arena_wait``."""
  from repro.serve_mmo.observability import FlightRecorder
  rec = FlightRecorder()
  # edges on which the child's and the parent's ends, each taken as
  # start + length, would round apart
  t, u = 45093.332211, 4028.40832
  rec.batch_complete(label="b", scheduled_s=t, stacked_s=t + 0.07,
                     executed_s=t + 0.071, device_s=t + 1.5,
                     completed_s=t + 1.53, backend="pallas",
                     schedule="local", batch=1, padded=1, h2d_bytes=0,
                     cache_hit=True, request_ids=[], arrivals_s=[],
                     dispatched_s=t + 0.08, fetched_s=t + 1.52)
  rec.arena_tick("a", live=1, evicted=0, g=4, t0_s=u + 2.0,
                 t1_s=u + 2.1, launched_s=u + 2.001, flags_s=u + 2.1)
  spans = [(e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6, e["name"])
           for e in rec.events() if e["ph"] == "X"]
  gaps = [(t + 1.4, t + 1.5), (u + 2.05, u + 2.1)]
  assert [g[0] for g in trace_reduce.attribute_gaps(gaps, spans)] == [
      "batch_wait", "arena_wait"]
