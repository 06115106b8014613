"""The per-layer readers on a hand-made run: flight-recorder events, a
reduced trace and two answered requests."""
import types

import numpy as np
import pytest

from bench import layers, peaks, spec, trace_reduce, work


def _served(rid, n, sent, done, iterations, dtype):
  return types.SimpleNamespace(
      request=types.SimpleNamespace(request_id=rid), n=n, app="x",
      adj=np.zeros((1, 1), dtype), sent_s=sent, done_s=done, outcome="done",
      future=object(), result=types.SimpleNamespace(
          extras={"iterations": iterations}))


@pytest.fixture
def run():
  events = [
      {"cat": "request", "ph": "b", "id": 1, "name": "queued", "ts": 1.0e6},
      {"cat": "request", "ph": "b", "id": 1, "name": "execute", "ts": 1.5e6},
      {"cat": "request", "ph": "b", "id": 2, "name": "queued", "ts": 2.0e6},
      {"cat": "request", "ph": "b", "id": 2, "name": "execute", "ts": 2.5e6},
      {"ph": "X", "name": "arena_tick", "ts": 1.5e6, "dur": 2e3},
      {"ph": "X", "name": "arena_tick", "ts": 2.5e6, "dur": 4e3},
      {"ph": "X", "name": "pad_and_stack", "ts": 9.0e6, "dur": 1e3},
  ]
  trace = trace_reduce.Reduced(window_s=4.0, busy_s={0: 1.0},
                               op_seconds={"simd2_fixpoint_orand": 0.5,
                                           "simd2_minplus": 0.25,
                                           "copy-done": 0.25},
                               gaps=[])
  # request 1 is served wholly inside [1, 5]; request 2's service
  # [2.5, 6.5] lies 2.5 of its 4 seconds inside
  served = [_served(1, 1024, 1.0, 2.0, 6, bool),
            _served(2, 4096, 2.0, 6.5, 12, np.float32)]
  return types.SimpleNamespace(served=served, events=events, t0=1.0, t1=5.0,
                               trace=trace, peak=peaks.peak_for("TPU v5 lite"))


def test_queue_and_span_readers(run):
  assert layers.queue_ms(run) == pytest.approx(500.0)
  assert layers.span_mean_ms(run, ("arena_tick",)) == pytest.approx(3.0)
  assert layers.span_mean_ms(run, ("split_results",)) is None
  # the pad_and_stack span starts after the window: nothing to read
  assert layers.host_ms_per_request(run, ("pad_and_stack",)) is None


def test_required_work_is_prorated_to_the_window(run):
  v5e = run.peak
  want = (work.required_seconds(1024, 6, 1, v5e)
          + 0.625 * work.required_seconds(4096, 12, 4, v5e))
  assert layers.required_seconds(run) == pytest.approx(want)
  assert layers.busy_mfu_pct(run) == pytest.approx(100 * want / 1.0)
  assert layers.kernel_roofline_pct(run, r"simd2_fixpoint_") == \
      pytest.approx(100 * want / 0.5)
  assert layers.kernel_roofline_pct(run, r"simd2_(?!fixpoint_)") == \
      pytest.approx(100 * want / 0.25)
  assert layers.kernel_roofline_pct(run, r"no_such_kernel") is None
  assert layers.idle_pct(run) == pytest.approx(75.0)


def test_every_metric_file_reads_the_hand_made_run(run):
  for name in ("queue_ms.arena", "tick_ms.arena", "idle.closures1024",
               "mfu.closures1024", "simd2_fixpoint_roofline.closures1024",
               "simd2_roofline.paths4096"):
    assert spec.metric_reader(name)(run) > 0
