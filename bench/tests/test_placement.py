"""The four-chip cell's placement readers on a hand-made run: each returns
its value, and None where the run holds nothing to read (a program whose
``batch_dispatch`` spans carry no placement, a trace of one device)."""
import types

import pytest

from bench import spec, trace_reduce


def _dispatch(t0, **args):
  ev = {"ph": "X", "name": "batch_dispatch", "ts": t0 * 1e6, "dur": 1e3}
  if args:
    ev["args"] = args
  return ev


def _run(events, busy):
  trace = trace_reduce.Reduced(window_s=10.0, busy_s=busy, op_seconds={},
                               gaps=[])
  return types.SimpleNamespace(served=[], events=events, t0=10.0, t1=20.0,
                               trace=trace)


def _read(name, run):
  return spec.metric_reader(name)(run)


def test_dp_fill_counts_live_over_all_dp_slots_in_the_window():
  run = _run([
      _dispatch(9.0, schedule="dp", rb=4, live=4, chips_live=4),   # before
      _dispatch(11.0, schedule="dp", rb=4, live=1, chips_live=1),
      _dispatch(12.0, schedule="dp", rb=8, live=5, chips_live=3),
      _dispatch(13.0, schedule="summa", rb=2, live=2, chips_live=4),
      _dispatch(14.0),                                             # local
  ], {0: 1.0})
  assert _read("dp_fill.dp4", run) == pytest.approx(100.0 * 6 / 12)


def test_dp_fill_is_silent_without_placement_args():
  assert _read("dp_fill.dp4", _run([_dispatch(11.0)], {0: 1.0})) is None


def test_chip_balance_is_least_over_most_busy_device():
  busy = {0: 4.0, 1: 2.0, 2: 1.0, 3: 3.0}
  assert _read("chip_balance.dp4", _run([], busy)) == pytest.approx(25.0)
  assert _read("chip_balance.dp4", _run([], {0: 4.0})) is None
  assert _read("chip_balance.dp4", _run([], {0: 0.0, 1: 0.0})) is None
