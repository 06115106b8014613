"""``bench/trace_reduce.py`` on a trace recorded on one v5e chip: two n=4096
closures (minplus, then maxplus) served by the per-iteration Pallas path."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "paths4096.xplane.pb"


@pytest.fixture(scope="module")
def profile():
  return tr.load(str(TRACE))


def test_op_name():
  assert tr.op_name("%simd2_fixpoint_orand.1 = (f32[8,1024,1024]) "
                    "custom-call(s32[8] %a)") == "simd2_fixpoint_orand"
  assert tr.op_name("%simd2_minplus.3 = f32[4096,4096] custom-call(...)") \
      == "simd2_minplus"
  assert tr.op_name("%copy-done.1 = f32[8] copy-done(...)") == "copy-done"
  assert tr.op_name("%while.4 = (f32[1,4096,4096]) while(...)") == "while"
  assert tr.op_name("%dynamic-slice_bitcast_fusion = f32[] fusion(...)") \
      == "dynamic-slice_bitcast_fusion"


def test_union_and_self_times():
  assert tr.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
  assert tr.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]
  own = {n: s for _, _, n, s in tr.self_times(
      [(0, 10, "while"), (1, 4, "k"), (5, 9, "k2"), (11, 12, "x")])}
  assert own == {"while": 3, "k": 3, "k2": 4, "x": 1}


def test_anchor_and_planes(profile):
  assert tr.anchor_ns(profile) == 39156028.0
  ops = tr.device_ops(profile)
  assert list(ops) == [0] and len(ops[0]) == 50
  assert tr.device_ops(profile, {1}) == {}


def test_reduce_pins_busy_ops_and_gaps(profile):
  base = tr.anchor_ns(profile) * 1e-9
  r = tr.reduce(profile, t0_s=0.0, t1_s=3.2, anchor_perf_s=base)
  assert r.window_s == pytest.approx(3.2)
  assert r.busy_s[0] == pytest.approx(2.728389838, rel=1e-9)
  assert r.idle_share() == pytest.approx(0.147378176, rel=1e-6)
  assert r.op_seconds["simd2_minplus"] == pytest.approx(1.635661225, rel=1e-9)
  assert r.op_seconds["simd2_maxplus"] == pytest.approx(1.090458588, rel=1e-9)
  assert r.op_seconds["while"] == pytest.approx(1.5137e-05, rel=1e-3)
  assert r.kernel_seconds(r"simd2_(?!fixpoint_)") == pytest.approx(
      1.635661225 + 1.090458588, rel=1e-9)
  assert r.kernel_seconds(r"simd2_fixpoint_") == 0.0
  # busy time is the union: self times add up to it
  assert sum(r.op_seconds.values()) == pytest.approx(r.busy_s[0], rel=1e-6)
  longest = sorted((g1 - g0 for g0, g1 in r.gaps), reverse=True)
  assert longest[0] == pytest.approx(0.2038975, rel=1e-6)
  assert longest[1] == pytest.approx(0.20077728, rel=1e-6)
  assert sum(longest) == pytest.approx(3.2 - r.busy_s[0], rel=1e-9)


def test_window_clips_and_gaps_are_attributed(profile):
  base = tr.anchor_ns(profile) * 1e-9
  r = tr.reduce(profile, t0_s=1.0, t1_s=2.0, anchor_perf_s=base)
  # minplus runs until 1.8408 s, maxplus starts at 2.0417 s
  assert r.busy_s[0] == pytest.approx(0.840881673, rel=1e-6)
  assert max(g1 - g0 for g0, g1 in r.gaps) == pytest.approx(0.159118327,
                                                           rel=1e-6)
  gaps = tr.attribute_gaps([(0.0, 0.2), (0.5, 0.55)],
                           [(0.1, 0.3, "pad_and_stack"),
                            (0.0, 0.05, "split_results")])
  assert gaps == [["pad_and_stack", pytest.approx(0.2)],
                  ["outside_spans", pytest.approx(0.05)]]


def test_a_trace_without_the_anchor_is_an_error(profile):
  with pytest.raises(ValueError):
    tr.anchor_ns(profile, name="no_such_anchor")
