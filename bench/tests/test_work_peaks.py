"""Required work and the peak table."""
import pytest

from bench import peaks, work


def test_required_work_of_a_closure():
  assert work.closure_ops(1024, 6) == 6 * 2 * 1024 ** 3
  assert work.closure_bytes(4096, 4) == 4 * 2 * 4096 ** 2
  v5e = peaks.peak_for("TPU v5 lite")
  # compute bound: 12 squarings of n = 4096 at 197 TFLOP/s
  assert work.required_seconds(4096, 12, 4, v5e) == pytest.approx(
      12 * 2 * 4096 ** 3 / 197e12)
  # memory bound: a closure that took no squaring still reads and writes
  assert work.required_seconds(4096, 0, 4, v5e) == pytest.approx(
      4 * 2 * 4096 ** 2 / 819e9)


def test_peak_table_is_keyed_by_device_kind():
  p = peaks.peak_for("TPU v5 lite")
  assert (p.flops_per_s, p.hbm_bytes_per_s) == (197e12, 819e9)
  assert "Google Cloud" in p.source
  for kind in ("cpu", "TPU v4", "TPU v5"):
    with pytest.raises(KeyError):
      peaks.peak_for(kind)
