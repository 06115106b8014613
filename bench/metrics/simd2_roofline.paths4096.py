"""Required time of the window's work at the chip's peak over the device
time of the per-iteration semiring kernel (``simd2_<ring>`` ops), in %."""
from bench import layers


def read(run):
  return layers.kernel_roofline_pct(run, r"simd2_(?!fixpoint_)")
