"""Required time of the window's work at one chip's peak over the device
time of the per-iteration semiring kernel (``simd2_<ring>`` ops, the
megakernel's ``simd2_fixpoint_`` excluded) summed over the chips, in %."""
from bench import layers


def read(run):
  return layers.kernel_roofline_pct(run, r"simd2_(?!fixpoint_)")
