"""Published peaks per chip, keyed by JAX's ``device_kind``.

Every semiring is held to the matrix unit's bf16 peak: that is the rate a
SIMD² unit built on the MXU would give every ring (the paper's claim), so a
share of it reads how far a ring is from that hardware.  A device missing
from the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
  flops_per_s: float
  hbm_bytes_per_s: float
  source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9,
                        "Google Cloud documentation, TPU v5e: 197 TFLOP/s "
                        "bf16, 819 GB/s HBM per chip"),
}


def peak_for(device_kind: str) -> Peak:
  try:
    return PEAKS[device_kind]
  except KeyError:
    raise KeyError(f"no published peak for device kind {device_kind!r}; "
                   f"known: {sorted(PEAKS)}") from None
