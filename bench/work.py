"""The work a closure request requires, from its logical shape alone.

A closure of an n-vertex graph that took ``iterations`` squarings does
``iterations · 2n³`` ⊕/⊗ operations (one ⊗ and one ⊕ per term of each
n×n×n semiring product) and must at least read its adjacency once and write
its closure once: ``itemsize · 2n²`` bytes.  Padding to a bucket and work on
idle slots are not required work, so a kernel that stops doing them reads
closer to its roofline, and one that replaces the kernel is measured
against the same work.
"""
from __future__ import annotations

from bench.peaks import Peak


def closure_ops(n: int, iterations: int) -> float:
  return float(iterations) * 2.0 * float(n) ** 3


def closure_bytes(n: int, itemsize: int) -> float:
  return float(itemsize) * 2.0 * float(n) ** 2


def required_seconds(n: int, iterations: int, itemsize: int,
                     peak: Peak) -> float:
  """The least time the chip could take: the larger of the compute bound
  and the memory bound."""
  return max(closure_ops(n, iterations) / peak.flops_per_s,
             closure_bytes(n, itemsize) / peak.hbm_bytes_per_s)
