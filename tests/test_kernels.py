"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per the deliverable: each kernel swept over shapes/dtypes and
assert_allclose'd against ref.py.
"""
import importlib
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.semiring import ALL_OPS, contraction_pads
from repro.core.semiring import get as get_sr
from repro.kernels import flash_attention, semiring_mmo
from repro.kernels.ref import attention_ref, semiring_mmo_ref

sm = importlib.import_module("repro.kernels.semiring_mmo")

RNG = np.random.default_rng(1)

MMO_SHAPES = [(128, 128, 128), (64, 200, 96), (13, 7, 5), (256, 384, 128),
              (1, 128, 1)]


@pytest.mark.parametrize("op", ALL_OPS)
@pytest.mark.parametrize("shape", MMO_SHAPES)
def test_semiring_kernel(op, shape):
  m, k, n = shape
  a = RNG.standard_normal((m, k)).astype(np.float32)
  b = RNG.standard_normal((k, n)).astype(np.float32)
  c = RNG.standard_normal((m, n)).astype(np.float32)
  if op == "orand":
    a, b, c = a > 0.8, b > 0.8, c > 1.5
  got = semiring_mmo(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c), op=op,
                     interpret=True)
  ref = semiring_mmo_ref(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c),
                         op=op)
  np.testing.assert_allclose(np.asarray(got, np.float64),
                             np.asarray(ref, np.float64),
                             rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("op", ["mma", "minplus", "addnorm"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_semiring_kernel_dtypes(op, dtype):
  a = jnp.asarray(RNG.standard_normal((64, 96)), dtype)
  b = jnp.asarray(RNG.standard_normal((96, 32)), dtype)
  got = semiring_mmo(a, b, op=op, interpret=True)
  ref = semiring_mmo_ref(a, b, op=op)
  np.testing.assert_allclose(np.asarray(got, np.float64),
                             np.asarray(ref, np.float64),
                             rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("op", ["mma", "addnorm"])
def test_faithful_vpu_variant(op):
  """The paper-faithful ⊗-ALU path must agree with the MXU rewrite."""
  a = jnp.asarray(RNG.standard_normal((40, 70)), jnp.float32)
  b = jnp.asarray(RNG.standard_normal((70, 50)), jnp.float32)
  got = semiring_mmo(a, b, op=op, interpret=True, faithful=True)
  ref = semiring_mmo_ref(a, b, op=op)
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4,
                             atol=1e-4)


def test_semiring_kernel_batched():
  a = jnp.asarray(RNG.standard_normal((3, 2, 16, 32)), jnp.float32)
  b = jnp.asarray(RNG.standard_normal((3, 2, 32, 24)), jnp.float32)
  got = semiring_mmo(a, b, op="minplus", interpret=True)
  ref = semiring_mmo_ref(a, b, op="minplus")
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("op", ["mma", "minplus", "maxmin", "orand"])
def test_semiring_kernel_masked_k(op):
  """Per-request k_valid skips dead K-blocks without changing the result:
  lanes at/beyond k_valid hold contraction pads (⊗(pa, pb) == ⊕-identity),
  so the skipped blocks were algebraic no-ops by construction."""
  from repro.core.semiring import contraction_pads, get as get_sr
  r, m, k, n = 3, 16, 64, 24
  kv = np.asarray([24, 40, 64], np.int32)
  pa, pb = contraction_pads(op)
  a = RNG.standard_normal((r, m, k)).astype(np.float32)
  b = RNG.standard_normal((r, k, n)).astype(np.float32)
  if get_sr(op).boolean:
    a, b = a > 0.3, b > 0.3
    pa = pb = False
  for i, kvi in enumerate(kv):
    a[i, :, kvi:] = pa
    b[i, kvi:, :] = pb
  got = semiring_mmo(jnp.asarray(a), jnp.asarray(b), op=op, bk=16,
                     interpret=True, k_valid=jnp.asarray(kv))
  ref = semiring_mmo_ref(jnp.asarray(a), jnp.asarray(b), op=op)
  np.testing.assert_allclose(np.asarray(got, np.float64),
                             np.asarray(ref, np.float64), rtol=1e-4,
                             atol=1e-4)
  # scalar k_valid on a single 2-D problem
  got0 = semiring_mmo(jnp.asarray(a[0]), jnp.asarray(b[0]), op=op, bk=16,
                      interpret=True, k_valid=24)
  np.testing.assert_allclose(np.asarray(got0, np.float64),
                             np.asarray(ref, np.float64)[0], rtol=1e-4,
                             atol=1e-4)


VPU_RINGS = ("minplus", "maxplus", "minmul", "maxmul", "maxmin", "minmax",
             "orand")
# several row strips and 128-lane tiles with K and N tails, then the 64 and
# 128 buckets
PARITY_SHAPES = [(300, 333, 520), (64, 64, 64), (128, 128, 128)]


def _vpu_operands(op, lead, m, k, n, seed):
  """A, B, C for a VPU ring: {0,1} for orand, else normals with a few NaNs
  in A (the strip fold must put NaNs where the oracle does)."""
  rng = np.random.default_rng(seed)
  a = rng.standard_normal(lead + (m, k)).astype(np.float32)
  b = rng.standard_normal(lead + (k, n)).astype(np.float32)
  c = rng.standard_normal(lead + (m, n)).astype(np.float32)
  if get_sr(op).boolean:
    return a > 0.8, b > 0.8, c > 1.5
  a[rng.random(a.shape) < 0.002] = np.nan
  return a, b, c


def _assert_value_identical(got, ref):
  got, ref = np.asarray(got), np.asarray(ref)
  assert got.dtype == ref.dtype and got.shape == ref.shape
  np.testing.assert_array_equal(np.isnan(got), np.isnan(ref))
  assert np.array_equal(got, ref, equal_nan=True)


@pytest.mark.parametrize("has_c", [False, True])
@pytest.mark.parametrize("shape", PARITY_SHAPES)
@pytest.mark.parametrize("op", VPU_RINGS)
def test_vpu_kernel_value_parity(op, shape, has_c):
  """The strip kernel folds the same ⊗ terms as the oracle; min and max
  do not see the association, so the results are value-identical."""
  a, b, c = _vpu_operands(op, (), *shape, seed=sum(shape))
  cc = jnp.asarray(c) if has_c else None
  got = semiring_mmo(jnp.asarray(a), jnp.asarray(b), cc, op=op,
                     interpret=True)
  ref = semiring_mmo_ref(jnp.asarray(a), jnp.asarray(b), cc, op=op)
  _assert_value_identical(got, ref)


@pytest.mark.parametrize("op", VPU_RINGS)
def test_vpu_kernel_value_parity_k_valid(op):
  """Batched entry with a per-request k_valid: none live, inside the first
  chunk, one lane into the first K block's second chunk, inside the second
  block, and all; lanes past k_valid hold the contraction pads."""
  m, k, n = 40, 700, 200
  assert sm.block_geometry(op, m, k, n).bk == 384
  kv = np.asarray([0, 50, 129, 500, k], np.int32)
  a, b, c = _vpu_operands(op, (len(kv),), m, k, n, seed=7)
  pa, pb = contraction_pads(op)
  if get_sr(op).boolean:
    pa = pb = False
  for i, kvi in enumerate(kv):
    a[i, :, kvi:] = pa
    b[i, kvi:, :] = pb
  a, b, c = jnp.asarray(a), jnp.asarray(b), jnp.asarray(c)
  for cc in (None, c):
    got = semiring_mmo(a, b, cc, op=op, interpret=True,
                       k_valid=jnp.asarray(kv))
    _assert_value_identical(got, semiring_mmo_ref(a, b, cc, op=op))
  got0 = semiring_mmo(a[1], b[1], op=op, interpret=True, k_valid=int(kv[1]))
  _assert_value_identical(got0, semiring_mmo_ref(a[1], b[1], op=op))


def test_block_geometry():
  """One pure function picks every kernel layout: MXU rings keep 128³;
  the VPU contraction takes wide blocks clipped to small shapes, strips of
  at most _ACC_VREGS accumulator vregs, and never passes its VMEM budget;
  an explicit block is honoured as the DMA block."""
  bg = sm.block_geometry
  assert bg("mma", 4096, 4096, 4096) == (128, 128, 128, 0)
  assert bg("addnorm", 13, 7, 5) == (16, 128, 8, 0)
  assert bg("mma", 4096, 4096, 4096, block=(256, None, 512)) == (
      256, 128, 512, 0)
  assert bg("minplus", 4096, 4096, 4096) == (256, 1024, 512, 32)
  assert bg("mma", 4096, 4096, 4096, faithful=True) == (256, 1024, 512, 32)
  assert bg("maxmin", 1024, 1024, 1024) == (256, 1024, 512, 32)
  assert bg("minplus", 64, 64, 64) == (64, 128, 64, 64)
  assert bg("orand", 128, 128, 128) == (128, 128, 128, 128)
  assert bg("minplus", 300, 333, 520) == (192, 640, 384, 48)
  assert bg("minplus", 4096, 4096, 4096, block=(128, 128, 128)) == (
      128, 128, 128, 128)
  assert bg("minplus", 64, 64, 64, block=(None, None, 16)).bk == 16
  sizes = (1, 7, 64, 100, 128, 300, 1000, 1024, 1500, 4096, 8192)
  for op in VPU_RINGS + ("mma",):
    for m, k, n in itertools.product(sizes, repeat=3):
      g = bg(op, m, k, n, faithful=op == "mma")
      assert sm.vmem_bytes(g, 4, 4) <= sm.VMEM_BUDGET, (op, m, k, n)
      assert g.bm <= -(-m // 8) * 8 and g.bn <= -(-n // 128) * 128
      assert g.bk <= (-(-k // 8) * 8 if k <= 128 else -(-k // 128) * 128)
      assert g.bk <= 128 or g.bk % 128 == 0
      assert g.bm % g.strip == 0 and g.strip % 8 == 0
      assert (g.strip // 8) * (g.bn // 128) <= sm._ACC_VREGS


FA_CASES = [
    # b, h, hkv, sq, skv, d, causal, window
    (2, 4, 2, 128, 128, 64, True, None),
    (1, 8, 2, 96, 160, 32, True, None),
    (2, 4, 4, 128, 128, 64, False, None),
    (1, 4, 1, 200, 200, 64, True, 96),
    (1, 2, 2, 64, 256, 128, True, None),
    (1, 4, 4, 160, 160, 80, True, None),   # non-128-aligned head dim
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention(case):
  b, h, hkv, sq, skv, d, causal, window = case
  q = RNG.standard_normal((b, h, sq, d)).astype(np.float32)
  k = RNG.standard_normal((b, hkv, skv, d)).astype(np.float32)
  v = RNG.standard_normal((b, hkv, skv, d)).astype(np.float32)
  kx = np.repeat(k, h // hkv, axis=1)
  vx = np.repeat(v, h // hkv, axis=1)
  ref = attention_ref(jnp.asarray(q), jnp.asarray(kx), jnp.asarray(vx),
                      causal=causal, window=window)
  got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=causal, window=window, bq=64, bkv=64,
                        interpret=True)
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
  q = jnp.asarray(RNG.standard_normal((1, 4, 64, 64)), jnp.bfloat16)
  k = jnp.asarray(RNG.standard_normal((1, 4, 64, 64)), jnp.bfloat16)
  v = jnp.asarray(RNG.standard_normal((1, 4, 64, 64)), jnp.bfloat16)
  got = flash_attention(q, k, v, interpret=True)
  ref = attention_ref(q, k, v)
  np.testing.assert_allclose(np.asarray(got, np.float32),
                             np.asarray(ref, np.float32), atol=3e-2)
