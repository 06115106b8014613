"""Generic Pallas TPU kernel for SIMD² matrix-matrix operations.

This is the TPU-native embodiment of the paper's SIMD² unit (§3.1): one
datapath (HBM→VMEM block pipeline + fp32 block accumulator resident in VMEM
across the K grid dimension) whose ⊗/⊕ "ALU" is selected per instruction.

  * mma           → the block contraction is a real MXU ``jnp.dot``.
  * addnorm       → fused MXU rewrite in-kernel: −2·a@b plus row/col norm
                    rank-1 corrections (O(K·M·N) work on the MXU).
  * min/max rings → VPU rank-1 updates: K is loaded through the refs in
                    lane-tile-aligned 128-wide chunks, and each chunk is
                    ⊕-folded as 128 static (bm,1) ⊗ (1,bn) outer products —
                    only static slices of loaded values, the form Mosaic
                    lowers without dynamic lane relayouts.
  * orand         → runs in the float {0,1} domain with (max, min); the
                    wrapper restores bool.

Block sizes default to (bm, bn, bk) = (128, 128, 128): MXU-aligned, and the
three resident blocks + fp32 accumulator use 128·128·(2+2+4+4) B ≈ 192 KiB of
VMEM — small enough for Mosaic's double buffering (~0.4 MiB total) with room
to grow bk.  K-tail padding uses per-ring pad values chosen so that
⊗(pad_a, pad_b) equals the ⊕-identity (see ``_PADS``), making padded lanes
algebraic no-ops.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import semiring as sr_mod
from repro.core.mmo import MXU_PRECISION

Array = jax.Array

# (pad_a, pad_b) per op with ⊗(pad_a, pad_b) == ⊕-identity (K-tail padding);
# the table lives in core/semiring.py so the serving layer's shape bucketing
# shares the exact same padding algebra.
_PADS = sr_mod._CONTRACTION_PADS

_SUBLANES = 8  # VPU sublane count — row padding granularity.
_LANES = 128   # lane tile — the K chunk width of the VPU contraction.


def _float_ring(sr: sr_mod.Semiring):
  """or-and executes on the VPU in the float {0,1} domain as (max, min)."""
  if sr.boolean:
    return jnp.maximum, jnp.minimum
  return sr.oplus, sr.otimes


def rank1_fold(oplus, otimes, acc, a_c: Array, b_c: Array):
  """⊕-fold ``a_c`` (bm, ck) ⊗ ``b_c`` (ck, bn) into ``acc`` (None = start
  from the first term) as ``ck`` static rank-1 updates: column t of a_c
  broadcast across lanes, row t of b_c across sublanes."""
  for t in range(a_c.shape[1]):
    term = otimes(a_c[:, t:t + 1], b_c[t:t + 1, :])
    acc = term if acc is None else oplus(acc, term)
  return acc


def _block_contract(sr: sr_mod.Semiring, a_ref, b_ref, acc_dtype,
                    faithful: bool = False) -> Array:
  """One (bm, bk) × (bk, bn) block contraction — the 'ALU' dispatch.

  ``faithful=True`` forces the paper's ⊗-ALU semantics (VPU rank-1 loop)
  even for ops with an MXU rewrite — the paper-faithful baseline arm in
  EXPERIMENTS.md §Perf.
  """
  if sr.name == "mma" and not faithful:
    return jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
                   precision=MXU_PRECISION)
  if sr.name == "addnorm" and not faithful:
    # Σ(a−b)² = ‖a‖²·1ᵀ + 1·‖b‖²ᵀ − 2ab: MXU dot + rank-1 VPU corrections.
    a, b = a_ref[...], b_ref[...]
    ab = jnp.dot(a, b, preferred_element_type=jnp.float32,
                 precision=MXU_PRECISION)
    a2 = jnp.sum(jnp.square(a.astype(jnp.float32)), axis=1, keepdims=True)
    b2 = jnp.sum(jnp.square(b.astype(jnp.float32)), axis=0, keepdims=True)
    return a2 - 2.0 * ab + b2

  oplus, otimes = _float_ring(sr)
  bk = a_ref.shape[1]
  acc = None
  for k0 in range(0, bk, _LANES):  # static, lane-tile-aligned K chunks
    k1 = min(k0 + _LANES, bk)
    acc = rank1_fold(oplus, otimes, acc, a_ref[:, k0:k1].astype(acc_dtype),
                     b_ref[k0:k1, :].astype(acc_dtype))
  return acc


def _make_kernel(sr: sr_mod.Semiring, acc_dtype, has_c: bool, has_kv: bool,
                 bk: int, faithful: bool = False):
  oplus, _ = _float_ring(sr)

  def kernel(*refs):
    refs = list(refs)
    a_ref, b_ref = refs[0], refs[1]
    pos = 2
    c_ref = None
    if has_c:
      c_ref, pos = refs[pos], pos + 1
    kv_ref = None
    if has_kv:
      kv_ref, pos = refs[pos], pos + 1
    o_ref = refs[pos]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
      # K-block 0 always runs: it both initializes o_ref and covers the
      # k_valid==0 case (a frozen request whose output the caller discards).
      part = _block_contract(sr, a_ref, b_ref, acc_dtype, faithful)
      if c_ref is not None:
        o_ref[...] = oplus(part, c_ref[...].astype(acc_dtype))
      else:
        o_ref[...] = part

    # Ragged masked-K skipping: a K-block whose first lane is at or beyond
    # this request's k_valid holds only algebraic-no-op pad lanes, so the
    # whole block contraction is dead work and is skipped.
    live = (k != 0) if kv_ref is None else ((k != 0) & (k * bk < kv_ref[0, 0]))

    @pl.when(live)
    def _acc():
      part = _block_contract(sr, a_ref, b_ref, acc_dtype, faithful)
      o_ref[...] = oplus(o_ref[...], part)

  return kernel


def _pad_to(x: Array, m: int, n: int, val: float) -> Array:
  pm, pn = m - x.shape[0], n - x.shape[1]
  if pm == 0 and pn == 0:
    return x
  return jnp.pad(x, ((0, pm), (0, pn)), constant_values=val)


@functools.partial(
    jax.jit,
    static_argnames=("op", "bm", "bn", "bk", "interpret", "faithful"))
def semiring_mmo(a: Array,
                 b: Array,
                 c: Optional[Array] = None,
                 *,
                 op: str = "mma",
                 bm: int = 128,
                 bn: int = 128,
                 bk: int = 128,
                 interpret: bool = False,
                 faithful: bool = False,
                 k_valid: Optional[Array] = None) -> Array:
  """Tiled Pallas D = C ⊕ (A ⊗ B) for 2-D operands (vmap for batching).

  ``k_valid`` (int32 scalar) marks how many leading K lanes are live; K
  blocks at or beyond it are skipped entirely (the caller guarantees those
  lanes are algebraic no-ops — contraction pads or isolated-vertex padding).
  """
  sr = sr_mod.get(op)
  was_bool = sr.boolean
  in_dtype = a.dtype
  if was_bool:
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if c is not None:
      c = c.astype(jnp.float32)
    in_dtype = jnp.dtype(jnp.float32)

  m, k = a.shape
  n = b.shape[1]
  bm_, bn_, bk_ = min(bm, _rup(m, 8)), min(bn, _rup(n, 128)), min(
      bk, _rup(k, _SUBLANES))
  mp, np_, kp = _rup(m, bm_), _rup(n, bn_), _rup(k, bk_)

  pa, pb = _PADS[sr.name]
  a_p = _pad_to(a, mp, kp, pa)
  b_p = _pad_to(b, kp, np_, pb)

  acc_dtype = jnp.float32 if sr.name in ("mma", "addnorm") else (
      jnp.float32 if was_bool else sr.acc_dtype(in_dtype))
  has_c = c is not None
  if has_c:
    c_p = _pad_to(c.astype(acc_dtype), mp, np_, 0.0)

  has_kv = k_valid is not None
  grid = (mp // bm_, np_ // bn_, kp // bk_)
  kernel = _make_kernel(sr, acc_dtype, has_c, has_kv, bk_, faithful)

  in_specs = [
      pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
      pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
  ]
  operands = [a_p, b_p]
  if has_c:
    in_specs.append(pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)))
    operands.append(c_p)
  if has_kv:
    # one live-K scalar, shipped as a (1, 1) int32 block every grid step
    in_specs.append(pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)))
    operands.append(jnp.asarray(k_valid, jnp.int32).reshape(1, 1))

  out = pl.pallas_call(
      kernel,
      grid=grid,
      in_specs=in_specs,
      out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
      out_shape=jax.ShapeDtypeStruct((mp, np_), acc_dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary")),
      interpret=interpret,
      name=f"simd2_{sr.name}",
  )(*operands)

  out = out[:m, :n]
  if was_bool:
    out = out > 0.5
  return out


def _rup(x: int, mult: int) -> int:
  return ((x + mult - 1) // mult) * mult
