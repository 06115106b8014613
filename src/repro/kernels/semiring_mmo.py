"""Generic Pallas TPU kernel for SIMD² matrix-matrix operations.

This is the TPU-native embodiment of the paper's SIMD² unit (§3.1): one
datapath (HBM→VMEM block pipeline + fp32 output block resident in VMEM
across the K grid dimension) whose ⊗/⊕ "ALU" is selected per instruction.

  * mma           → the block contraction is a real MXU ``jnp.dot``.
  * addnorm       → fused MXU rewrite in-kernel: −2·a@b plus row/col norm
                    rank-1 corrections (O(K·M·N) work on the MXU).
  * min/max rings → VPU rank-1 updates into a register-resident
                    accumulator strip (see below).
  * orand         → runs in the float {0,1} domain with (max, min); the
                    wrapper restores bool.

Block geometry comes from ``block_geometry`` — one pure function of ring
family and shape (every dtype the kernel takes fits the same blocks).  The MXU rings keep (bm, bn, bk) = (128, 128, 128).
The VPU contraction (every ring without an MXU rewrite, and the
``faithful`` arm) is paced by the lane broadcast of each A column, so its
blocks are wide: up to (256, 1024, 512), ~9 MiB of VMEM double-buffered in
f32.  The output block is initialised once (from C, or the ⊕-identity) and
each live K block is folded into it one row strip at a time: the strip's
(strip, bn) accumulator — at most ``_ACC_VREGS`` vregs — stays in registers
across the block's 128-lane K chunks, and each rank-1 term broadcasts its
A column once and reuses it across all bn/128 lane tiles of the strip.

K-tail padding uses per-ring pad values chosen so that ⊗(pad_a, pad_b)
equals the ⊕-identity (see ``_PADS``), making padded lanes algebraic no-ops;
the VPU chunk loop stops at the last chunk holding a live lane.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

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

_MXU_BLOCK = (128, 128, 128)
# VPU contraction: largest DMA block, and the accumulator strip's size in
# f32 vregs (the register file holds 64).
_VPU_BLOCK = (256, 1024, 512)
_ACC_VREGS = 32
# what the VPU blocks may take of the 16 MiB default scoped VMEM
# (double-buffered A, B, C and the output block), the rest left to Mosaic
VMEM_BUDGET = 12 << 20


class Geometry(NamedTuple):
  """Resolved kernel layout: the (bm, bn, bk) DMA block, and the rows of
  one register-resident accumulator strip (0 = MXU block contraction)."""
  bm: int
  bn: int
  bk: int
  strip: int


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


def _split(total: int, unit: int, cap: int) -> int:
  """Equal blocks, each a multiple of ``unit`` and at most ``max(cap,
  unit)``, covering ``total`` with the least padding."""
  tiles = -(-total // unit)
  nblk = -(-tiles // max(1, cap // unit))
  return -(-tiles // nblk) * unit


def vmem_bytes(geom: Geometry, in_itemsize: int, acc_itemsize: int) -> int:
  """Double-buffered VMEM of one launch's blocks: A, B, C and the output."""
  bm, bn, bk, _ = geom
  return 2 * ((bm * bk + bk * bn) * in_itemsize + 2 * bm * bn * acc_itemsize)


def _is_vpu(op: str, faithful: bool) -> bool:
  return faithful or sr_mod.get(op).name not in ("mma", "addnorm")


def block_geometry(op: str, m: int, k: int, n: int, *,
                   block: tuple = (None, None, None),
                   faithful: bool = False) -> Geometry:
  """Kernel layout for ring ``op`` on an (m, k) × (k, n) problem.

  ``block`` entries that are not None are honoured as the DMA block (clipped
  to the problem, like every block; a VPU bk past one lane tile rounds up
  to whole tiles); the rest are chosen here.  MXU rings default to 128³.
  The VPU contraction takes the widest lane extent (≤ 1024) that pads n
  least, strips of up to ``_ACC_VREGS`` accumulator vregs, bm up to 256 in
  whole strips, and bk up to 512 in whole 128-lane chunks: at most 9 MiB
  of VMEM (``vmem_bytes``) for operands of up to 4 bytes, inside
  ``VMEM_BUDGET``, so no dtype the kernel takes changes the choice.
  """
  bm, bn, bk = block
  if not _is_vpu(op, faithful):
    bm, bn, bk = (d if d is not None else x for d, x in zip(block,
                                                             _MXU_BLOCK))
    return Geometry(min(bm, _rup(m, _SUBLANES)), min(bn, _rup(n, _LANES)),
                    min(bk, _rup(k, _SUBLANES)), 0)

  if bn is None:
    bn = _split(n, _LANES, _VPU_BLOCK[1])
  bn = min(bn, _rup(n, _LANES))
  target = min(_SUBLANES * max(1, _ACC_VREGS // max(1, bn // _LANES)),
               _rup(m, _SUBLANES))
  if bm is None:
    bm = _split(m, target, _VPU_BLOCK[0])
  bm = min(bm, _rup(m, _SUBLANES))
  if bk is None:
    bk = (_split(k, _LANES, _VPU_BLOCK[2]) if k > _LANES
          else _rup(k, _SUBLANES))
  bk = min(bk, _rup(k, _SUBLANES))
  if bk > _LANES:
    bk = _rup(bk, _LANES)
  # the strip: the most rows, up to target, that tile bm in whole sublane
  # tiles
  strip = bm
  if bm % _SUBLANES == 0:
    strip = max(s for s in range(_SUBLANES, min(target, bm) + 1, _SUBLANES)
                if bm % s == 0)
  return Geometry(bm, bn, bk, strip)


def _mxu_contract(sr: sr_mod.Semiring, a_ref, b_ref) -> Array:
  """One (bm, bk) × (bk, bn) block contraction on the MXU."""
  if sr.name == "mma":
    return jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
                   precision=MXU_PRECISION)
  # addnorm: Σ(a−b)² = ‖a‖²·1ᵀ + 1·‖b‖²ᵀ − 2ab: MXU dot + rank-1 VPU
  # corrections.
  a, b = a_ref[...], b_ref[...]
  ab = jnp.dot(a, b, preferred_element_type=jnp.float32,
               precision=MXU_PRECISION)
  a2 = jnp.sum(jnp.square(a.astype(jnp.float32)), axis=1, keepdims=True)
  b2 = jnp.sum(jnp.square(b.astype(jnp.float32)), axis=0, keepdims=True)
  return a2 - 2.0 * ab + b2


def _unpack(refs, has_c: bool, has_kv: bool):
  a_ref, b_ref, *rest = refs
  c_ref = rest.pop(0) if has_c else None
  kv_ref = rest.pop(0) if has_kv else None
  return a_ref, b_ref, c_ref, kv_ref, rest[0]


def _make_mxu_kernel(sr: sr_mod.Semiring, has_c: bool, has_kv: bool,
                     bk: int):
  def kernel(*refs):
    a_ref, b_ref, c_ref, kv_ref, o_ref = _unpack(refs, has_c, has_kv)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
      # K-block 0 always runs: it both initializes o_ref and covers the
      # k_valid==0 case (a frozen request whose output the caller discards).
      part = _mxu_contract(sr, a_ref, b_ref)
      if c_ref is not None:
        o_ref[...] = part + c_ref[...]
      else:
        o_ref[...] = part

    # Ragged masked-K skipping: a K-block whose first lane is at or beyond
    # this request's k_valid holds only algebraic-no-op pad lanes, so the
    # whole block contraction is dead work and is skipped.
    live = (k != 0) if kv_ref is None else ((k != 0) & (k * bk < kv_ref[0, 0]))

    @pl.when(live)
    def _acc():
      part = _mxu_contract(sr, a_ref, b_ref)
      o_ref[...] = o_ref[...] + part

  return kernel


def _make_vpu_kernel(sr: sr_mod.Semiring, acc_dtype, has_c: bool,
                     has_kv: bool, geom: Geometry, k: int):
  """One body for every K block: initialise the output block at k == 0,
  then ⊕-fold each live K block into it, one register-resident row strip
  at a time, over the block's live 128-lane K chunks.  Every term is the
  same ⊗ of the same two operands as a plain rank-1 fold; only the
  association of ⊕ differs, which min and max do not see."""
  oplus, otimes = _float_ring(sr)
  bm, bn, bk, strip = geom
  ck = min(_LANES, bk)
  nchunk = bk // ck

  def kernel(*refs):
    a_ref, b_ref, c_ref, kv_ref, o_ref = _unpack(refs, has_c, has_kv)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
      if c_ref is not None:
        o_ref[...] = c_ref[...]
      else:
        o_ref[...] = jnp.full((bm, bn), sr.oplus_identity, acc_dtype)

    # Ragged masked K: lanes at or beyond min(k_valid, k) are algebraic
    # no-ops, so the fold stops at the last chunk holding a live lane and a
    # K block with none is skipped whole.
    limit = k if kv_ref is None else jnp.minimum(kv_ref[0, 0], k)
    nlive = jnp.clip((limit - kk * bk + ck - 1) // ck, 0, nchunk)

    @pl.when(nlive > 0)
    def _fold():
      def row_strip(s, carry):
        rows = pl.ds(pl.multiple_of(s * strip, strip), strip)

        def chunk(c, acc):
          # a block of one chunk narrower than a lane tile (k < 128) loads
          # at a static offset: Mosaic cannot prove a dynamic one aligned
          k0 = 0 if nchunk == 1 else pl.multiple_of(c * ck, ck)
          a_c = a_ref[rows, pl.ds(k0, ck)].astype(acc_dtype)
          for t in range(ck):
            # B rows load one at a time, next to their use: a whole
            # (128, bn) chunk held as a value outgrows the register file
            b_t = b_ref[pl.ds(k0 + t, 1), :].astype(acc_dtype)
            acc = oplus(acc, otimes(a_c[:, t:t + 1], b_t))
          return acc

        o_ref[rows, :] = jax.lax.fori_loop(0, nlive, chunk, o_ref[rows, :])
        return carry

      jax.lax.fori_loop(0, bm // strip, row_strip, 0)

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
                 bm: Optional[int] = None,
                 bn: Optional[int] = None,
                 bk: Optional[int] = None,
                 interpret: bool = False,
                 faithful: bool = False,
                 k_valid: Optional[Array] = None) -> Array:
  """Tiled Pallas D = C ⊕ (A ⊗ B) for 2-D operands (vmap for batching).

  ``bm``/``bn``/``bk`` left None are chosen by ``block_geometry``.
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
  geom = block_geometry(sr.name, m, k, n, block=(bm, bn, bk),
                        faithful=faithful)
  bm_, bn_, bk_ = geom.bm, geom.bn, geom.bk
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
  if geom.strip:
    kernel = _make_vpu_kernel(sr, acc_dtype, has_c, has_kv, geom, k)
    # the geometry that ran, for the trace (serve_mmo's compile span)
    metadata = {"block": f"{bm_}x{bn_}x{bk_}", "strip": str(geom.strip)}
  else:
    kernel = _make_mxu_kernel(sr, has_c, has_kv, bk_)
    metadata = None

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
      metadata=metadata,
  )(*operands)

  out = out[:m, :n]
  if was_bool:
    out = out > 0.5
  return out


def _rup(x: int, mult: int) -> int:
  return ((x + mult - 1) // mult) * mult
