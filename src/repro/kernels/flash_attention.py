"""Pallas TPU flash-attention (forward) kernel for the LM stack.

Perf-critical compute layer for the assigned transformer architectures:
online-softmax block attention with causal and sliding-window (SWA) masking.
Grid is (batch·heads, q_blocks, kv_blocks); running max / denominator / fp32
output accumulator live in VMEM scratch across the kv dimension (the
TPU-idiomatic replacement for a GPU warp-register accumulator).  Blocks whose
entire kv range is masked out are skipped via ``pl.when`` (causal + window
early-out), so compute for a causal prefill is ~half the rectangle and SWA
prefill is O(S·window).

GQA wrapping, KV-cache paging and decode (q_len=1) stay in XLA — only the
O(S²) prefill core is a kernel (see models/attention.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_NEG_INF = -1e30
_LANES = 128  # scratch minor dim (VPU lane count)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: Optional[int],
               bq: int, bkv: int, nkv: int, seq_off: int, kv_len: int):
  """One (q_block, kv_block) step of online softmax."""
  qi = pl.program_id(1)
  kj = pl.program_id(2)

  @pl.when(kj == 0)
  def _init():
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

  # absolute positions: q rows sit at the *end* of the kv axis (decode-style
  # alignment); seq_off = skv - sq.
  q_start = qi * bq + seq_off
  k_start = kj * bkv

  # block-level reachability early-out (skips ~half the causal rectangle,
  # and everything outside the sliding window)
  conds = []
  if causal:
    conds.append(k_start <= q_start + bq - 1)
  if window is not None:
    conds.append(k_start + bkv - 1 > q_start - window)
  run = None
  for c in conds:
    run = c if run is None else jnp.logical_and(run, c)

  def _step():
    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bkv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kpos < kv_len  # mask kv-tail padding
    if causal:
      mask &= kpos <= qpos
    if window is not None:
      mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[:, 0]                      # (bq,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)           # rescale factor
    p = jnp.exp(s - m_cur[:, None])           # (bq, bkv)
    l_cur = alpha * l_scr[:, 0] + jnp.sum(p, axis=1)

    v = v_ref[0].astype(jnp.float32)          # (bkv, d)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
    m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

  if run is None:
    _step()
  else:
    pl.when(run)(_step)

  @pl.when(kj == nkv - 1)
  def _finish():
    l = l_scr[:, 0]
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros, not NaN
    o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bkv", "interpret"))
def flash_attention(q: Array, k: Array, v: Array, *,
                    causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = False) -> Array:
  """q: (B, H, Sq, D); k, v: (B, H, Skv, D); returns (B, H, Sq, D).

  Expand GQA KV heads before calling (wrapper does this lazily via
  broadcasting in index_map — no materialized copy)."""
  b, h, sq, d = q.shape
  skv = k.shape[-2]
  hkv = k.shape[1]
  assert h % hkv == 0, (h, hkv)
  grp = h // hkv
  scale_v = (d ** -0.5) if scale is None else scale

  bq_ = min(bq, sq)
  bkv_ = min(bkv, skv)
  sq_p, skv_p = _rup(sq, bq_), _rup(skv, bkv_)
  if sq_p != sq:
    q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
  if skv_p != skv:
    # padded kv rows must never win the max: rely on causal/pos mask — pad
    # positions sit beyond every real q position, masked by kpos <= qpos when
    # causal; for non-causal we mask via kpos < skv below.
    k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))

  nq, nkv = sq_p // bq_, skv_p // bkv_
  bh = b * h
  q4 = q.reshape(bh, sq_p, d)
  seq_off = skv - sq

  kernel = functools.partial(
      _fa_kernel, scale=scale_v, causal=causal, window=window,
      bq=bq_, bkv=bkv_, nkv=nkv, seq_off=seq_off, kv_len=skv)

  # map flattened (b*h) → kv head index without materializing GQA expansion
  def kv_index(bh_i, qi, kj):
    return (bh_i // (grp * hkv) * hkv + (bh_i % (grp * hkv)) // grp, kj, 0)

  k3 = k.reshape(b * hkv, skv_p, d)
  v3 = v.reshape(b * hkv, skv_p, d)

  out = pl.pallas_call(
      kernel,
      grid=(bh, nq, nkv),
      in_specs=[
          pl.BlockSpec((1, bq_, d), lambda bh_i, qi, kj: (bh_i, qi, 0)),
          pl.BlockSpec((1, bkv_, d), kv_index),
          pl.BlockSpec((1, bkv_, d), kv_index),
      ],
      out_specs=pl.BlockSpec((1, bq_, d), lambda bh_i, qi, kj: (bh_i, qi, 0)),
      out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
      scratch_shapes=[
          pltpu.VMEM((bq_, _LANES), jnp.float32),  # running max
          pltpu.VMEM((bq_, _LANES), jnp.float32),  # running denom
          pltpu.VMEM((bq_, d), jnp.float32),       # fp32 out accumulator
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary")),
      interpret=interpret,
      name="flash_attention_fwd",
  )(q4, k3, v3)

  return out.reshape(b, h, sq_p, d)[:, :, :sq, :]


def _rup(x: int, mult: int) -> int:
  return ((x + mult - 1) // mult) * mult
