"""jit'd public wrappers for the Pallas kernels.

``semiring_mmo`` / ``flash_attention`` here are the entry points the rest of
the framework uses; on a CPU host they run in interpret mode automatically
(the kernels themselves target TPU Mosaic).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import semiring_mmo as _sm
from repro.kernels import flash_attention as _fa

Array = jax.Array


def _on_tpu() -> bool:
  return jax.default_backend() == "tpu"


def semiring_mmo(a: Array, b: Array, c: Optional[Array] = None, *,
                 op: str = "mma", bm: Optional[int] = None,
                 bn: Optional[int] = None, bk: Optional[int] = None,
                 interpret: Optional[bool] = None, faithful: bool = False,
                 k_valid: Optional[Array] = None) -> Array:
  """Batched-aware Pallas MMO; vmaps leading batch dims onto the 2-D kernel.

  Block sizes left None are the kernel's own choice
  (``semiring_mmo.block_geometry``).

  ``k_valid`` broadcasts over the batch dims (one live-K scalar per kernel
  instance), so a (R, M, K) batch takes an (R,) vector of per-request K
  counts — the ragged masked-K serving path.
  """
  interp = (not _on_tpu()) if interpret is None else interpret
  kw = dict(op=op, bm=bm, bn=bn, bk=bk, interpret=interp, faithful=faithful)
  has_c, has_kv = c is not None, k_valid is not None

  def base(*ops_):
    pos = 2
    cc = ops_[pos] if has_c else None
    pos += has_c
    kv = ops_[pos] if has_kv else None
    return _sm.semiring_mmo(ops_[0], ops_[1], cc, k_valid=kv, **kw)

  operands = [a, b]
  if has_c:
    operands.append(c)
  if has_kv:
    operands.append(jnp.broadcast_to(jnp.asarray(k_valid, jnp.int32),
                                     a.shape[:-2]))
  fn = base
  for _ in range(a.ndim - 2):
    fn = jax.vmap(fn)
  return fn(*operands)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: Optional[bool] = None) -> Array:
  interp = (not _on_tpu()) if interpret is None else interpret
  return _fa.flash_attention(q, k, v, causal=causal, window=window,
                             scale=scale, bq=bq, bkv=bkv, interpret=interp)
