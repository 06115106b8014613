"""``mmo`` — the SIMD² matrix-matrix-operation API (paper §3.2/§4).

``D = C ⊕ (A ⊗ B)`` with A: (..., M, K), B: (..., K, N), C/D: (..., M, N).

Backends (selected via ``backend=``):

  'vector'  — blocked broadcast-⊗ + ⊕-reduce.  This is the TPU analogue of
              the paper's "SIMD² w/ CUDA cores" arm: correct on any platform,
              no MXU, O(M·bk·N) live intermediate per K-block.
  'xla'     — MXU-reuse rewrites where an exact one exists (mma → jnp.matmul,
              addnorm → ‖a‖²+‖b‖²−2ab expansion, orand → count>0), otherwise
              falls back to 'vector'.  This is the production path on CPU and
              the non-Pallas path on TPU.
  'pallas'  — the generic Pallas semiring kernel (kernels/semiring_mmo.py),
              the TPU-native embodiment of a SIMD² unit.  ``interpret=True``
              on CPU.
  'auto'    — consult the measured cost table (repro.tuning) for the cheapest
              (backend, block config) at this call's bucket signature; 'xla'
              when no table is loaded or it has no entry — the dispatcher
              that a compiler targeting SIMD² hardware would implement.

All backends produce identical results (tests sweep ops × shapes × dtypes).

Ragged contraction: ``k_valid`` (an int32 scalar, or one per leading request
for batched operands) declares how many leading K lanes are live.  The caller
guarantees K lanes at or beyond ``k_valid`` are algebraic no-ops (contraction
pads, or a closure's isolated-vertex padding), so backends are free to *skip*
them: the Pallas kernel masks dead K-blocks per request, the vector path
contracts a dynamic number of K-blocks bounded by ``max(k_valid)``, and the
MXU rewrites ignore the hint (full padded K on the MXU is already cheap).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import semiring as sr_mod

Array = jax.Array

_DEFAULT_BLOCK_K = 512
# Every f32 MXU contraction (the XLA rewrites here, the Pallas kernels in
# kernels/) runs at this one precision, so the backends agree on the chip as
# they do on CPU: TPU's default f32 matmul precision is a single bf16 pass.
MXU_PRECISION = jax.lax.Precision.HIGHEST
# Aim for at least this many dynamic K-blocks when a k_valid hint is present,
# so skipping dead blocks has useful granularity.
_DYN_K_BLOCKS = 8


def _check_shapes(a, b, c):
  if a.ndim < 2 or b.ndim < 2:
    raise ValueError(f"mmo operands must be >=2D, got {a.shape} {b.shape}")
  if a.shape[-1] != b.shape[-2]:
    raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
  m, n = a.shape[-2], b.shape[-1]
  if c is not None and c.shape[-2:] != (m, n):
    raise ValueError(f"C shape {c.shape} != ({m},{n})")


# ---------------------------------------------------------------------------
# vector backend: blocked broadcast/reduce.
# ---------------------------------------------------------------------------


def _contract_vector(a: Array, b: Array, sr: sr_mod.Semiring,
                     block_k: int) -> Array:
  """⊕_k ⊗(a[..,m,k], b[..,k,n]) by scanning K blocks."""
  *batch, m, k = a.shape
  n = b.shape[-1]
  acc_dtype = sr.acc_dtype(a.dtype)
  block_k = min(block_k, k)
  nblocks, rem = divmod(k, block_k)

  def blk(a_blk, b_blk):
    # (..., m, bk, 1) ⊗ (..., 1, bk, n) → ⊕ over bk
    prod = sr.otimes(a_blk[..., :, :, None].astype(acc_dtype),
                     b_blk[..., None, :, :].astype(acc_dtype))
    return sr_mod.oplus_reduce(sr, prod, axis=-2)

  # Initialize from the first block (not the ⊕-identity) so the accumulator
  # inherits the operands' types — incl. shard_map varying-axis annotations.
  a_main = a[..., : nblocks * block_k].reshape(*batch, m, nblocks, block_k)
  b_main = b[..., : nblocks * block_k, :].reshape(*batch, nblocks, block_k, n)
  out = blk(a_main[..., :, 0, :], b_main[..., 0, :, :])

  if nblocks > 1:
    def body(i, acc):
      part = blk(a_main[..., :, i, :], b_main[..., i, :, :])
      return sr.oplus(acc, part)

    out = jax.lax.fori_loop(1, nblocks, body, out)
  if rem:
    out = sr.oplus(out, blk(a[..., nblocks * block_k:],
                            b[..., nblocks * block_k:, :]))
  return out


def _dyn_block_k(k: int, block_k: int) -> int:
  """K-block size for the ragged path: shrink toward ~_DYN_K_BLOCKS blocks so
  the dynamic trip count has granularity to skip dead work."""
  bk = min(block_k, k)
  while bk > 8 and k / bk < _DYN_K_BLOCKS:
    bk = (bk + 1) // 2
  return max(bk, 1)


def _contract_vector_dynk(a: Array, b: Array, sr: sr_mod.Semiring,
                          block_k: int, k_valid: Array) -> Array:
  """Ragged vector contraction: only ``ceil(max(k_valid)/bk)`` K-blocks run.

  Batch-max semantics — requests with a smaller ``k_valid`` still see lanes
  up to the batch max, which the k_valid contract guarantees are ⊕-identity
  no-ops, so results match the full contraction exactly while the work
  tracks the *largest live* request instead of the padded K.
  """
  *batch, m, k = a.shape
  n = b.shape[-1]
  acc_dtype = sr.acc_dtype(a.dtype)
  bk = _dyn_block_k(k, block_k)
  kp = ((k + bk - 1) // bk) * bk
  if kp != k:  # pad the K tail so every dynamic block is full-width
    pa, pb = sr_mod.contraction_pads(sr)
    if sr.boolean:
      pa = pb = False
    a = jnp.pad(a, [(0, 0)] * len(batch) + [(0, 0), (0, kp - k)],
                constant_values=pa)
    b = jnp.pad(b, [(0, 0)] * len(batch) + [(0, kp - k), (0, 0)],
                constant_values=pb)
  nblocks = kp // bk
  live = jnp.clip((jnp.max(k_valid) + bk - 1) // bk, 1, nblocks)

  def blk(i):
    a_blk = jax.lax.dynamic_slice_in_dim(a, i * bk, bk, axis=-1)
    b_blk = jax.lax.dynamic_slice_in_dim(b, i * bk, bk, axis=-2)
    prod = sr.otimes(a_blk[..., :, :, None].astype(acc_dtype),
                     b_blk[..., None, :, :].astype(acc_dtype))
    return sr_mod.oplus_reduce(sr, prod, axis=-2)

  out = blk(0)
  if nblocks > 1:
    out = jax.lax.fori_loop(1, live, lambda i, acc: sr.oplus(acc, blk(i)),
                            out)
  return out


# ---------------------------------------------------------------------------
# MXU-reuse rewrites (exact; see DESIGN.md §2).
# ---------------------------------------------------------------------------


def _contract_matmul(a: Array, b: Array, sr: sr_mod.Semiring) -> Array:
  del sr
  return jnp.matmul(a, b, preferred_element_type=jnp.float32,
                    precision=MXU_PRECISION)


def _contract_addnorm(a: Array, b: Array, sr: sr_mod.Semiring) -> Array:
  """Σ_k (a−b)² = Σa² − 2Σab + Σb² — the O(K·M·N) term rides the MXU."""
  del sr
  ab = jnp.matmul(a, b, preferred_element_type=jnp.float32,
                  precision=MXU_PRECISION)
  a2 = jnp.sum(jnp.square(a.astype(jnp.float32)), axis=-1, keepdims=True)
  b2 = jnp.sum(jnp.square(b.astype(jnp.float32)), axis=-2, keepdims=True)
  return a2 - 2.0 * ab + b2


def _contract_orand(a: Array, b: Array, sr: sr_mod.Semiring) -> Array:
  """or-and over {0,1} == (#k: a∧b) > 0 — a thresholded MXU matmul."""
  del sr
  af = a.astype(jnp.bfloat16) if a.dtype == jnp.bool_ else (a != 0).astype(
      jnp.bfloat16)
  bf = b.astype(jnp.bfloat16) if b.dtype == jnp.bool_ else (b != 0).astype(
      jnp.bfloat16)
  # {0,1} operands are exact in bf16 and the f32 count is exact, so this
  # contraction needs no precision pin
  cnt = jnp.matmul(af, bf, preferred_element_type=jnp.float32)
  return cnt > 0.5

_REWRITES = {
    "matmul": _contract_matmul,
    "addnorm": _contract_addnorm,
    "orand": _contract_orand,
}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _resolve_auto(op: str, a, b) -> tuple:
  """backend='auto' → (backend, cfg) from the active cost table (trace-time
  host work; shapes/dtypes are static under tracing)."""
  from repro.tuning import dispatch as _dispatch  # lazy: tuning is optional
  d = _dispatch.resolve(op, a.shape[-2], a.shape[-1], b.shape[-1], a.dtype)
  return d.backend, d.cfg


@functools.partial(
    jax.jit,
    static_argnames=("op", "backend", "block_k", "bm", "bn", "bk",
                     "interpret"))
def _mmo_impl(a, b, c, k_valid, *, op, backend, block_k, bm, bn, bk,
              interpret):
  sr = sr_mod.get(op)
  if backend == "pallas":
    from repro.kernels import ops as kops  # local import: kernels optional
    out = kops.semiring_mmo(a, b, op=sr.name, bm=bm, bn=bn, bk=bk,
                            interpret=interpret,  # auto on CPU
                            k_valid=k_valid)
  elif backend == "xla" and sr.mxu_rewrite is not None:
    # full padded K on the MXU — the k_valid hint is not worth a branch here
    out = _REWRITES[sr.mxu_rewrite](a, b, sr)
  elif backend in ("xla", "vector"):
    if k_valid is None:
      out = _contract_vector(a, b, sr, block_k)
    else:
      out = _contract_vector_dynk(a, b, sr, block_k, k_valid)
  else:
    raise ValueError(f"unknown backend {backend!r}")

  if c is not None:
    out = sr.oplus(out, c.astype(out.dtype))
  return out


def mmo(a: Array,
        b: Array,
        c: Optional[Array] = None,
        *,
        op="mma",
        backend: str = "auto",
        block_k: int = _DEFAULT_BLOCK_K,
        block: Optional[tuple] = None,
        interpret: Optional[bool] = None,
        k_valid: Optional[Array] = None) -> Array:
  """D = C ⊕ (A ⊗ B).  See module docstring for backend semantics.

  ``block`` is the tuning-table block config: ``(bm, bn, bk)`` for the
  Pallas kernel, ``(block_k,)`` for the vector path, ``()`` for "use the
  defaults".  ``backend='auto'`` fills it from the cost table when the
  caller leaves it unset.
  """
  if backend == "megakernel":
    # a cost-table arm, but a whole-fixpoint one: it prices G fused closure
    # iterations per launch, so there is no single-contraction entry point
    raise ValueError(
        "backend 'megakernel' fuses whole closure fixpoints, not single "
        "contractions — select it via batched_leyzorek_closure / "
        "batched_bellman_ford_closure(fixpoint_backend='megakernel'), or "
        "let closure-bucket auto dispatch pick it (tuning.dispatch."
        "CLOSURE_BACKENDS)")
  sr = sr_mod.get(op)
  _check_shapes(a, b, c)
  if sr.boolean:
    a = a.astype(jnp.bool_) if a.dtype != jnp.bool_ else a
    b = b.astype(jnp.bool_) if b.dtype != jnp.bool_ else b

  if backend == "auto":
    backend, cfg = _resolve_auto(op, a, b)
    if block is None:
      block = cfg

  bm = bn = bk = None  # unset: the Pallas kernel chooses its geometry
  if block:
    if backend == "pallas":
      if len(block) != 3:
        raise ValueError(f"pallas block config must be (bm, bn, bk), "
                         f"got {block!r}")
      bm, bn, bk = (int(x) for x in block)
    elif len(block) == 1:
      block_k = int(block[0])
    else:
      raise ValueError(f"block config must be (block_k,), got {block!r}")

  if k_valid is not None:
    k_valid = jnp.asarray(k_valid, jnp.int32)
  return _mmo_impl(a, b, c, k_valid, op=sr.name, backend=backend,
                   block_k=block_k, bm=bm, bn=bn, bk=bk, interpret=interpret)


def mmo_batched(a: Array,
                b: Array,
                c: Optional[Array] = None,
                *,
                op="mma",
                backend: str = "auto",
                block_k: int = _DEFAULT_BLOCK_K,
                block: Optional[tuple] = None,
                interpret: Optional[bool] = None,
                k_valid: Optional[Array] = None) -> Array:
  """D[r] = C[r] ⊕ (A[r] ⊗ B[r]) over a leading request axis.

  The serving engine's raw-mmo entry point: one compiled program per
  (bucket_shape, op, dtype, backend) executes a whole padded request batch.
  Every backend accepts the leading axis ('vector'/'xla' natively, 'pallas'
  via the batch vmap in kernels/ops.py); this wrapper pins the contract and
  validates that all operands agree on the request count.  ``k_valid``
  optionally carries one live-K count per request (see ``mmo``).
  """
  if a.ndim < 3 or b.ndim < 3:
    raise ValueError(f"mmo_batched needs (R, M, K)/(R, K, N), got "
                     f"{a.shape} {b.shape}")
  if c is not None and c.ndim < 3:
    raise ValueError(f"mmo_batched needs (R, M, N) for c, got {c.shape}")
  if a.shape[0] != b.shape[0] or (c is not None and c.shape[0] != a.shape[0]):
    shapes = f"a={a.shape} b={b.shape}" + (
        "" if c is None else f" c={c.shape}")
    raise ValueError(f"request-axis mismatch: {shapes}")
  return mmo(a, b, c, op=op, backend=backend, block_k=block_k, block=block,
             interpret=interpret, k_valid=k_valid)


def mmo_reference(a, b, c=None, *, op="mma"):
  """Unblocked O(MKN)-memory oracle (tests only)."""
  sr = sr_mod.get(op)
  acc = sr.acc_dtype(a.dtype)
  if sr.boolean:
    a, b = a.astype(jnp.bool_), b.astype(jnp.bool_)
    prod = sr.otimes(a[..., :, :, None], b[..., None, :, :])
  else:
    prod = sr.otimes(a[..., :, :, None].astype(acc),
                     b[..., None, :, :].astype(acc))
  out = sr_mod.oplus_reduce(sr, prod, axis=-2)
  if c is not None:
    out = sr.oplus(out, c.astype(out.dtype))
  return out
