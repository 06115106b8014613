"""Pallas TPU kernel for the Mamba2/SSD intra-chunk contraction.

The SSD "dual form" intra-chunk term is literally a masked semiring-like
matrix operation (DESIGN.md §4):

    Y[q, p] = Σ_k  (C_q · B_k)  ·  exp(cum_q − cum_k) · 1[k ≤ q]  ·  dt_k · X[k, p]
              └── MXU dot ──┘   └──── decay mask L (VPU) ────┘     └─ MXU dot ─┘

One grid cell = one (batch·chunk, head) tile: C/B (Q,N), X (Q,P), dt/cum (Q)
all resident in VMEM; two MXU matmuls bracket a VPU mask — the same
dataflow as the SIMD² unit with a fused ⊗-stage decay.  Q=256, N=128, P=64
⇒ ~0.6 MiB VMEM/cell.  Validated in interpret mode against the einsum
oracle (tests/test_kernels_ssd.py); models/ssm.py keeps the XLA einsum as
the dry-run path (Mosaic is TPU-only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(c_ref, b_ref, x_ref, dt_ref, cum_ref, o_ref):
  f32 = jnp.float32
  c = c_ref[0, 0].astype(f32)          # (Q, N)
  b = b_ref[0, 0].astype(f32)          # (Q, N)
  x = x_ref[0, 0].astype(f32)          # (Q, P)
  dt = dt_ref[0, 0].astype(f32)        # (Q, 1)
  cum = cum_ref[0, 0].astype(f32)      # (Q, 1)

  scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=f32)       # (Q, Q) MXU
  q = scores.shape[0]
  seg = cum[:, 0][:, None] - cum[:, 0][None, :]                  # cum_q−cum_k
  iq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
  ik = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
  decay = jnp.where(ik <= iq, jnp.exp(seg), 0.0)                 # L mask VPU
  p_mat = scores * decay * dt[:, 0][None, :]                     # (Q, Q)
  y = jax.lax.dot_general(p_mat, x, (((1,), (0,)), ((), ())),
                          preferred_element_type=f32)            # (Q, P) MXU
  o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(c: Array, b: Array, xh: Array, dt: Array, cum: Array,
                    *, interpret: bool = False) -> Array:
  """Intra-chunk SSD output.

  c, b: (BZ, H, Q, N) per-head (group-expanded) projections;
  xh:   (BZ, H, Q, P); dt, cum: (BZ, H, Q).  Returns y (BZ, H, Q, P).
  (BZ = batch·n_chunks; the inter-chunk recurrence stays in JAX.)
  """
  bz, h, q, n = c.shape
  p = xh.shape[-1]
  dt2 = dt[..., None]                                   # (BZ,H,Q,1)
  cum2 = cum[..., None]

  spec_qn = pl.BlockSpec((1, 1, q, n), lambda i, j: (i, j, 0, 0))
  spec_qp = pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0))
  spec_q1 = pl.BlockSpec((1, 1, q, 1), lambda i, j: (i, j, 0, 0))

  return pl.pallas_call(
      _kernel,
      grid=(bz, h),
      in_specs=[spec_qn, spec_qn, spec_qp, spec_q1, spec_q1],
      out_specs=spec_qp,
      out_shape=jax.ShapeDtypeStruct((bz, h, q, p), jnp.float32),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel")),
      interpret=interpret,
      name="ssd_intra_chunk",
  )(c, b, xh, dt2, cum2)


def ssd_intra_chunk_ref(c, b, xh, dt, cum):
  """einsum oracle (identical math to models/ssm.ssd_chunked's y_diag)."""
  f32 = jnp.float32
  scores = jnp.einsum("zhqn,zhkn->zhqk", c.astype(f32), b.astype(f32))
  seg = cum.astype(f32)[..., :, None] - cum.astype(f32)[..., None, :]
  qlen = c.shape[-2]
  mask = jnp.tril(jnp.ones((qlen, qlen), bool))
  decay = jnp.where(mask, jnp.exp(seg), 0.0)
  return jnp.einsum("zhqk,zhk,zhkp->zhqp", scores * decay, dt.astype(f32),
                    xh.astype(f32))
