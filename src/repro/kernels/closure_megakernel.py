"""Fused Pallas closure megakernel — G fixpoint iterations per launch.

``_batched_fixpoint`` (core/closure.py) runs one device program per squaring
step: every iteration round-trips the whole (R, n, n) iterate through HBM and
re-reads it for the next contraction.  The TCU computational model
(arXiv:1908.06649) says exactly this off-chip traffic — not FLOPs — bounds
iterative matrix algorithms, so this kernel keeps each request's iterate
resident in VMEM and runs **G whole iterations per grid visit**:

  * grid = (requests, G, output row-blocks), all sequential (the flag
    outputs are whole-array SMEM buffers every step shares).
  * the output ref doubles as the on-chip iterate: initialized from the
    incoming stack at (g == 0, i == 0), updated in place each iteration, and
    flushed to HBM once per request — HBM traffic is paid once per G
    iterations instead of once per iteration.
  * per-request ``k_valid``/live-n, the incoming active flags, iteration
    counters, and the chunk's live-step budget are **scalar-prefetched**
    (the ragged-attention idiom): available before the body runs, so a
    frozen request's grid steps skip all contraction work via ``pl.when``
    without any host observation.
  * the per-request convergence reduction — ``_changed``'s inf-aware (and
    NaN-aware) compare — runs in-kernel on the last block of each iteration
    and lands in an SMEM output flag vector the host driver folds back into
    the surrounding ``lax.while_loop``.
  * a ``pl.CostEstimate`` tells XLA the launch covers R·G contractions'
    worth of flops over one chunk's worth of HBM bytes, so it schedules the
    fused program sanely instead of assuming one-matmul cost.

Why G-iteration chunks instead of unrolling the whole fixpoint on-chip: the
iterate must stay fully VMEM-resident (each iteration reads every row of the
previous one), which caps n (``MAX_N``), and worst-case trip counts (n−1 for
Bellman-Ford) would force a worst-case-sized launch even though most batches
converge early.  Chunking keeps the early-exit: the host ``while_loop`` asks
for at most G more iterations, re-checks ``any(active)``, and stops — frozen
requests inside a chunk cost one scalar test per grid step.

Bit-parity contract: outputs *and* per-request iteration counts match
``_batched_fixpoint`` exactly for every ring with a ⊗-identity (the parity
suite in tests/test_closure_megakernel.py pins this in interpret mode).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import closure as cl_mod
from repro.core import semiring as sr_mod
from repro.core.mmo import MXU_PRECISION
from repro.kernels.semiring_mmo import _LANES, _float_ring, _rup, rank1_fold

Array = jax.Array

DEFAULT_G = 8  # chunk length: fixpoint iterations fused per kernel launch

# Largest padded n the megakernel serves.  Each request's whole iterate is
# VMEM-resident: the double-buffered input and output blocks, the scratch
# iterate, and for Bellman-Ford the double-buffered adjacency — 7 × n² × 4 B,
# 28 MiB at n = 1024 and 112 MiB at n = 2048 against v5e's 128 MiB of VMEM.
# tests/test_tpu_compile.py compiles every pow2 bucket up to this cap for a
# described v5e; the engine routes larger closure buckets to the
# per-iteration path instead of letting a compile fail.
MAX_N = 1024

# Mosaic's own scratch on top of the blocks: the v5e compile needs ~12 MiB
# beyond them at every n from 256 to 1024
_VMEM_HEADROOM = 16 << 20


def vmem_limit_bytes(np_: int, itemsize: int, has_adj: bool) -> int:
  """Scoped-VMEM budget for one launch at padded size ``np_``."""
  mats = 2 + 2 + 1 + (2 if has_adj else 0)
  return mats * np_ * np_ * itemsize + _VMEM_HEADROOM


def _make_fixpoint_kernel(sr: sr_mod.Semiring, acc_dtype, nblk: int, bm: int,
                          has_adj: bool):
  """Kernel factory; ``has_adj`` selects Bellman-Ford (D ← D ⊕ (D ⊗ A),
  constant second operand) vs repeated squaring (C ← C ⊕ (C ⊗ C)).

  Each grid step computes one (bm, np_) row slab of the next iterate into
  scratch, one (bm, bn) column block at a time.  The min/max rings read K
  through the refs in lane-aligned chunks, bounded by the request's live n
  (isolated-vertex padding lanes are ⊕-identity no-ops — exact algebra);
  mma contracts each column block on the MXU over the full padded K, so its
  per-element summation order matches the per-iteration path.
  """
  oplus, otimes = _float_ring(sr)
  boolean = sr.boolean
  np_ = nblk * bm
  bn = ck = min(_LANES, np_)
  ncol, nchunk = np_ // bn, np_ // ck

  def rows(s):
    return pl.ds(pl.multiple_of(s * bm, bm), bm)

  def fixpoint_kernel(kv_ref, act0_ref, it0_ref, glim_ref, *refs):
    # scalar-prefetch refs first (SMEM, whole vectors, indexable by request)
    if has_adj:
      c_ref, b_ref, o_ref, it_ref, act_ref, new_ref = refs
    else:
      c_ref, o_ref, it_ref, act_ref, new_ref = refs
      b_ref = o_ref
    r = pl.program_id(0)
    g = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when((g == 0) & (i == 0))
    def _init():
      # seed the VMEM-resident iterate + this request's SMEM flags
      def seed(s, carry):
        o_ref[0, rows(s), :] = c_ref[0, rows(s), :].astype(acc_dtype)
        return carry
      jax.lax.fori_loop(0, nblk, seed, 0)
      it_ref[r] = it0_ref[r]
      act_ref[r] = act0_ref[r]

    # frozen requests (and steps past the request's live budget) skip every
    # contraction — one scalar test per grid step, no host round-trip.  The
    # budget is a per-request vector: the batched driver broadcasts one
    # chunk-wide value, the arena hands every slot its own remaining cap so
    # slots admitted at different times share a launch without over-running.
    live = (act_ref[r] != 0) & (g < glim_ref[r])

    @pl.when(live)
    def _compute():
      a_rows = rows(i)
      # live K chunks: the ragged masked-K trip count (chunk 0 always runs —
      # every live request has kv >= 1)
      nlive = jnp.clip((kv_ref[r] + ck - 1) // ck, 1, nchunk)

      def column(j, carry):
        cols = pl.ds(pl.multiple_of(j * bn, bn), bn)
        if sr.name == "mma":
          part = jnp.dot(o_ref[0, a_rows, :], b_ref[0, :, cols],
                         preferred_element_type=jnp.float32,
                         precision=MXU_PRECISION)
        else:
          def fold(c, acc):
            ks = pl.ds(pl.multiple_of(c * ck, ck), ck)
            return rank1_fold(oplus, otimes, acc,
                              o_ref[0, a_rows, ks].astype(acc_dtype),
                              b_ref[0, ks, cols].astype(acc_dtype))
          part = jax.lax.fori_loop(1, nlive, fold, fold(0, None))
        new_ref[a_rows, cols] = oplus(part, o_ref[0, a_rows, cols])
        return carry

      jax.lax.fori_loop(0, ncol, column, 0)

    @pl.when(live & (i == nblk - 1))
    def _commit():
      # every row slab of this iteration is in scratch: run the convergence
      # reduction (inf- and NaN-aware, matching core.closure._changed) and
      # advance the iterate + flags in place, one slab at a time
      def slab(s, ndiff):
        old = o_ref[0, rows(s), :]
        new = new_ref[rows(s), :]
        if boolean:
          same = new == old  # float {0,1} domain — plain equality is exact
        else:
          same = ((new == old)
                  | (jnp.isinf(new) & jnp.isinf(old)
                     & (jnp.sign(new) == jnp.sign(old)))
                  | (jnp.isnan(new) & jnp.isnan(old)))
        o_ref[0, rows(s), :] = new
        return ndiff + jnp.sum(jnp.logical_not(same).astype(jnp.int32))

      ndiff = jax.lax.fori_loop(0, nblk, slab, jnp.int32(0))
      it_ref[r] = it_ref[r] + 1
      act_ref[r] = (ndiff > 0).astype(jnp.int32)

  return fixpoint_kernel


def _chunk_call(c: Array, adj: Optional[Array], kv: Array, act: Array,
                it: Array, glim: Array, *, op: str, g_steps: int, bm: int,
                interpret: bool):
  """One megakernel launch: up to ``g_steps`` fixpoint iterations on-chip.

  ``glim`` is an (R,) int32 vector of per-request live-step budgets —
  request ``r`` runs ``min(glim[r], g_steps)`` iterations (fewer if it
  converges first).  Returns (iterate, iteration counters, active flags) —
  the pieces the host ``while_loop`` carries between chunks.
  """
  sr = sr_mod.get(op)
  acc_dtype = c.dtype
  r, np_ = c.shape[0], c.shape[-1]
  if np_ > MAX_N:
    raise ValueError(f"megakernel serves padded n <= {MAX_N}, got {np_}")
  nblk = np_ // bm
  has_adj = adj is not None
  kernel = _make_fixpoint_kernel(sr, acc_dtype, nblk, bm, has_adj)

  def mat_spec():
    return pl.BlockSpec((1, np_, np_), lambda rr, gg, ii, *_: (rr, 0, 0))

  def flag_spec():
    # whole (R,) flag vectors in SMEM: scalar stores, indexed by request
    return pl.BlockSpec(memory_space=pltpu.SMEM)

  in_specs = [mat_spec()]
  operands = [c]
  if has_adj:
    in_specs.append(mat_spec())
    operands.append(adj)

  itemsize = jnp.dtype(acc_dtype).itemsize
  # the whole point of the fusion: HBM traffic is one chunk's worth (read
  # the stack once, write it once, plus the constant A for Bellman-Ford),
  # while the flops cover all R·G contractions run from VMEM
  cost = pl.CostEstimate(
      flops=2 * r * g_steps * np_ * np_ * np_,
      bytes_accessed=itemsize * r * np_ * np_ * (2 + int(has_adj)),
      transcendentals=0,
  )

  out, it_out, act_out = pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=4,
          grid=(r, g_steps, nblk),
          in_specs=in_specs,
          out_specs=[mat_spec(), flag_spec(), flag_spec()],
          scratch_shapes=[pltpu.VMEM((np_, np_), acc_dtype)],
      ),
      out_shape=[
          jax.ShapeDtypeStruct((r, np_, np_), acc_dtype),
          jax.ShapeDtypeStruct((r,), jnp.int32),
          jax.ShapeDtypeStruct((r,), jnp.int32),
      ],
      # the flag outputs are whole-array SMEM buffers shared by every grid
      # step, so no grid dim may be split across cores
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
          vmem_limit_bytes=vmem_limit_bytes(np_, itemsize, has_adj)),
      cost_estimate=cost,
      interpret=interpret,
      name=f"simd2_fixpoint_{sr.name}",
  )(kv, act, it, glim, *operands)
  return out, it_out, act_out


class ChunkGeometry(NamedTuple):
  """Resolved kernel layout for one (ring, n, dtype) combination.

  Both megakernel callers — the batched ``megakernel_fixpoint`` driver and
  the request arena (serve_mmo/arena.py) — derive their buffers from this
  one resolver, so a slot admitted into the arena lands in a byte-identical
  layout to the same request stacked into a batch: bit-parity of the two
  paths is by construction, not by test luck.
  """
  was_bool: bool      # boolean ring: stored as float32 {0,1}, output > 0.5
  missing: float      # ⊕-identity fill for padded cells
  self_value: float   # ⊗-identity for padded diagonal (isolated vertices)
  acc_dtype: Any      # on-chip iterate dtype
  bm: int             # row-slab height (lane/sublane aligned)
  np_: int            # padded matrix dim (multiple of bm)
  interpret: bool     # Pallas interpret mode (CPU CI) vs compiled TPU


def chunk_geometry(op: str, n: int, dtype=jnp.float32, *, bm: int = 128,
                   interpret: Optional[bool] = None) -> ChunkGeometry:
  """Resolve the megakernel layout for ring ``op`` at true size ``n``.

  Raises for rings without a ⊗-identity (addnorm) — no isolated-vertex
  embedding exists, exactly like the per-iteration path refuses closure.
  """
  sr = sr_mod.get(op)
  missing, self_value = cl_mod.closure_pad_values(op)
  interp = (jax.default_backend() != "tpu") if interpret is None else (
      bool(interpret))
  was_bool = sr.boolean
  if was_bool:
    missing, self_value = float(missing), float(self_value)
  store = jnp.float32 if was_bool else jnp.dtype(dtype)
  acc_dtype = jnp.float32 if (sr.name == "mma" or was_bool) else (
      sr.acc_dtype(store))
  # lane/sublane-aligned padding; interpret mode keeps it minimal so the
  # CPU parity suite stays cheap
  bm_ = min(bm, _rup(n, 8 if interp else 128))
  np_ = _rup(n, bm_)
  return ChunkGeometry(was_bool=was_bool, missing=missing,
                       self_value=self_value, acc_dtype=acc_dtype,
                       bm=bm_, np_=np_, interpret=interp)


def fixpoint_iters(algorithm: str, n: int) -> int:
  """Default trip-count cap: the same bound both fixpoint paths use —
  Bellman-Ford needs n relaxation rounds, repeated squaring ⌈log2 n⌉."""
  if algorithm == "bellman_ford":
    return max(1, int(n))
  if algorithm == "leyzorek":
    import math
    return max(1, math.ceil(math.log2(max(n, 2))))
  raise ValueError(f"unknown algorithm {algorithm!r}")


def fixpoint_chunk(c: Array, adj: Optional[Array], kv: Array, act: Array,
                   it: Array, glim: Array, *, op: str, g_steps: int, bm: int,
                   interpret: bool):
  """Public chunk entry point — one fused launch of up to ``g_steps``
  fixpoint iterations over an (R, np_, np_) stack with per-request budgets.

  The arena jit-wraps this over its whole slot buffer each tick; operands
  must already be in ``chunk_geometry`` layout (padded, acc_dtype, bool
  rings as float32).  Returns (iterate, iteration counters, active flags).
  """
  return _chunk_call(c, adj, kv, act, it, glim, op=op, g_steps=g_steps,
                     bm=bm, interpret=interpret)


def _pad_closure(x: Array, np_: int, missing, self_value) -> Array:
  """Embed (R, n, n) into (R, np_, np_) as isolated vertices — the same
  stable-under-closure padding the serving bucketer uses, so the in-kernel
  convergence compare over the padded region never flips a flag."""
  r, n = x.shape[0], x.shape[-1]
  if np_ == n:
    return x
  out = jnp.full((r, np_, np_), jnp.asarray(missing, x.dtype), x.dtype)
  out = out.at[:, :n, :n].set(x)
  diag = jnp.arange(n, np_)
  return out.at[:, diag, diag].set(jnp.asarray(self_value, x.dtype))


@functools.partial(
    jax.jit,
    static_argnames=("op", "algorithm", "max_iters", "g", "bm", "interpret"))
def megakernel_fixpoint(adj: Array,
                        *,
                        op: str,
                        algorithm: str = "leyzorek",
                        max_iters: Optional[int] = None,
                        valid_n: Optional[Array] = None,
                        g: int = DEFAULT_G,
                        bm: int = 128,
                        interpret: Optional[bool] = None):
  """Whole-fixpoint driver: ``lax.while_loop`` over G-iteration megakernel
  chunks.  Drop-in replacement for ``core.closure._batched_fixpoint`` —
  same (closure, per-request iteration counts) contract, bit-identical
  results (the per-chunk live budget ``min(g, max_iters − i)`` keeps the
  ``max_iters`` cap exact even when G doesn't divide the trip count).
  """
  if adj.ndim != 3:
    raise ValueError(f"megakernel fixpoint needs (R, n, n) input, "
                     f"got {adj.shape}")
  if algorithm not in ("leyzorek", "bellman_ford"):
    raise ValueError(f"unknown algorithm {algorithm!r}")
  if g < 1:
    raise ValueError(f"chunk length g must be >= 1, got {g}")
  sr = sr_mod.get(op)

  r, n = adj.shape[0], adj.shape[-1]
  iters = fixpoint_iters(algorithm, n) if max_iters is None else max_iters

  # the shared layout resolver refuses rings without a ⊗-identity (addnorm)
  # — no isolated-vertex embedding exists, like the per-iteration path
  was_bool = sr.boolean
  x = adj.astype(jnp.float32) if was_bool else adj
  geom = chunk_geometry(op, n, adj.dtype, bm=bm, interpret=interpret)
  acc_dtype, bm_, np_, interp = (geom.acc_dtype, geom.bm, geom.np_,
                                 geom.interpret)
  c0 = _pad_closure(x.astype(acc_dtype), np_, geom.missing, geom.self_value)
  adj_operand = c0 if algorithm == "bellman_ford" else None

  if valid_n is None:
    kv = jnp.full((r,), n, jnp.int32)
  else:
    kv = jnp.asarray(valid_n, jnp.int32)

  g_steps = min(g, iters)

  def cond(state):
    _, active, _, i = state
    return jnp.any(active) & (i < iters)

  def body(state):
    c, active, it, i = state
    glim = jnp.minimum(jnp.asarray(g_steps, jnp.int32),
                       jnp.asarray(iters, jnp.int32) - i)
    c2, it2, act2 = _chunk_call(
        c, adj_operand, kv, active.astype(jnp.int32), it,
        jnp.full((r,), glim, jnp.int32),
        op=op, g_steps=g_steps, bm=bm_, interpret=interp)
    return c2, act2 > 0, it2, i + glim

  state0 = (c0, jnp.ones((r,), jnp.bool_), jnp.zeros((r,), jnp.int32),
            jnp.asarray(0, jnp.int32))
  out, _, iters_run, _ = jax.lax.while_loop(cond, body, state0)
  out = out[:, :n, :n]
  if was_bool:
    out = out > 0.5
  return out, iters_run
