"""Distributed SIMD² — semiring matmuls and closures on a device mesh.

The paper scales SIMD² across SMs inside one GPU; at pod scale the analogous
question is how the ⊕/⊗ contraction maps onto collectives.  Because every
SIMD² ⊕ is one of {+, min, max, or}, **the cross-device reduction is always
expressible as psum/pmin/pmax** — a "generalized matmul" needs only a
generalized all-reduce.  Three schedules are provided:

  * ``mmo_kspan``      — K-sharded: local partial contraction then a single
                         ⊕-all-reduce.  Minimum collective volume when K is
                         the big axis (one M×N reduce).
  * ``summa_mmo``      — 2-D blocked SUMMA: A row-panels all-gathered along
                         the model axis, B col-panels along the data axis,
                         local contraction on (M/p, K)×(K, N/q) blocks.
                         This is the workhorse for distributed closures where
                         the *same* matrix is squared (Leyzorek), since C
                         stays 2-D-sharded in place across iterations.
  * ``ring_mmo``       — SUMMA with the all-gather replaced by K-step
                         collective_permute so each chunk's contraction
                         overlaps the transfer of the next (compute/comm
                         overlap; the beyond-paper schedule measured in
                         EXPERIMENTS.md §Perf).

All three return bit-identical results (tests assert so on a host-device
mesh) and accept every registered op.

Each schedule also has a **batched** variant (``*_batched``) over a leading
replicated request axis — the serving engine's sharded execution path: one
bucket batch too big for a single device runs the same contraction with its
problem axes sharded across the mesh, while the request axis stays whole so
per-request ``k_valid`` masks (ragged masked-K, PR 2) keep working.  K-sharded
schedules rebase ``k_valid`` per shard/step, so ragged work skipping survives
distribution.  ``sharded_closure_batched`` runs the batched Leyzorek /
Bellman-Ford fixpoint (per-request convergence masks and all) with every ⊕/⊗
step executing as a mesh schedule — SUMMA squaring being the workhorse, since
C stays 2-D-sharded in place across iterations.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core.mmo import mmo as _mmo
from repro.core import semiring as sr_mod

Array = jax.Array


def make_mesh(shape, axes=("data", "model"), *, devices=None) -> Mesh:
  """A device mesh whose axes are all Auto: jit places the schedules'
  shard_maps on it without entering a ``jax.set_mesh`` context (the
  default Explicit axes would demand one for every call)."""
  return jax.make_mesh(tuple(shape), tuple(axes),
                       axis_types=(AxisType.Auto,) * len(axes),
                       devices=devices)


def _varying(x, axis):
  """Mark a replicated loop carry as varying over ``axis`` so shard_map's
  type check accepts the per-device values the loop writes into it."""
  return jax.lax.pcast(x, (axis,), to="varying")


def _local_contract(a, b, sr_name, backend):
  return _mmo(a, b, None, op=sr_name, backend=backend)


def mmo_kspan(a: Array, b: Array, c: Optional[Array], *, op: str, mesh: Mesh,
              axis: str = "model", backend: str = "auto") -> Array:
  """K-sharded contraction + ⊕-all-reduce along ``axis``.

  A: (M, K) sharded on K over ``axis``; B: (K, N) sharded on K; C/D
  replicated along ``axis``.
  """
  sr = sr_mod.get(op)

  def kernel(a_blk, b_blk, c_blk):
    part = _local_contract(a_blk, b_blk, sr.name, backend)
    full = sr_mod.oplus_allreduce(sr, part, axis)
    if c_blk is not None:
      full = sr.oplus(full, c_blk.astype(full.dtype))
    return full

  in_specs = (P(None, axis), P(axis, None),
              None if c is None else P(None, None))
  fn = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                     out_specs=P(None, None))
  return fn(a, b, c)


def summa_mmo(a: Array, b: Array, c: Optional[Array], *, op: str, mesh: Mesh,
              row_axis: str = "data", col_axis: str = "model",
              backend: str = "auto") -> Array:
  """2-D SUMMA: operands and result all 2-D block-sharded (row_axis, col_axis).

  Per device: all-gather A's K-panels along ``col_axis`` (row broadcast) and
  B's K-panels along ``row_axis`` (column broadcast), contract locally.
  """
  sr = sr_mod.get(op)

  def kernel(a_blk, b_blk, c_blk):
    a_row = jax.lax.all_gather(a_blk, col_axis, axis=1, tiled=True)
    b_col = jax.lax.all_gather(b_blk, row_axis, axis=0, tiled=True)
    out = _local_contract(a_row, b_col, sr.name, backend)
    if c_blk is not None:
      out = sr.oplus(out, c_blk.astype(out.dtype))
    return out

  spec = P(row_axis, col_axis)
  fn = jax.shard_map(kernel, mesh=mesh,
                     in_specs=(spec, spec, None if c is None else spec),
                     out_specs=spec)
  return fn(a, b, c)


def ring_mmo(a: Array, b: Array, c: Optional[Array], *, op: str, mesh: Mesh,
             axis: str = "model", backend: str = "auto") -> Array:
  """1-D ring schedule: B K-sharded along ``axis`` and rotating; device j owns
  output columns C[:, Nj] and ⊕-accumulates one K-chunk's contribution per
  step.  Each step's contraction overlaps the next chunk's collective-permute
  (the overlapped alternative to SUMMA's blocking all-gather; compared in
  EXPERIMENTS.md §Perf)."""
  sr = sr_mod.get(op)
  n_dev = mesh.shape[axis]

  def kernel(a_blk, b_blk, c_blk):
    # a_blk: (M, K) replicated; b_blk: (K/p, N) rotating K-chunk.
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    k_chunk = b_blk.shape[0]
    n_cols = b_blk.shape[1] // n_dev  # my output column block

    def step(i, state):
      b_cur, acc = state
      # after i forward rotations the chunk held here originated at device
      # (idx - i) mod p → it holds K rows [src*k_chunk, ...).
      src = (idx - i) % n_dev
      a_piece = jax.lax.dynamic_slice_in_dim(a_blk, src * k_chunk, k_chunk, 1)
      b_cols = jax.lax.dynamic_slice_in_dim(b_cur, idx * n_cols, n_cols, 1)
      part = _local_contract(a_piece, b_cols, sr.name, backend)
      acc = sr.oplus(acc, part.astype(acc.dtype))
      b_nxt = jax.lax.ppermute(b_cur, axis, perm)
      return b_nxt, acc

    m = a_blk.shape[0]
    acc0 = sr.identity_like((m, n_cols), sr.acc_dtype(a_blk.dtype))
    acc0 = _varying(acc0, axis)
    _, acc = jax.lax.fori_loop(0, n_dev, step, (b_blk, acc0))
    if c_blk is not None:
      acc = sr.oplus(acc, c_blk.astype(acc.dtype))
    return acc

  in_specs = (P(None, None), P(axis, None),
              None if c is None else P(None, axis))
  fn = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                     out_specs=P(None, axis))
  return fn(a, b, c)


# ---------------------------------------------------------------------------
# Batched schedules — a leading request axis (the serving engine's sharded
# bucket-batch path).  kspan/summa/ring shard the *problem* axes and keep the
# request axis replicated (specs mirror the unbatched variants with a ``None``
# prepended); ``dp`` shards the *request* axis over every mesh device and
# needs no collectives at all.  ``k_valid`` is one live-K count per request.
# ---------------------------------------------------------------------------

SCHEDULES = ("dp", "kspan", "summa", "ring")


def _dp_axes(mesh: Mesh) -> tuple:
  """The composite leading-axis sharding for dp: every mesh axis at once."""
  return tuple(mesh.axis_names)


def _local_kv(kv, axis, k_chunk):
  """Rebase a per-request global live-K count onto this shard's K-chunk
  [idx·k_chunk, (idx+1)·k_chunk): lanes before the chunk are someone else's,
  lanes past the global count are dead pads either way."""
  if kv is None:
    return None
  idx = jax.lax.axis_index(axis)
  return jnp.clip(kv - idx * k_chunk, 0, k_chunk)


def mmo_dp_batched(a: Array, b: Array, c: Optional[Array] = None, *,
                   op: str, mesh: Mesh, backend: str = "xla",
                   block: Optional[tuple] = None,
                   interpret: Optional[bool] = None,
                   k_valid: Optional[Array] = None) -> Array:
  """Batched data-parallel contraction: requests sharded over all devices.

  Each device contracts its own R/P requests locally — zero collectives,
  the vLLM-style scale-out schedule for a bucket batch of *independent*
  problems.  Requires R divisible by the mesh's device count (the engine
  falls back to 'local' for partial batches).
  """
  if a.shape[0] % mesh.size:
    raise ValueError(f"dp needs the request axis ({a.shape[0]}) divisible by "
                     f"the mesh's {mesh.size} devices")
  sr = sr_mod.get(op)
  axes = _dp_axes(mesh)
  spec = P(axes, None, None)

  def kernel(a_blk, b_blk, c_blk, kv):
    return _mmo(a_blk, b_blk, c_blk, op=sr.name, backend=backend,
                block=block or None, interpret=interpret, k_valid=kv)

  in_specs = (spec, spec, None if c is None else spec,
              None if k_valid is None else P(axes))
  # the ragged masked-K path lowers its dynamic K-block trip count to a
  # `while`, which has no varying-axis rule: those calls skip the check
  fn = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs, out_specs=spec,
                     check_vma=k_valid is None)
  return fn(a, b, c, k_valid)


def mmo_kspan_batched(a: Array, b: Array, c: Optional[Array] = None, *,
                      op: str, mesh: Mesh, axis: str = "model",
                      backend: str = "xla", block: Optional[tuple] = None,
                      interpret: Optional[bool] = None,
                      k_valid: Optional[Array] = None) -> Array:
  """Batched K-sharded contraction + ⊕-all-reduce along ``axis``.

  A: (R, M, K) and B: (R, K, N) sharded on K; C/D and ``k_valid`` replicated.
  """
  sr = sr_mod.get(op)
  k_chunk = a.shape[-1] // mesh.shape[axis]

  def kernel(a_blk, b_blk, c_blk, kv):
    part = _mmo(a_blk, b_blk, None, op=sr.name, backend=backend,
                block=block or None, interpret=interpret,
                k_valid=_local_kv(kv, axis, k_chunk))
    full = sr_mod.oplus_allreduce(sr, part, axis)
    if c_blk is not None:
      full = sr.oplus(full, c_blk.astype(full.dtype))
    return full

  in_specs = (P(None, None, axis), P(None, axis, None),
              None if c is None else P(None, None, None),
              None if k_valid is None else P(None))
  fn = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                     out_specs=P(None, None, None),
                     check_vma=k_valid is None)
  return fn(a, b, c, k_valid)


def summa_mmo_batched(a: Array, b: Array, c: Optional[Array] = None, *,
                      op: str, mesh: Mesh, row_axis: str = "data",
                      col_axis: str = "model", backend: str = "xla",
                      block: Optional[tuple] = None,
                      interpret: Optional[bool] = None,
                      k_valid: Optional[Array] = None) -> Array:
  """Batched 2-D SUMMA: operands/result 2-D block-sharded per request.

  Each device all-gathers its K-panels and contracts a (M/p, K)×(K, N/q)
  block per request; K is whole after the gathers, so ``k_valid`` applies
  unrebased.
  """
  sr = sr_mod.get(op)

  def kernel(a_blk, b_blk, c_blk, kv):
    a_row = jax.lax.all_gather(a_blk, col_axis, axis=2, tiled=True)
    b_col = jax.lax.all_gather(b_blk, row_axis, axis=1, tiled=True)
    out = _mmo(a_row, b_col, None, op=sr.name, backend=backend,
               block=block or None, interpret=interpret, k_valid=kv)
    if c_blk is not None:
      out = sr.oplus(out, c_blk.astype(out.dtype))
    return out

  spec = P(None, row_axis, col_axis)
  fn = jax.shard_map(kernel, mesh=mesh,
                     in_specs=(spec, spec, None if c is None else spec,
                               None if k_valid is None else P(None)),
                     out_specs=spec, check_vma=k_valid is None)
  return fn(a, b, c, k_valid)


def ring_mmo_batched(a: Array, b: Array, c: Optional[Array] = None, *,
                     op: str, mesh: Mesh, axis: str = "model",
                     backend: str = "xla", block: Optional[tuple] = None,
                     interpret: Optional[bool] = None,
                     k_valid: Optional[Array] = None) -> Array:
  """Batched 1-D ring: B K-sharded and rotating, device j owns output
  columns D[:, :, Nj]; each step's contraction overlaps the next permute."""
  sr = sr_mod.get(op)
  n_dev = mesh.shape[axis]

  def kernel(a_blk, b_blk, c_blk, kv):
    # a_blk: (R, M, K) replicated; b_blk: (R, K/p, N) rotating K-chunk.
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    k_chunk = b_blk.shape[1]
    n_cols = b_blk.shape[2] // n_dev

    def step(i, state):
      b_cur, acc = state
      src = (idx - i) % n_dev  # chunk origin after i forward rotations
      a_piece = jax.lax.dynamic_slice_in_dim(a_blk, src * k_chunk, k_chunk, 2)
      b_cols = jax.lax.dynamic_slice_in_dim(b_cur, idx * n_cols, n_cols, 2)
      kv_step = None if kv is None else jnp.clip(kv - src * k_chunk, 0,
                                                 k_chunk)
      part = _mmo(a_piece, b_cols, None, op=sr.name, backend=backend,
                  block=block or None, interpret=interpret, k_valid=kv_step)
      acc = sr.oplus(acc, part.astype(acc.dtype))
      b_nxt = jax.lax.ppermute(b_cur, axis, perm)
      return b_nxt, acc

    r, m = a_blk.shape[0], a_blk.shape[1]
    acc0 = sr.identity_like((r, m, n_cols), sr.acc_dtype(a_blk.dtype))
    acc0 = _varying(acc0, axis)
    _, acc = jax.lax.fori_loop(0, n_dev, step, (b_blk, acc0))
    if c_blk is not None:
      acc = sr.oplus(acc, c_blk.astype(acc.dtype))
    return acc

  in_specs = (P(None, None, None), P(None, axis, None),
              None if c is None else P(None, None, axis),
              None if k_valid is None else P(None))
  fn = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                     out_specs=P(None, None, axis),
                     check_vma=k_valid is None)
  return fn(a, b, c, k_valid)


def mmo_sharded_batched(a: Array, b: Array, c: Optional[Array] = None, *,
                        op: str, schedule: str, mesh: Mesh,
                        backend: str = "xla", block: Optional[tuple] = None,
                        interpret: Optional[bool] = None,
                        k_valid: Optional[Array] = None) -> Array:
  """One batched mesh schedule by name — the engine's sharded entry point.

  Axis convention: the mesh's first axis is the SUMMA row axis, its last the
  SUMMA column / K-span / ring axis (a (1, p) mesh therefore runs kspan and
  ring over all p devices and SUMMA as a 1×p column split).
  """
  row_axis, col_axis = mesh.axis_names[0], mesh.axis_names[-1]
  if schedule == "dp":
    return mmo_dp_batched(a, b, c, op=op, mesh=mesh, backend=backend,
                          block=block, interpret=interpret, k_valid=k_valid)
  if schedule == "kspan":
    return mmo_kspan_batched(a, b, c, op=op, mesh=mesh, axis=col_axis,
                             backend=backend, block=block,
                             interpret=interpret, k_valid=k_valid)
  if schedule == "summa":
    return summa_mmo_batched(a, b, c, op=op, mesh=mesh, row_axis=row_axis,
                             col_axis=col_axis, backend=backend, block=block,
                             interpret=interpret, k_valid=k_valid)
  if schedule == "ring":
    return ring_mmo_batched(a, b, c, op=op, mesh=mesh, axis=col_axis,
                            backend=backend, block=block,
                            interpret=interpret, k_valid=k_valid)
  raise ValueError(f"unknown schedule {schedule!r}; pick from {SCHEDULES}")


def schedule_fits(schedule: str, m: int, k: int, n: int, mesh: Mesh) -> bool:
  """Whether a contraction's problem axes divide evenly onto the mesh for
  one schedule (shard_map requires exact partitions; bucket dims are powers
  of two, so any pow2 mesh axis ≤ the dim fits)."""
  rows, cols = mesh.shape[mesh.axis_names[0]], mesh.shape[mesh.axis_names[-1]]
  if schedule == "dp":
    return True  # no problem-axis constraint; the request axis is checked
    # at batch-build time (the engine falls back to 'local' when the padded
    # batch doesn't divide over the mesh)
  if schedule == "kspan":
    return k % cols == 0
  if schedule == "summa":
    # K is sharded over cols on A and over rows on B before the all-gathers
    return (m % rows == 0 and n % cols == 0
            and k % rows == 0 and k % cols == 0)
  if schedule == "ring":
    return k % cols == 0 and n % cols == 0
  return False


def sharded_closure_batched(adj: Array, *, op: str,
                            algorithm: str = "leyzorek",
                            mesh: Mesh, schedule: str = "summa",
                            backend: str = "xla",
                            block: Optional[tuple] = None,
                            interpret: Optional[bool] = None,
                            max_iters: Optional[int] = None,
                            valid_n: Optional[Array] = None):
  """Batched semiring fixpoint with the mesh schedule threaded through.

  For the contraction schedules (kspan/summa/ring) this reuses the batched
  closure machinery (per-request convergence masks, converged requests
  dropping to ``k_valid=0``) with the mmo step swapped for a mesh schedule.
  SUMMA is the natural choice — C stays 2-D-sharded in place between
  iterations — but any schedule name works (GSPMD reshards between steps
  for the others).

  ``"dp"`` instead shards the *request* axis and runs one independent
  fixpoint per device: each shard's ``while`` loop exits as soon as its own
  requests converge, so a straggler (a high-diameter graph that needs the
  full lg(n) squarings) no longer drags every other request through its
  extra iterations — the schedule that wins whenever a bucket batch mixes
  convergence speeds.  Returns (closure, per-request iterations).
  """
  if schedule == "dp":
    if adj.shape[0] % mesh.size:
      raise ValueError(f"dp needs the request axis ({adj.shape[0]}) "
                       f"divisible by the mesh's {mesh.size} devices")
    fn = _dp_closure_fn(op, algorithm, backend, block, interpret,
                        max_iters, valid_n is not None, mesh)
    return fn(adj, valid_n)

  solver = _closure_solver(algorithm)
  return solver(adj, op=op, backend=backend,
                mmo_fn=_sched_mmo_fn(schedule, mesh, backend, block,
                                     interpret),
                max_iters=max_iters, valid_n=valid_n)


def _closure_solver(algorithm: str):
  from repro.core import closure as cl_mod  # local import: no cycle at load
  return (cl_mod.batched_leyzorek_closure if algorithm == "leyzorek"
          else cl_mod.batched_bellman_ford_closure)


@functools.lru_cache(maxsize=None)
def _sched_mmo_fn(schedule: str, mesh: Mesh, backend: str,
                  block: Optional[tuple] = None,
                  interpret: Optional[bool] = None):
  """One mmo_fn per (schedule, mesh, backend) — the solvers jit with
  ``mmo_fn`` as a static argument (hashed by identity), so handing them a
  fresh closure per call would retrace the whole fixpoint every time."""

  def mmo_fn(a, b, c, op_, bk, k_valid=None):
    del bk  # same value as the memoized ``backend`` (the solver echoes it)
    return mmo_sharded_batched(a, b, c, op=op_, schedule=schedule, mesh=mesh,
                               backend=backend, block=block,
                               interpret=interpret, k_valid=k_valid)

  return mmo_fn


@functools.lru_cache(maxsize=None)
def _local_mmo_fn(block: Optional[tuple], interpret: Optional[bool]):
  """Shard-local mmo step honoring a tuned block config / interpret flag;
  None (default settings) lets the solver use its own default step."""
  if not block and interpret is None:
    return None

  def mmo_fn(a, b, c, op_, bk, k_valid=None):
    return _mmo(a, b, c, op=op_, backend=bk, block=block or None,
                interpret=interpret, k_valid=k_valid)

  return mmo_fn


@functools.lru_cache(maxsize=None)
def _dp_closure_fn(op: str, algorithm: str, backend: str,
                   block: Optional[tuple], interpret: Optional[bool],
                   max_iters: Optional[int], has_valid: bool, mesh: Mesh):
  """Memoized jitted dp fixpoint (stable identity → stable jit cache)."""
  solver = _closure_solver(algorithm)
  axes = _dp_axes(mesh)

  def kernel(adj_blk, vn_blk):
    return solver(adj_blk, op=op, backend=backend, mmo_fn=_local_mmo_fn(
        block, interpret), max_iters=max_iters, valid_n=vn_blk)

  return jax.jit(jax.shard_map(
      kernel, mesh=mesh,
      in_specs=(P(axes, None, None), P(axes) if has_valid else None),
      out_specs=(P(axes, None, None), P(axes)),
      check_vma=False))  # per-shard fixpoint lowers to `while`


# ---------------------------------------------------------------------------
# Distributed closure (Leyzorek on a 2-D-sharded matrix via SUMMA squaring).
# ---------------------------------------------------------------------------


def distributed_leyzorek(adj: Array, *, op: str, mesh: Mesh,
                         row_axis: str = "data", col_axis: str = "model",
                         max_iters: Optional[int] = None,
                         backend: str = "auto"):
  """C ← C ⊕ (C ⊗ C) with C living 2-D-sharded across the mesh the whole
  time; only K-panels move (SUMMA all-gathers) per iteration."""
  import math
  n = adj.shape[-1]
  iters = max_iters if max_iters is not None else max(
      1, math.ceil(math.log2(max(n, 2))))

  @functools.partial(jax.jit, donate_argnums=0)
  def run(c):
    def body(_, cur):
      return summa_mmo(cur, cur, cur, op=op, mesh=mesh, row_axis=row_axis,
                       col_axis=col_axis, backend=backend)
    return jax.lax.fori_loop(0, iters, body, c)

  spec = jax.sharding.NamedSharding(mesh, P(row_axis, col_axis))
  adj = jax.device_put(adj, spec)
  return run(adj)
