"""Pad-and-stack micro-batcher: bucket → one compiled program.

Three pieces per bucket:

  ``stack_batch``    — host-side: pad every request's operands to the bucket
                       shape and stack along a new leading request axis.
                       Padding is algebra-aware so it is a semantic no-op:
                       K-axis pads use core.semiring.contraction_pads (⊗ of
                       pads == ⊕-identity), adjacency pads add isolated
                       vertices (core.closure.closure_pad_values), and KNN
                       batches carry a per-request valid-row count so padded
                       corpus rows are masked to +inf before top-k (data-
                       scale independent — no magic far-away sentinel).
                       mmo/closure batches additionally carry a per-request
                       live-K / valid-n vector: because the padding is an
                       algebraic no-op, the backends may *skip* dead K work
                       instead of computing it (ragged masked-K execution).
                       ``inert`` trailing slots hold no request at all: a
                       live size of 0 and operands that are already the
                       answer (a closure slot is its own fixpoint), so a
                       dp shard holding only those leaves its fixpoint at
                       the first convergence check.
  ``make_batch_fn``  — the pure jax function the executable cache compiles:
                       mmo_batched / batched_*_closure (per-request
                       convergence masks) / addnorm+top-k.
  ``split_results``  — slice the padded batch output back to each request's
                       true shape.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import closure as cl_mod
from repro.core import semiring as sr_mod
from repro.core.mmo import mmo_batched
from repro.serve_mmo.api import MMOResult, ProblemRequest
from repro.serve_mmo.scheduler import BucketKey

def _pad2d(x: np.ndarray, rows: int, cols: int,
           row_val, col_val) -> np.ndarray:
  """Pad a 2-D array to (rows, cols); new rows get row_val, new cols col_val."""
  out = np.full((rows, cols), col_val, dtype=x.dtype)
  out[x.shape[0]:, :] = row_val
  out[:x.shape[0], :x.shape[1]] = x
  return out


@functools.lru_cache(maxsize=64)
def _filled(shape: tuple, value, dtype: str) -> np.ndarray:
  """A read-only ``shape`` array of ``value``: one inert slot's operand."""
  out = np.full(shape, value, np.dtype(dtype))
  out.flags.writeable = False
  return out


@functools.lru_cache(maxsize=64)
def _empty_graph(op: str, nb: int, dtype: str) -> np.ndarray:
  """``nb`` isolated vertices: the closure slot that is its own fixpoint."""
  out = cl_mod.pad_adjacency(np.zeros((0, 0), np.dtype(dtype)), nb, op=op)
  out.flags.writeable = False
  return out


def _stack_mmo(key: BucketKey, reqs: Sequence[ProblemRequest], inert: int):
  mb, kb, nb = key.shape
  pa, pb = sr_mod.contraction_pads(key.op)
  boolean = sr_mod.get(key.op).boolean
  if boolean:
    pa = pb = False
  (has_c,) = key.params
  a = np.stack([_pad2d(r.arrays["a"], mb, kb, pa, pa) for r in reqs]
               + [_filled((mb, kb), pa, key.dtypes[0])] * inert)
  b = np.stack([_pad2d(r.arrays["b"], kb, nb, pb, pb) for r in reqs]
               + [_filled((kb, nb), pb, key.dtypes[1])] * inert)
  # per-request live-K: lanes beyond a request's true K are contraction pads
  # (⊗(pa, pb) == ⊕-identity), so backends may skip them (ragged masked-K)
  kv = np.asarray([r.shape[1] for r in reqs] + [0] * inert, np.int32)
  if not has_c:
    return (a, b, kv)
  ident = False if boolean else sr_mod.get(key.op).oplus_identity
  c = np.stack([_pad2d(r.arrays["c"], mb, nb, ident, ident) for r in reqs]
               + [_filled((mb, nb), ident, key.dtypes[2])] * inert)
  return (a, b, c, kv)


def _stack_closure(key: BucketKey, reqs: Sequence[ProblemRequest],
                   inert: int):
  (nb,) = key.shape
  adj = np.stack([cl_mod.pad_adjacency(r.arrays["adj"], nb, op=key.op)
                  for r in reqs]
                 + [_empty_graph(key.op, nb, key.dtypes[0])] * inert)
  # true problem sizes: rows/cols beyond valid[r] are isolated-vertex padding
  valid = np.asarray([r.shape[0] for r in reqs] + [0] * inert, np.int32)
  return (adj, valid)


def _stack_knn(key: BucketKey, reqs: Sequence[ProblemRequest], inert: int):
  qb, rb, db = key.shape
  # all pads are zeros (query pad rows' outputs are sliced away; padded dims
  # contribute (0-0)²=0 for real rows); ``valid`` carries each request's true
  # corpus size so the compiled program can mask padded rows out of top-k.
  q = np.stack([_pad2d(r.arrays["queries"], qb, db, 0.0, 0.0) for r in reqs]
               + [_filled((qb, db), 0.0, key.dtypes[0])] * inert)
  ref = np.stack([_pad2d(r.arrays["corpus"], rb, db, 0.0, 0.0) for r in reqs]
                 + [_filled((rb, db), 0.0, key.dtypes[1])] * inert)
  valid = np.asarray([r.arrays["corpus"].shape[0] for r in reqs]
                     + [0] * inert, np.int32)
  return (q, ref, valid)


def stack_batch(key: BucketKey, reqs: Sequence[ProblemRequest],
                inert: int = 0):
  """Pad + stack all request operands for one bucket batch, then ``inert``
  slots that hold no request (module docstring)."""
  if key.kind == "mmo":
    return _stack_mmo(key, reqs, inert)
  if key.kind == "closure":
    return _stack_closure(key, reqs, inert)
  if key.kind == "knn":
    return _stack_knn(key, reqs, inert)
  raise ValueError(f"unknown kind {key.kind!r}")


def stacked_nbytes(stacked) -> int:
  """Bytes one stacked batch stages host→device (pads included) — the H2D
  traffic gauge the engine's metrics and trace spans report per batch."""
  return sum(int(a.nbytes) for a in stacked)


def abstract_batch(key: BucketKey, batch: int):
  """ShapeDtypeStructs matching ``stack_batch``'s output for ``batch``
  requests — lets prewarm compile executables without materializing data."""
  if key.kind == "mmo":
    mb, kb, nb = key.shape
    (has_c,) = key.params
    shapes = [(batch, mb, kb), (batch, kb, nb)]
    if has_c:
      shapes.append((batch, mb, nb))
    return tuple(jax.ShapeDtypeStruct(s, np.dtype(dt))
                 for s, dt in zip(shapes, key.dtypes)) + (
        jax.ShapeDtypeStruct((batch,), np.dtype(np.int32)),)
  if key.kind == "closure":
    (nb,) = key.shape
    return (jax.ShapeDtypeStruct((batch, nb, nb), np.dtype(key.dtypes[0])),
            jax.ShapeDtypeStruct((batch,), np.dtype(np.int32)))
  if key.kind == "knn":
    qb, rb, db = key.shape
    return (jax.ShapeDtypeStruct((batch, qb, db), np.dtype(key.dtypes[0])),
            jax.ShapeDtypeStruct((batch, rb, db), np.dtype(key.dtypes[1])),
            jax.ShapeDtypeStruct((batch,), np.dtype(np.int32)))
  raise ValueError(f"unknown kind {key.kind!r}")


# ---------------------------------------------------------------------------
# compiled-program construction
# ---------------------------------------------------------------------------


def make_batch_fn(key: BucketKey, *, backend: str, block: tuple = (),
                  interpret: Optional[bool] = None,
                  mesh=None, schedule: str = "local"):
  """Pure jax function over the stacked operands for one bucket.

  ``backend``/``block`` are the bucket's dispatch decision (resolved once at
  batch-build time by the engine and baked into the executable-cache key), so
  a mixed-backend steady state replays stored executables and never retraces.

  ``schedule`` places the bucket: ``"local"`` runs the single-device batched
  entry points; a name from ``core.distributed.SCHEDULES`` runs the same
  contraction sharded over ``mesh`` — kspan/SUMMA/ring shard the problem
  axes, ``"dp"`` shards the request axis (independent per-device work, and
  for closures independent per-device fixpoints) — with ``backend``
  selecting each shard's local contraction path and the per-request
  ``k_valid``/``valid_n`` ragged masks carried through.
  """
  sharded = schedule != "local"
  if sharded:
    if mesh is None:
      raise ValueError(f"schedule {schedule!r} needs a mesh")
    from repro.core import distributed as dist

    def contract(a, b, c, op, kv):
      return dist.mmo_sharded_batched(a, b, c, op=op, schedule=schedule,
                                      mesh=mesh, backend=backend, block=block,
                                      interpret=interpret, k_valid=kv)
  else:

    def contract(a, b, c, op, kv):
      return mmo_batched(a, b, c, op=op, backend=backend, block=block,
                         interpret=interpret, k_valid=kv)

  if key.kind == "mmo":
    (has_c,) = key.params

    def fn(*args):
      a, b = args[0], args[1]
      c = args[2] if has_c else None
      kv = args[2 + has_c]
      return contract(a, b, c, key.op, kv)

    return fn

  if key.kind == "closure":
    (algorithm,) = key.params

    if sharded:
      # whole-solver entry point: for dp each device runs an *independent*
      # fixpoint over its own requests (straggler decoupling); for the
      # contraction schedules it swaps the squaring step for the mesh one.
      # The fused megakernel is a single-device program — a megakernel
      # decision on a mesh-routed bucket degrades to the xla shard-local
      # contraction rather than failing the batch.
      local_bk = "xla" if backend == "megakernel" else backend

      def fn(adj, valid):
        return dist.sharded_closure_batched(adj, op=key.op,
                                            algorithm=algorithm, mesh=mesh,
                                            schedule=schedule,
                                            backend=local_bk, block=block,
                                            interpret=interpret,
                                            valid_n=valid)

      return fn

    solver = (cl_mod.batched_leyzorek_closure if algorithm == "leyzorek"
              else cl_mod.batched_bellman_ford_closure)

    if backend == "megakernel":
      # fused fixpoint: the whole G-iteration chunk runs on-chip; the
      # dispatch cfg is the chunk length G (cost_table DEFAULT_CONFIGS)
      g = int(block[0]) if block else 8

      def fn(adj, valid):
        return solver(adj, op=key.op, fixpoint_backend="megakernel",
                      megakernel_g=g, interpret=interpret, valid_n=valid)

      return fn

    def mmo_fn(a, b, c, op, bk, k_valid=None):
      from repro.core.mmo import mmo as _mmo
      return _mmo(a, b, c, op=op, backend=bk, block=block,
                  interpret=interpret, k_valid=k_valid)

    def fn(adj, valid):
      return solver(adj, op=key.op, backend=backend, mmo_fn=mmo_fn,
                    valid_n=valid)

    return fn

  if key.kind == "knn":
    (k,) = key.params

    def fn(q, ref, valid):
      d2 = contract(q, jnp.swapaxes(ref, -1, -2), None, "addnorm",
                    None)  # feature dim is never padded raggedly
      # mask padded corpus rows to +inf so they lose every top-k comparison
      row_ok = jnp.arange(d2.shape[-1]) < valid[:, None]  # (R, rb)
      # repro: ignore[semiring-hardcoded-identity] — top-k mask, not a pad
      d2 = jnp.where(row_ok[:, None, :], d2, jnp.inf)
      neg, idx = jax.lax.top_k(-d2, k)
      return -neg, idx

    return fn

  raise ValueError(f"unknown kind {key.kind!r}")


def _primary_output(key: BucketKey, out):
  """The batch output array callers consume as the result value (mmo: the
  contraction itself; closure: the closed matrix; knn: the distances)."""
  return out[0] if isinstance(out, (tuple, list)) else out


def validate_finite(key: BucketKey, out, live: int):
  """NaN scan over the primary output's first ``live`` slots; returns the
  offending request-slot indices (empty = clean).

  Only NaN counts as garbage.  ±inf is a *legitimate* value in tropical
  semirings — APSP spells "unreachable" as +inf — so this is ``isnan``,
  never ``isfinite``.  Boolean/integer outputs cannot carry NaN and always
  validate clean."""
  arr = np.asarray(_primary_output(key, out))
  if not np.issubdtype(arr.dtype, np.floating) or live < 1:
    return []
  # fast path first: one NaN-propagating reduction (min carries NaN through)
  # decides clean batches — this runs on EVERY batch, so it must cost one
  # pass and no temporaries; per-slot attribution only runs on the rare hit
  if not np.isnan(np.min(arr[:live])):
    return []
  bad = np.isnan(arr[:live]).any(axis=tuple(range(1, arr.ndim)))
  return [int(i) for i in np.nonzero(bad)[0]]


def poison_output(key: BucketKey, out, slots: Sequence[int]):
  """Overwrite the primary output's ``slots`` with NaN — the fault
  injector's ``nonfinite`` point (faults.py): the engine's result
  validation must catch exactly this.  Returns a rebuilt output structure;
  non-float primaries (boolean semirings) pass through unpoisoned."""
  primary = np.asarray(_primary_output(key, out))
  if not np.issubdtype(primary.dtype, np.floating) or not len(slots):
    return out
  primary = primary.copy()
  primary[list(slots)] = np.nan
  if isinstance(out, (tuple, list)):
    return (primary,) + tuple(out[1:])
  return primary


def split_results(key: BucketKey, reqs: Sequence[ProblemRequest], out):
  """Batched program output → per-request MMOResults at true shapes."""
  results = []
  if key.kind == "mmo":
    d = np.asarray(out)
    for i, r in enumerate(reqs):
      m, _, n = r.shape
      results.append(MMOResult(value=d[i, :m, :n]))
  elif key.kind == "closure":
    closed, iters = (np.asarray(out[0]), np.asarray(out[1]))
    for i, r in enumerate(reqs):
      (n,) = r.shape
      results.append(MMOResult(value=closed[i, :n, :n],
                               extras={"iterations": int(iters[i])}))
  elif key.kind == "knn":
    d2, idx = np.asarray(out[0]), np.asarray(out[1])
    for i, r in enumerate(reqs):
      q = r.shape[0]
      results.append(MMOResult(value=d2[i, :q], extras={"indices": idx[i, :q]}))
  else:
    raise ValueError(f"unknown kind {key.kind!r}")
  return results
