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
                       the first convergence check.  Each operand is
                       allocated once and each slot written once, pads in
                       place; a closure batch of one request that already
                       has its bucket's shape and dtype, with no inert
                       slot, is staged with no host write at all
                       (``zero_copy``): its operand is a view of the
                       request's own buffer.
  ``make_batch_fn``  — the pure jax function the executable cache compiles:
                       mmo_batched / batched_*_closure (per-request
                       convergence masks) / addnorm+top-k.
  ``split_results``  — slice the padded batch output back to each request's
                       true shape.

Contract: a staged batch may alias the caller's arrays, so the batch program
never donates or writes its inputs, and nothing downstream of staging writes
a request's arrays (``poison_output`` works on a copy).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import closure as cl_mod
from repro.core import semiring as sr_mod
from repro.core.mmo import mmo_batched
from repro.serve_mmo.api import MMOResult, ProblemRequest
from repro.serve_mmo.scheduler import BucketKey


def _stack_padded(xs: Sequence[np.ndarray], shape: tuple, dtype: str, pad,
                  inert: int) -> np.ndarray:
  """``xs`` padded with ``pad`` to ``shape`` and stacked, then ``inert``
  slots of ``pad``: one allocation, each slot written once."""
  out = np.empty((len(xs) + inert,) + tuple(shape), np.dtype(dtype))
  for slot, x in zip(out, xs):
    r, c = x.shape
    slot[:r, :c] = x
    slot[:r, c:] = pad
    slot[r:] = pad
  out[len(xs):] = pad
  return out


def _stack_mmo(key: BucketKey, reqs: Sequence[ProblemRequest], inert: int):
  mb, kb, nb = key.shape
  pa, pb = sr_mod.contraction_pads(key.op)
  boolean = sr_mod.get(key.op).boolean
  if boolean:
    pa = pb = False
  (has_c,) = key.params
  a = _stack_padded([r.arrays["a"] for r in reqs], (mb, kb), key.dtypes[0],
                    pa, inert)
  b = _stack_padded([r.arrays["b"] for r in reqs], (kb, nb), key.dtypes[1],
                    pb, inert)
  # per-request live-K: lanes beyond a request's true K are contraction pads
  # (⊗(pa, pb) == ⊕-identity), so backends may skip them (ragged masked-K)
  kv = np.asarray([r.shape[1] for r in reqs] + [0] * inert, np.int32)
  if not has_c:
    return (a, b, kv)
  ident = False if boolean else sr_mod.get(key.op).oplus_identity
  c = _stack_padded([r.arrays["c"] for r in reqs], (mb, nb), key.dtypes[2],
                    ident, inert)
  return (a, b, c, kv)


def zero_copy(key: BucketKey, reqs: Sequence[ProblemRequest],
              inert: int) -> bool:
  """Whether ``stack_batch`` stages this batch with no host write: one
  closure request, no inert slot, its matrix already of the bucket's shape
  and dtype, so the stacked operand is a view of the request's buffer."""
  if key.kind != "closure" or inert or len(reqs) != 1:
    return False
  adj = reqs[0].arrays["adj"]
  (nb,) = key.shape
  return adj.shape == (nb, nb) and adj.dtype == np.dtype(key.dtypes[0])


def _stack_closure(key: BucketKey, reqs: Sequence[ProblemRequest],
                   inert: int):
  (nb,) = key.shape
  # true problem sizes: rows/cols beyond valid[r] are isolated-vertex padding
  valid = np.asarray([r.shape[0] for r in reqs] + [0] * inert, np.int32)
  if zero_copy(key, reqs, inert):
    return (reqs[0].arrays["adj"][None], valid)
  missing, self_value = cl_mod.closure_pad_values(key.op)
  adj = _stack_padded([r.arrays["adj"] for r in reqs], (nb, nb),
                      key.dtypes[0], missing, inert)
  for slot, n in zip(adj, valid):
    pad = np.arange(n, nb)
    slot[pad, pad] = self_value
  return (adj, valid)


def _stack_knn(key: BucketKey, reqs: Sequence[ProblemRequest], inert: int):
  qb, rb, db = key.shape
  # all pads are zeros (query pad rows' outputs are sliced away; padded dims
  # contribute (0-0)²=0 for real rows); ``valid`` carries each request's true
  # corpus size so the compiled program can mask padded rows out of top-k.
  q = _stack_padded([r.arrays["queries"] for r in reqs], (qb, db),
                    key.dtypes[0], 0.0, inert)
  ref = _stack_padded([r.arrays["corpus"] for r in reqs], (rb, db),
                      key.dtypes[1], 0.0, inert)
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
