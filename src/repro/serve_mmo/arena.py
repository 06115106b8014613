"""Device-resident request arena: slot-based continuous batching for closures.

The batch path holds every closure request hostage to its bucket's full
fixpoint cycle: requests are pad-and-stacked host-side, the whole batch runs
to convergence, and an arrival during the cycle waits for the next one.  The
arena removes the cycle.  It preallocates a fixed-capacity slot buffer ON
DEVICE — a (capacity, np_, np_) iterate plus per-slot ``k_valid`` /
``active`` / ``iteration`` vectors — and serves requests by slot lifecycle:

  admit  — one ``jax.lax.dynamic_update_slice`` writes the padded adjacency
           into a free slot (no host restack of the other residents),
  tick   — ONE fused chunk launch (``kernels.closure_megakernel.
           fixpoint_chunk``) advances every live slot by up to ``g``
           iterations in place; frozen/empty slots cost one scalar test in
           the kernel's scalar-prefetched gating,
  evict  — between chunks, converged slots (active flag 0) or capped slots
           are read out, freed, and backfilled by the next admissions.

This is the ``SequenceBuffer`` continuous-batching idiom from LLM inference
runners applied to semiring fixpoints, and the same TCU-model argument the
megakernel made (operands stay resident; HBM traffic amortizes across
steps) stretched from one batch's G iterations to the engine's lifetime.

Bit-parity with the batched path is BY CONSTRUCTION, not luck:

  * layout — both paths derive padding, accumulator dtype, and slab height
    from one resolver (``chunk_geometry``), called at the BUCKET dim ``nb``
    (not the request's true n): a request admitted into the arena lands in
    a byte-identical layout to the same request stacked into a batch;
  * iteration budget — each slot carries its own remaining-trips budget
    ``clip(max_iters - it, 0, g)``, with ``max_iters`` the same
    ``fixpoint_iters(algorithm, nb)`` default the batched solver computes
    from its stack dim, so counters and caps agree exactly;
  * independence — the fused kernel never mixes data across the request
    dim, so per-slot trajectories are independent of WHEN neighboring slots
    are admitted or evicted.  Eviction happens strictly between chunk
    launches and only rewrites freed slots' host bookkeeping; live slots'
    device state is untouched.

Zero steady-state retraces: the three programs (admit / tick / read) are
AOT-compiled once per arena through the shared ``ExecutableCache`` with the
slot index and true size as *traced* int32 scalars — every admission and
eviction replays the same stored executables, countable via the cache's
miss counter (asserted in tests/test_arena.py and benchmarks/arena_bench.py).

Thread-safety: all host bookkeeping (slot table, free list, counters) and
the device-state swaps happen under the arena's own lock.  The engine's
lock order is engine → arena; the arena never calls back into the engine.
"""
from __future__ import annotations

import threading
import time
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import closure as cl_mod
from repro.kernels.closure_megakernel import (chunk_geometry, fixpoint_chunk,
                                              fixpoint_iters)
from repro.serve_mmo.api import ProblemRequest
from repro.serve_mmo.cache import ExecutableCache
from repro.serve_mmo.metrics import bucket_label
from repro.serve_mmo.scheduler import BucketKey

__all__ = ["DEFAULT_CAPACITY", "DEFAULT_ARENA_G", "Eviction", "RequestArena"]

DEFAULT_CAPACITY = 8
DEFAULT_ARENA_G = 4


class Eviction(NamedTuple):
  """One request leaving its slot: the engine turns this into a result."""
  request: ProblemRequest
  slot: int
  value: np.ndarray   # true-shape (n, n) closure, bool rings decoded
  iterations: int     # measured fixpoint trip count (parity-pinned)
  admit_s: float      # when the request entered its slot (engine clock)


class RequestArena:
  """Fixed-capacity device slot buffer for ONE closure bucket.

  Every request admitted here shares the bucket's (op, algorithm, nb,
  dtype) signature; the engine keeps one arena per closure ``BucketKey``.
  ``capacity`` bounds resident requests, ``g`` is the fused chunk length
  per tick, ``max_iters`` defaults to the batched solver's own trip cap at
  the bucket dim (MUST stay nb-derived for cross-path parity).
  """

  def __init__(self, key: BucketKey, *, capacity: int = DEFAULT_CAPACITY,
               g: int = DEFAULT_ARENA_G, cache: Optional[ExecutableCache] = None,
               max_iters: Optional[int] = None,
               interpret: Optional[bool] = None, clock=None):
    if key.kind != "closure":
      raise ValueError(f"arena serves closure buckets only, got {key.kind!r}")
    if capacity < 1:
      raise ValueError(f"capacity must be >= 1, got {capacity}")
    if g < 1:
      raise ValueError(f"g must be >= 1, got {g}")
    self.key = key
    (self.nb,) = key.shape
    self.op = key.op
    (self.algorithm,) = key.params
    self.capacity = int(capacity)
    self.g = int(g)
    self.cache = cache if cache is not None else ExecutableCache()
    self._clock = clock if clock is not None else time.perf_counter
    # the bucket dim, not any request's true n: the batched reference
    # computes its default trip cap from the padded stack dim, so the arena
    # must too or capped counters diverge between the paths
    self.max_iters = (fixpoint_iters(self.algorithm, self.nb)
                      if max_iters is None else int(max_iters))
    self.geom = chunk_geometry(key.op, self.nb, key.dtypes[0],
                               interpret=interpret)
    # Bellman-Ford relaxes against the original adjacency (D ← D ⊕ D⊗A);
    # Leyzorek squares the iterate against itself and needs no second buffer
    self._has_adj = self.algorithm == "bellman_ford"

    C, np_ = self.capacity, self.geom.np_
    acc = np.dtype(self.geom.acc_dtype)
    base = np.full((np_, np_), self.geom.missing, acc)
    np.fill_diagonal(base, self.geom.self_value)
    init = jnp.asarray(np.repeat(base[None], C, axis=0))
    # device slot state — swapped wholesale under _lock by admit/tick
    self._c = init                              # (C, np_, np_) iterate
    self._adj = init if self._has_adj else None
    self._kv = jnp.zeros((C,), jnp.int32)       # per-slot true n (masked K)
    self._act = jnp.zeros((C,), jnp.int32)      # 1 = still iterating
    self._it = jnp.zeros((C,), jnp.int32)       # measured iteration counter

    # host bookkeeping — GUARDED_BY _lock (see analysis/lock_rules.py)
    self._lock = threading.RLock()
    self._slots: List[Optional[ProblemRequest]] = [None] * C
    self._admit_s: List[float] = [0.0] * C
    self._free: List[int] = list(range(C - 1, -1, -1))  # pop() → slot 0 first
    self._admitted = 0
    self._evicted = 0
    self._ticks = 0
    # clock reading when the last sweep's flags reached the host: where the
    # host stopped waiting on the tick and began reading evicted slots out
    self.flags_s: Optional[float] = None
    self._program_specs = self._build_program_specs()

  # -- AOT programs ----------------------------------------------------------

  def _build_program_specs(self) -> dict:
    """name → (make_fn, abstract args) for the three arena programs.  The
    slot index and true size are traced scalars, so one compiled executable
    serves every slot and every request size in the bucket — admissions and
    evictions never retrace."""
    C, np_ = self.capacity, self.geom.np_
    acc, i32 = self.geom.acc_dtype, jnp.int32
    has_adj = self._has_adj
    op, g, bm = self.op, self.g, self.geom.bm
    max_iters, interpret = self.max_iters, self.geom.interpret
    mat3 = jax.ShapeDtypeStruct((C, np_, np_), acc)
    vec = jax.ShapeDtypeStruct((C,), i32)
    mat2 = jax.ShapeDtypeStruct((np_, np_), acc)
    scal = jax.ShapeDtypeStruct((), i32)

    def make_admit():
      def admit(*args):
        if has_adj:
          c, adj, kv, act, it, mat, slot, n = args
        else:
          c, kv, act, it, mat, slot, n = args
          adj = None
        c = jax.lax.dynamic_update_slice(c, mat[None], (slot, 0, 0))
        if adj is not None:
          adj = jax.lax.dynamic_update_slice(adj, mat[None], (slot, 0, 0))
        kv = jax.lax.dynamic_update_slice(kv, jnp.reshape(n, (1,)), (slot,))
        act = jax.lax.dynamic_update_slice(act, jnp.ones((1,), i32), (slot,))
        it = jax.lax.dynamic_update_slice(it, jnp.zeros((1,), i32), (slot,))
        return (c, adj, kv, act, it) if adj is not None else (c, kv, act, it)
      return admit

    def make_tick():
      def tick(*args):
        if has_adj:
          c, adj, kv, act, it = args
        else:
          c, kv, act, it = args
          adj = None
        # per-slot remaining-trips budget: a slot admitted mid-stream gets
        # exactly the iterations the batched path would have given it
        glim = jnp.clip(max_iters - it, 0, g).astype(i32)
        return fixpoint_chunk(c, adj, kv, act, it, glim, op=op, g_steps=g,
                              bm=bm, interpret=interpret)
      return tick

    def make_read():
      def read(c, slot):
        return jax.lax.dynamic_slice(c, (slot, 0, 0), (1, np_, np_))[0]
      return read

    state = (mat3, mat3) if has_adj else (mat3,)
    return {
        "admit": (make_admit, state + (vec, vec, vec, mat2, scal, scal)),
        "tick": (make_tick, state + (vec, vec, vec)),
        "read": (make_read, (mat3, scal)),
    }

  def _compiled(self, name: str):
    make_fn, abstract = self._program_specs[name]
    return self.cache.get_or_compile(
        ("arena", self.key, name, self.capacity, self.g, self.max_iters),
        make_fn, abstract,
        label=f"arena/{bucket_label(self.key)}/{name}/c{self.capacity}")

  def prewarm(self) -> None:
    """Compile all three programs; after this, arena traffic never retraces
    (the zero-recompile guarantee tests and benches assert via the shared
    cache's miss counter)."""
    for name in self._program_specs:
      self._compiled(name)

  # -- slot lifecycle --------------------------------------------------------

  def free_slots(self) -> int:
    with self._lock:
      return len(self._free)

  def live_slots(self) -> int:
    with self._lock:
      return self.capacity - len(self._free)

  def live_requests(self) -> list:
    with self._lock:
      return [r for r in self._slots if r is not None]

  def admit(self, req: ProblemRequest, *, now: Optional[float] = None) -> int:
    """Write one request into a free slot; returns the slot index.  The
    padded adjacency is built host-side (one small H2D), then a single
    dynamic_update_slice lands it — neighboring residents never restack."""
    n = int(req.shape[0])
    if n > self.nb:
      raise ValueError(f"request n={n} exceeds arena bucket nb={self.nb}")
    mat = np.asarray(cl_mod.pad_adjacency(req.arrays["adj"], self.geom.np_,
                                          op=self.op))
    if self.geom.was_bool:
      mat = mat.astype(np.float32)
    mat = np.asarray(mat, dtype=np.dtype(self.geom.acc_dtype))
    with self._lock:
      if not self._free:
        raise RuntimeError(
            f"arena full: {self.capacity} slots live — the engine must "
            f"bound admissions by free_slots()")
      slot = self._free.pop()
      fn = self._compiled("admit")
      if self._has_adj:
        self._c, self._adj, self._kv, self._act, self._it = fn(
            self._c, self._adj, self._kv, self._act, self._it,
            mat, np.int32(slot), np.int32(n))
      else:
        self._c, self._kv, self._act, self._it = fn(
            self._c, self._kv, self._act, self._it,
            mat, np.int32(slot), np.int32(n))
      self._slots[slot] = req
      self._admit_s[slot] = self._clock() if now is None else now
      self._admitted += 1
      return slot

  def tick(self) -> bool:
    """One fused chunk over the whole slot buffer (≤ g iterations per live
    slot, in place).  Returns False without launching when nothing is live.
    Dispatch is async — ``sweep`` is the synchronization point."""
    with self._lock:
      if len(self._free) == self.capacity:
        return False
      fn = self._compiled("tick")
      if self._has_adj:
        self._c, self._it, self._act = fn(self._c, self._adj, self._kv,
                                          self._act, self._it)
      else:
        self._c, self._it, self._act = fn(self._c, self._kv, self._act,
                                          self._it)
      self._ticks += 1
      return True

  def sweep(self) -> List[Eviction]:
    """Evict every occupied slot that converged (active flag 0) or hit the
    trip cap: read its closure out, free the slot for backfill.  Runs
    strictly between chunk launches, so live slots' device state is never
    touched — the bit-parity invariant.  Freed slots need no device write:
    their stale flags are inert (the next tick's budget clips to 0 compute)
    until an admission reseeds them."""
    with self._lock:
      act = np.asarray(self._act)  # blocks on the tick — the one sync point
      it = np.asarray(self._it)
      self.flags_s = self._clock()
      read = self._compiled("read")
      evictions = []
      for slot, req in enumerate(self._slots):
        if req is None:
          continue
        if act[slot] != 0 and it[slot] < self.max_iters:
          continue
        n = int(req.shape[0])
        value = np.asarray(read(self._c, np.int32(slot)))[:n, :n]
        if self.geom.was_bool:
          value = value > 0.5
        evictions.append(Eviction(request=req, slot=slot, value=value,
                                  iterations=int(it[slot]),
                                  admit_s=self._admit_s[slot]))
        self._slots[slot] = None
        self._free.append(slot)
        self._evicted += 1
      return evictions

  def reset(self) -> list:
    """Abandon all resident requests (tick-failure recovery): zero the
    per-slot flags, free every slot, and return the forfeited requests for
    the engine to fail.  The iterate buffer itself needs no wipe — admission
    overwrites a slot's matrix wholesale."""
    with self._lock:
      live = [r for r in self._slots if r is not None]
      self._slots = [None] * self.capacity
      self._admit_s = [0.0] * self.capacity
      self._free = list(range(self.capacity - 1, -1, -1))
      self._kv = jnp.zeros_like(self._kv)
      self._act = jnp.zeros_like(self._act)
      self._it = jnp.zeros_like(self._it)
      return live

  def stats(self) -> dict:
    with self._lock:
      live = self.capacity - len(self._free)
      return {"capacity": self.capacity, "live": live,
              "free": len(self._free), "admitted": self._admitted,
              "evicted": self._evicted, "ticks": self._ticks,
              "g": self.g, "max_iters": self.max_iters}
