"""Placement: where and how one bucket's batches run.

One ``Placement`` per engine owns every per-bucket execution decision —
backend and block config, mesh schedule, padded batch size, the breaker's
fallback arms, whether the closure megakernel (and so the arena) holds a
bucket — and the one compile of a batch program (``program``), behind the
executable-cache key (``exec_key``).  Each decision is memoized at its
first resolution under this object's lock, so a bucket keeps one decision
and one cache key for the engine's lifetime.  Placement never calls back
into the engine: the lock order is engine → placement.
"""
from __future__ import annotations

import threading
from typing import Optional

from repro.kernels import closure_megakernel as _megakernel
from repro.serve_mmo import batching
from repro.serve_mmo.cache import ExecutableCache
from repro.serve_mmo.metrics import bucket_label
from repro.serve_mmo.scheduler import bucket_dim, contract_shape


class Placement:
  """Per-bucket placement for one engine, from the engine's ``backend``,
  ``cost_table``, ``mesh``, ``schedule``, ``shard_flops``, ``interpret``
  and ``fallback_backends`` options (see MMOEngine)."""

  def __init__(self, *, backend: str, cost_table, mesh, schedule: str,
               shard_flops: float, interpret: Optional[bool],
               fallback_backends, cache: ExecutableCache):
    from repro.core import distributed as dist
    valid_schedules = ("auto", "local") + dist.SCHEDULES
    if schedule not in valid_schedules:
      raise ValueError(f"unknown schedule {schedule!r}; one of "
                       f"{valid_schedules}")
    if mesh is None and schedule not in ("auto", "local"):
      raise ValueError(f"schedule {schedule!r} needs a mesh")
    self.backend = backend
    self.cost_table = cost_table
    self.mesh = mesh
    self.schedule = schedule
    self.shard_flops = float(shard_flops)
    self.interpret = interpret
    self.fallback_backends = (None if fallback_backends is None
                              else tuple(fallback_backends))
    self.cache = cache
    self._mesh_sig = None if mesh is None else tuple(
        (a, int(mesh.shape[a])) for a in mesh.axis_names)
    self._lock = threading.RLock()
    self._decisions: dict = {}  # BucketKey → (backend, block cfg)
    self._schedules: dict = {}  # BucketKey → 'local' | distributed schedule
    self._fallback_arms_memo: dict = {}  # BucketKey → tuple of arms

  @staticmethod
  def megakernel_serves(key) -> bool:
    """Whether the fused megakernel (and so the arena) can hold this bucket:
    closures whose bucket dim is within the kernel's VMEM-resident cap."""
    return key.kind == "closure" and key.shape[0] <= _megakernel.MAX_N

  def resolve_backend(self, key) -> tuple:
    """(backend, block cfg) for one bucket — the dispatch decision.

    Memoized: the first resolution a bucket ever gets is the one it keeps
    for this engine's lifetime (stable executable-cache keys).  The whole
    check-resolve-memoize sequence holds the lock: ``prewarm`` on the
    caller thread and ``step`` on the background loop race here, and an
    unsynchronized dict could memoize two divergent decisions if the global
    cost table moved between their resolutions.
    """
    with self._lock:
      dec = self._decisions.get(key)
      if dec is None:
        if self.backend == "megakernel" and not self.megakernel_serves(key):
          # above the cap the fused arm cannot compile; the per-iteration
          # Pallas path runs the same kernel family one contraction a time
          dec = ("pallas", ())
        elif self.backend != "auto":
          dec = (self.backend, ())
        else:
          from repro.tuning import dispatch as _dispatch
          m, k, n = contract_shape(key)
          # closure buckets own a whole fixpoint, so the fused 'megakernel'
          # arm competes for them (and only them: a single-contraction
          # bucket can't run it) up to its size cap.  The choice flows into
          # exec_key via the (backend, block) slots, so cached executables
          # stay distinct.
          pool = (_dispatch.closure_backends(key.shape[0])
                  if key.kind == "closure" else None)
          d = _dispatch.resolve(key.op, m, k, n, key.dtypes[0],
                                table=self.cost_table, backends=pool)
          dec = (d.backend, d.cfg)
        self._decisions[key] = dec
      return dec

  def resolve_schedule(self, key) -> str:
    """Mesh placement for one bucket: 'local' or a distributed schedule name.

    Memoized like ``resolve_backend`` (stable cache keys); without a mesh
    every bucket is 'local'.
    """
    with self._lock:
      sched = self._schedules.get(key)
      if sched is None:
        sched = self._route(key)
        self._schedules[key] = sched
      return sched

  def _route(self, key) -> str:
    """The size-threshold router: buckets whose per-request contraction
    exceeds ``shard_flops`` go to the mesh, the rest stay local.  Above the
    threshold, a pinned ``schedule`` is used as-is (when it divides onto the
    mesh); ``"auto"`` asks the cost table's mesh rows (roofline-prior
    fallback) whether a distributed schedule actually beats the local path.
    Closure buckets only consider dp (independent per-device fixpoints — the
    straggler-decoupling schedule) and SUMMA (the one contraction schedule
    whose iterate stays sharded in place across squarings)."""
    if self.mesh is None or self.schedule == "local":
      return "local"
    m, k, n = contract_shape(key)
    if 2.0 * m * k * n < self.shard_flops:
      return "local"
    from repro.core import distributed as dist
    fits = [s for s in dist.SCHEDULES
            if dist.schedule_fits(s, m, k, n, self.mesh)]
    if key.kind == "closure":
      fits = [s for s in fits if s in ("dp", "summa")]
    if self.schedule != "auto":
      return self.schedule if self.schedule in fits else "local"
    if not fits:
      return "local"
    from repro.tuning import dispatch as _dispatch
    mesh_dims = tuple(s for _, s in self._mesh_sig)
    d = _dispatch.resolve(key.op, m, k, n, key.dtypes[0],
                          table=self.cost_table, mesh_shape=mesh_dims,
                          schedules=tuple(fits))
    return d.backend if d.backend in fits else "local"

  def resolve(self, key) -> tuple:
    """(backend, block cfg, schedule) — the full per-bucket decision.  The
    backend doubles as each shard's local contraction path when the bucket
    is routed to the mesh.  A dp bucket runs every batch on the mesh: its
    batch size rounds up to a multiple of the mesh's devices
    (``padded_batch``) and the padding slots are inert."""
    with self._lock:
      backend, block = self.resolve_backend(key)
      return backend, block, self.resolve_schedule(key)

  def schedules(self) -> dict:
    """The schedules resolved so far: BucketKey → schedule."""
    with self._lock:
      return dict(self._schedules)

  def padded_batch(self, r: int, schedule: str) -> int:
    """The batch size a placement runs ``r`` requests at: the power-of-two
    bucket (the request axis is shape-bucketed like the problem axes, so a
    bucket spawns at most log2(max_batch)+1 executables), rounded up to a
    multiple of the mesh's devices under dp so the request axis always
    divides over the mesh (1–4 requests on four chips run at 4, 5–8 at 8)."""
    rb = bucket_dim(r, 1)
    if schedule == "dp":
      rb = -(-rb // self.mesh.size) * self.mesh.size
    return rb

  def chips_live(self, schedule: str, live: int, rb: int) -> int:
    """Devices holding at least one request of a sharded batch: dp gives
    each device ``rb / P`` consecutive slots, requests first; the problem-
    axis schedules split every request over every device."""
    if schedule != "dp":
      return self.mesh.size
    return -(-live // (rb // self.mesh.size))

  def fallback_arms(self, key) -> tuple:
    """Sibling arms for breaker re-dispatch, best first: every arm computes
    bit-identical results for this bucket (one substrate, many kernels —
    the SIMD² property), so traffic can move between them freely.

    Order: a sharded bucket's first fallback is its own backend on the
    local path (same kernel, no mesh collectives — survives schedule-level
    faults); then the other backends on the local path ranked by cost-table
    seconds, with the reference dense backend ('vector' — pure jnp, works
    everywhere) forced last as the terminal arm.  ``fallback_backends``
    overrides the backend order outright (deterministic tests, operator
    pinning).  Memoized per bucket: stable executable-cache keys."""
    with self._lock:
      memo = self._fallback_arms_memo.get(key)
      if memo is not None:
        return memo
      primary_backend, block, schedule = self.resolve(key)
      arms = []
      if schedule != "local":
        arms.append((primary_backend, block, "local"))
      if self.fallback_backends is not None:
        order = [b for b in self.fallback_backends if b != primary_backend]
      else:
        from repro.tuning import dispatch as _dispatch
        m, k, n = contract_shape(key)
        ranked = []
        for b in ("xla", "pallas"):
          if b == primary_backend:
            continue
          try:
            _, _, s = _dispatch.contraction_seconds(
                key.op, m, k, n, key.dtypes[0], backend=b,
                table=self.cost_table)
          except Exception:  # noqa: BLE001 — an unpriceable arm is skipped
            continue
          ranked.append((s, b))
        ranked.sort()
        order = [b for _, b in ranked]
        if primary_backend != "vector":
          order.append("vector")
      arms.extend((b, (), "local") for b in order)
      memo = tuple(arms)
      self._fallback_arms_memo[key] = memo
      return memo

  def exec_key(self, key, rb: int, backend: str, block: tuple,
               schedule: str) -> tuple:
    """Executable-cache key: placement included, so a bucket's sharded and
    local programs (or programs for two different meshes) never collide."""
    return (key, rb, backend, block, schedule,
            None if schedule == "local" else self._mesh_sig)

  def program(self, key, rb: int, backend: str, block: tuple, schedule: str,
              operands):
    """The compiled batch program for ``rb`` requests of ``key`` on one arm,
    from the executable cache (compiled on a miss).  ``operands`` fix the
    shapes and dtypes: the stacked batch, or ``batching.abstract_batch``."""
    return self.cache.get_or_compile(
        self.exec_key(key, rb, backend, block, schedule),
        lambda: batching.make_batch_fn(key, backend=backend, block=block,
                                       interpret=self.interpret,
                                       mesh=self.mesh, schedule=schedule),
        operands, label=f"{bucket_label(key)}/b{rb}/{backend}/{schedule}")
