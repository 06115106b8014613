"""Autotuner: microbenchmark op × shape-bucket × dtype × backend × blocks.

    PYTHONPATH=src python -m repro.tuning.autotune --out cost_table.json
    PYTHONPATH=src python -m repro.tuning.autotune --dry-prior --out t.json

    # mesh rows too: measure dp/kspan/SUMMA/ring on a (2, 4) device mesh so
    # backend="auto" sharded serving dispatches from measurements
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.tuning.autotune --mesh 2,4 --out cost_table.json

Every point is first seeded with the analytic roofline prior, then (unless
``--dry-prior``) measured on the live device with best-of wall timing; the
table's measured-beats-prior precedence means re-running the tuner only ever
sharpens the table.  ``--dry-prior`` exists for CI: it exercises the whole
sweep → record → serialize path with zero device timing, so schema rot is
caught without needing quiet hardware.

``--mesh ROWS,COLS`` extends the sweep to the distributed-schedule arms:
each (op, shape, dtype) point is also measured as one batched sharded
contraction per schedule (same per-request single-step units as
``benchmarks/shard_bench.py --cost-table`` records, so rows from either
source are interchangeable), which is what ``dispatch.resolve(mesh_shape=…)``
compares against the local arm when routing serving buckets to the mesh.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Optional, Sequence

import numpy as np

from repro.core import semiring as sr_mod
from repro.tuning.cost_table import (SCHEDULE_ARMS, CostTable,
                                     DEFAULT_CONFIGS, bucket_shape,
                                     prior_seconds, sharded_prior_seconds)

DEFAULT_OPS = ("mma", "minplus", "maxmin", "maxmul", "orand", "addnorm")
DEFAULT_SHAPES = ((64, 64, 64), (128, 128, 128), (64, 256, 64))
DEFAULT_BACKENDS = ("xla", "vector", "pallas", "megakernel")


def _megakernel_point_ok(op: str, shape) -> bool:
  """The fused-fixpoint arm only exists for closure-shaped points: square
  contractions on rings with a ⊗-identity (closure is refused elsewhere),
  up to the kernel's size cap."""
  from repro.kernels import closure_megakernel as mk
  m, k, n = bucket_shape(shape)
  return (m == k == n and m <= mk.MAX_N
          and sr_mod.get(op).otimes_identity is not None)


def _device_label() -> str:
  import jax
  try:
    dev = jax.devices()[0]
    return f"{jax.default_backend()}:{getattr(dev, 'device_kind', dev)}"
  except Exception:  # noqa: BLE001 — label only, never fail the tuner
    return "unknown"


def _operands(op: str, shape, dtype, seed: int = 0):
  """Random operands at the bucket shape (bool for boolean rings)."""
  m, k, n = bucket_shape(shape)
  rng = np.random.default_rng(seed)
  if sr_mod.get(op).boolean:
    return (rng.random((m, k)) > 0.5), (rng.random((k, n)) > 0.5)
  a = rng.standard_normal((m, k)).astype(dtype)
  b = rng.standard_normal((k, n)).astype(dtype)
  if op in ("minmul", "maxmul"):  # reliability rings want [0, 1] weights
    a, b = np.abs(np.tanh(a)).astype(dtype), np.abs(np.tanh(b)).astype(dtype)
  return a, b


def measure_point(op: str, shape, dtype, backend: str, cfg: tuple, *,
                  iters: int = 3, warmup: int = 1) -> float:
  """Best-of wall seconds for one table point on the live device."""
  import jax
  import jax.numpy as jnp
  from repro.core.mmo import mmo

  a_h, b_h = _operands(op, shape, dtype)
  a, b = jnp.asarray(a_h), jnp.asarray(b_h)
  def run():
    return mmo(a, b, op=op, backend=backend, block=cfg)
  for _ in range(warmup):
    jax.block_until_ready(run())
  best = float("inf")
  for _ in range(iters):
    t0 = time.perf_counter()
    jax.block_until_ready(run())
    best = min(best, time.perf_counter() - t0)
  return best


def measure_megakernel_point(op: str, shape, dtype, cfg: tuple, *,
                             iters: int = 3, warmup: int = 1) -> float:
  """Best-of wall seconds *per fused iteration* for one megakernel row.

  The table prices every backend in per-contraction units, so the fused
  arm is timed as one G-iteration chunk and divided by G.  The operand is
  a directed line graph — the slowest-converging closure input — with
  ``max_iters=G`` so the kernel runs exactly its chunk and never exits
  early: what we record is the steady-state fused iteration cost, not a
  lucky early convergence."""
  import jax
  import jax.numpy as jnp
  from repro.core.closure import batched_bellman_ford_closure

  m, k, n = bucket_shape(shape)
  assert m == k == n, "megakernel rows are square closure points"
  g = int(cfg[0]) if cfg else 8
  sr = sr_mod.get(op)
  rng = np.random.default_rng(0)
  if sr.boolean:
    adj_h = np.zeros((n, n), dtype=bool)
    adj_h[np.arange(n - 1), np.arange(1, n)] = True
  else:
    adj_h = np.full((n, n), sr.oplus_identity, dtype=dtype)
    np.fill_diagonal(adj_h, sr.otimes_identity)
    adj_h[np.arange(n - 1), np.arange(1, n)] = np.abs(
        np.tanh(rng.standard_normal(n - 1))).astype(dtype)
  adj = jnp.asarray(adj_h)[None]
  def run():
    out, _ = batched_bellman_ford_closure(
        adj, op=op, fixpoint_backend="megakernel", megakernel_g=g,
        max_iters=g)
    return out
  for _ in range(warmup):
    jax.block_until_ready(run())
  best = float("inf")
  for _ in range(iters):
    t0 = time.perf_counter()
    jax.block_until_ready(run())
    best = min(best, time.perf_counter() - t0)
  return best / g


def default_backends() -> tuple:
  """Measurement-worthy backends for this host: Pallas (and the megakernel,
  which is Pallas underneath) is only a serving option on TPU — on CPU it
  runs in interpret mode, orders of magnitude slower, and measuring it
  would stall warmup for no dispatchable gain.  (``--dry-prior`` sweeps
  still cover both: priors cost nothing.)"""
  import jax
  return ("xla", "vector") + (
      ("pallas", "megakernel") if jax.default_backend() == "tpu" else ())


def tune(*,
         ops: Sequence[str] = DEFAULT_OPS,
         shapes: Sequence[tuple] = DEFAULT_SHAPES,
         dtypes: Sequence[str] = ("float32",),
         backends: Optional[Sequence[str]] = None,
         configs: Optional[dict] = None,
         table: Optional[CostTable] = None,
         iters: int = 3,
         warmup: int = 1,
         dry_prior: bool = False,
         fill_prior: bool = True,
         verbose: bool = False) -> CostTable:
  """Sweep the grid, recording priors for every point and measurements for
  all of them unless ``dry_prior``.  Updates and returns ``table``."""
  if backends is None:
    # dry-prior sweeps cost nothing — cover every backend for schema
    # coverage; live measurement sticks to what this host can serve with
    backends = DEFAULT_BACKENDS if dry_prior else default_backends()
  configs = configs or DEFAULT_CONFIGS
  if table is None:
    table = CostTable(device="prior-only" if dry_prior else _device_label())
  for op in ops:
    boolean = sr_mod.get(op).boolean
    op_dtypes = ("bool",) if boolean else dtypes
    for shape in shapes:
      for dtype in op_dtypes:
        for backend in backends:
          if backend == "megakernel" and not _megakernel_point_ok(op, shape):
            continue  # closure undefined here: no row, prior or measured
          for cfg in configs.get(backend, ((),)):
            if fill_prior:
              table.record(op, shape, dtype, backend, cfg,
                           prior_seconds(op, shape, dtype, backend, cfg),
                           source="prior")
            if dry_prior:
              continue
            if backend == "megakernel":
              seconds = measure_megakernel_point(op, shape, dtype, cfg,
                                                 iters=iters, warmup=warmup)
            else:
              seconds = measure_point(op, shape, dtype, backend, cfg,
                                      iters=iters, warmup=warmup)
            table.record(op, shape, dtype, backend, cfg, seconds,
                         source="measured")
            if verbose:
              print(f"[autotune] {op} {shape} {dtype} {backend} {cfg}: "
                    f"{seconds * 1e6:.1f}us", file=sys.stderr)
  return table


def measure_sharded_point(op: str, shape, dtype, schedule: str, mesh, *,
                          requests: Optional[int] = None, iters: int = 3,
                          warmup: int = 1) -> float:
  """Best-of wall seconds *per request* for one distributed-schedule arm:
  one batched sharded contraction over ``requests`` (default: one per
  device, the smallest batch every schedule can shard).  Per-request
  single-step units match ``benchmarks/shard_bench.py``'s ``step_seconds``
  and the table's one-(m, k, n)-contraction signature."""
  import jax
  import jax.numpy as jnp
  from repro.core.distributed import mmo_sharded_batched

  r = requests if requests is not None else mesh.size
  m, k, n = bucket_shape(shape)
  ops = []
  for i in range(r):
    a_h, b_h = _operands(op, shape, dtype, seed=i)
    ops.append((a_h, b_h))
  a = jnp.asarray(np.stack([o[0] for o in ops]))
  b = jnp.asarray(np.stack([o[1] for o in ops]))
  fn = jax.jit(lambda x, y: mmo_sharded_batched(
      x, y, op=op, schedule=schedule, mesh=mesh, backend="xla"))
  for _ in range(warmup):
    jax.block_until_ready(fn(a, b))
  best = float("inf")
  for _ in range(iters):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(a, b))
    best = min(best, time.perf_counter() - t0)
  return best / r


def tune_mesh(*,
              dims: Sequence[int],
              mesh=None,
              ops: Sequence[str] = DEFAULT_OPS,
              shapes: Sequence[tuple] = DEFAULT_SHAPES,
              dtypes: Sequence[str] = ("float32",),
              schedules: Sequence[str] = SCHEDULE_ARMS,
              table: Optional[CostTable] = None,
              iters: int = 3,
              warmup: int = 1,
              dry_prior: bool = False,
              verbose: bool = False) -> CostTable:
  """Sweep the distributed-schedule arms on a (rows, cols) mesh, recording
  the sharded roofline prior for every point and measurements unless
  ``dry_prior`` (which needs no mesh at all — CI schema coverage).  Points a
  schedule cannot shard (``core.distributed.schedule_fits``) are skipped.
  Updates and returns ``table``."""
  dims = tuple(int(d) for d in dims)
  if table is None:
    table = CostTable(device="prior-only" if dry_prior else _device_label())
  if not dry_prior:
    from repro.core.distributed import make_mesh, schedule_fits
    if mesh is None:
      mesh = make_mesh(dims)
  for op in ops:
    op_dtypes = ("bool",) if sr_mod.get(op).boolean else dtypes
    for shape in shapes:
      m, k, n = bucket_shape(shape)
      for dtype in op_dtypes:
        for sched in schedules:
          if sched not in SCHEDULE_ARMS:
            raise ValueError(f"unknown schedule {sched!r}; one of "
                             f"{SCHEDULE_ARMS}")
          table.record(op, shape, dtype, sched, dims,
                       sharded_prior_seconds(op, (m, k, n), dtype, sched,
                                             dims),
                       source="prior")
          if dry_prior:
            continue
          if not schedule_fits(sched, m, k, n, mesh):
            continue
          seconds = measure_sharded_point(op, shape, dtype, sched, mesh,
                                          iters=iters, warmup=warmup)
          table.record(op, shape, dtype, sched, dims, seconds,
                       source="measured")
          if verbose:
            print(f"[autotune] {op} {shape} {dtype} {sched}@{dims}: "
                  f"{seconds * 1e6:.1f}us", file=sys.stderr)
  return table


def tune_for_requests(reqs, **kw) -> CostTable:
  """Tune exactly the (op, contraction-shape, dtype) points a sample of
  serving requests exercises — the engine-warmup entry point."""
  from repro.serve_mmo.scheduler import contract_shape, request_bucket
  points = {}
  for req in reqs:
    key = request_bucket(req)
    points.setdefault((key.op, contract_shape(key), key.dtypes[0]), None)
  table = kw.pop("table", None)
  if table is None:  # NB not `or`: an empty CostTable is falsy but valid
    table = CostTable(device=_device_label())
  for (op, shape, dtype) in points:
    table = tune(ops=(op,), shapes=(shape,), dtypes=(dtype,), table=table,
                 **kw)
  return table


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--out", default="cost_table.json",
                  help="JSON path to write the table to")
  ap.add_argument("--update", action="store_true",
                  help="load --out first and update it in place")
  ap.add_argument("--dry-prior", action="store_true",
                  help="analytic prior only — no device timing (CI mode)")
  ap.add_argument("--ops", default=",".join(DEFAULT_OPS))
  ap.add_argument("--shapes",
                  default=",".join("x".join(map(str, s))
                                   for s in DEFAULT_SHAPES),
                  help="comma-separated MxKxN triples, e.g. 64x64x64,128x128x128")
  ap.add_argument("--dtypes", default="float32")
  ap.add_argument("--backends", default=None,
                  help="comma-separated; default: every backend for "
                       "--dry-prior, else what this host can serve with")
  ap.add_argument("--iters", type=int, default=3)
  ap.add_argument("--warmup", type=int, default=1)
  ap.add_argument("--mesh", default=None, metavar="ROWS,COLS",
                  help="also sweep the distributed-schedule arms "
                       f"({','.join(SCHEDULE_ARMS)}) on a device mesh of "
                       "this shape, recording mesh rows the sharded serving "
                       "path dispatches from (dry-prior needs no devices)")
  ap.add_argument("--schedules", default=",".join(SCHEDULE_ARMS),
                  help="comma-separated schedule arms for --mesh")
  ap.add_argument("-v", "--verbose", action="store_true")
  args = ap.parse_args(argv)

  try:
    shapes = tuple(tuple(int(d) for d in s.split("x"))
                   for s in args.shapes.split(","))
    if any(len(s) != 3 for s in shapes):
      raise ValueError
  except ValueError:
    ap.error(f"--shapes must be comma-separated MxKxN triples, got "
             f"{args.shapes!r}")

  dims = None
  if args.mesh:
    try:
      dims = tuple(int(x) for x in args.mesh.split(","))
      if len(dims) != 2 or any(d <= 0 for d in dims):
        raise ValueError
    except ValueError:
      ap.error(f"--mesh must be 'rows,cols' positive ints, got {args.mesh!r}")
    if not args.dry_prior:
      import jax
      need, have = dims[0] * dims[1], len(jax.devices())
      if need > have:
        ap.error(f"--mesh {args.mesh} needs {need} devices, host has {have} "
                 f"(on CPU: XLA_FLAGS=--xla_force_host_platform_device_count="
                 f"{need})")

  table = CostTable.load(args.out) if args.update else None
  backends = tuple(args.backends.split(",")) if args.backends else None
  table = tune(ops=tuple(args.ops.split(",")), shapes=shapes,
               dtypes=tuple(args.dtypes.split(",")),
               backends=backends, table=table,
               iters=args.iters, warmup=args.warmup,
               dry_prior=args.dry_prior, verbose=args.verbose)
  if dims is not None:
    table = tune_mesh(dims=dims, ops=tuple(args.ops.split(",")),
                      shapes=shapes, dtypes=tuple(args.dtypes.split(",")),
                      schedules=tuple(args.schedules.split(",")),
                      table=table, iters=args.iters, warmup=args.warmup,
                      dry_prior=args.dry_prior, verbose=args.verbose)
  table.save(args.out)
  counts = table.counts()
  print(f"[autotune] wrote {args.out}: {len(table)} entries "
        f"({counts['measured']} measured, {counts['prior']} prior) "
        f"device={table.device}")
  return 0


if __name__ == "__main__":
  sys.exit(main())
