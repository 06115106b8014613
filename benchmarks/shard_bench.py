"""Sharded-serving benchmark: local vs distributed schedules on big buckets.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/shard_bench.py [--out BENCH_shard.json]

(The driver re-execs itself with that flag when the host exposes fewer
devices than ``--devices``, so a bare ``python benchmarks/shard_bench.py``
works on a laptop CPU.)

One experiment per closure bucket size: a batch of ragged min-plus closure
requests (the serving engine's heaviest bucket shape) executed five ways —
the single-device batched path, and the four batched mesh schedules from
core/distributed.py (dp / kspan / SUMMA / ring) on a (dp, mp) host-device
mesh.  The batch mixes one high-diameter line graph (the straggler that
needs all lg(n) squarings) with fast-converging dense graphs — the
convergence mix real closure buckets have, and the one where dp's
independent per-device fixpoints decouple the straggler from everyone else.
All arms run the identical padded stack with the identical per-request
``valid_n`` ragged masks, and every arm's output is asserted equal to the
local arm before timing counts.

Results land in a JSON perf-trajectory artifact; mesh rows for the winning
(and losing) schedules can be recorded into a dispatch cost table with
``--cost-table``, which is how measured mesh rows reach ``backend="auto"``
serving (launch/serve_mmo.py --mesh … --cost-table …).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

# script-mode friendliness: `python benchmarks/shard_bench.py` puts only
# benchmarks/ on sys.path — add the repo root so benchmarks.common resolves
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
  sys.path.insert(0, _ROOT)


def _line_graph(n, seed=0):
  """Path graph i→i+1: diameter n−1 — the straggler that keeps Leyzorek
  iterating lg(n) rounds."""
  rng = np.random.default_rng(seed)
  w = np.full((n, n), np.inf, np.float32)
  w[np.arange(n - 1), np.arange(1, n)] = rng.uniform(
      0.5, 1.5, n - 1).astype(np.float32)
  return w


def _dense_graph(n, seed=0):
  rng = np.random.default_rng(seed)
  w = rng.uniform(0.5, 1.5, (n, n)).astype(np.float32)
  w[rng.random((n, n)) > 0.5] = np.inf
  return w


def bench_bucket(nb: int, mesh, *, requests: int = 8, iters: int = 3,
                 backend: str = "xla"):
  """{arm: seconds} + parity for one (R, nb, nb) min-plus closure bucket.

  Also times a single batched squaring per arm (``step_seconds``, normalized
  per request) — the measurement whose units match the cost table's
  one-(m, k, n)-contraction signature; whole-fixpoint wall times do not.
  """
  import jax
  import jax.numpy as jnp

  from benchmarks.common import timeit
  from repro.core import mmo_batched, pad_adjacency, prepare_adjacency
  from repro.core.closure import batched_leyzorek_closure
  from repro.core.distributed import (SCHEDULES, mmo_sharded_batched,
                                      sharded_closure_batched)

  rng = np.random.default_rng(nb)
  sizes = [int(rng.integers(nb // 2 + 1, nb + 1)) for _ in range(requests - 1)]
  sizes.append(nb)
  ws = [_line_graph(n, seed=n) for n in sizes[:1]] + [
      _dense_graph(n, seed=n) for n in sizes[1:]]
  prepared = [prepare_adjacency(jnp.asarray(w), op="minplus") for w in ws]
  stack = jnp.stack([pad_adjacency(p, nb, op="minplus") for p in prepared])
  valid = jnp.asarray(sizes, jnp.int32)

  arms = {}
  local_fn = lambda: batched_leyzorek_closure(  # noqa: E731
      stack, op="minplus", backend=backend, valid_n=valid)[0]
  local_out = np.asarray(local_fn())
  arms["local"] = timeit(local_fn, iters=iters)
  for sched in SCHEDULES:
    fn = lambda s=sched: sharded_closure_batched(  # noqa: E731
        stack, op="minplus", mesh=mesh, schedule=s, backend=backend,
        valid_n=valid)[0]
    out = np.asarray(fn())
    assert np.array_equal(out, local_out), f"{sched} diverged from local"
    arms[sched] = timeit(fn, iters=iters)
  step_fns = {"local": jax.jit(lambda x: mmo_batched(
      x, x, op="minplus", backend=backend, k_valid=valid))}
  for sched in SCHEDULES:
    step_fns[sched] = jax.jit(lambda x, s=sched: mmo_sharded_batched(
        x, x, op="minplus", schedule=s, mesh=mesh, backend=backend,
        k_valid=valid))
  steps = {}
  for name, f in step_fns.items():  # timeit's warmup call absorbs compile
    steps[name] = timeit(lambda: f(stack), iters=iters) / requests

  best_sched = min((s for s in arms if s != "local"), key=arms.get)
  return {
      "bucket": nb,
      "requests": requests,
      "sizes": sizes,
      "seconds": arms,
      "step_seconds": steps,
      "best_schedule": best_sched,
      "speedup_best_vs_local": arms["local"] / arms[best_sched],
  }


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("--out", default="BENCH_shard.json")
  ap.add_argument("--buckets", default="64,128,256",
                  help="comma-separated closure bucket sizes")
  ap.add_argument("--requests", type=int, default=8,
                  help="requests per bucket batch (divisible by the device "
                       "count so the dp arm can shard the request axis)")
  ap.add_argument("--iters", type=int, default=3)
  ap.add_argument("--devices", type=int, default=8,
                  help="fake host devices to request when the host has fewer")
  ap.add_argument("--mesh", default="2,4", metavar="DP,MP")
  ap.add_argument("--cost-table", default=None, metavar="PATH",
                  help="record the measured mesh rows (and local row) into "
                       "this dispatch cost table (created if missing)")
  args = ap.parse_args(argv)

  import jax
  if len(jax.devices()) < args.devices and jax.default_backend() == "cpu":
    # must be set before jax initializes — re-exec with the flag appended
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{args.devices}").strip()
    return subprocess.call([sys.executable, os.path.abspath(__file__),
                            *(argv or sys.argv[1:])], env=env)

  from repro.core.distributed import make_mesh
  dims = tuple(int(x) for x in args.mesh.split(","))
  mesh = make_mesh(dims)
  buckets = tuple(int(b) for b in args.buckets.split(","))

  rows = []
  for nb in buckets:
    row = bench_bucket(nb, mesh, requests=args.requests, iters=args.iters)
    rows.append(row)
    secs = "  ".join(f"{a}={s * 1e3:8.1f}ms" for a, s in
                     row["seconds"].items())
    print(f"[shard_bench] bucket={nb:4d} R={args.requests}  {secs}  "
          f"best={row['best_schedule']} "
          f"({row['speedup_best_vs_local']:.2f}x vs local)")

  if args.cost_table:
    from repro.core.distributed import SCHEDULES
    from repro.tuning import CostTable
    table = (CostTable.load(args.cost_table)
             if os.path.exists(args.cost_table) else CostTable(
                 device=f"{jax.default_backend()}-mesh{args.mesh}"))
    # record the per-request single-squaring timings: the table's signature
    # is one (m, k, n) contraction, so whole-fixpoint wall times would be
    # off by R × iterations and poison every later backend="auto" resolve
    for row in rows:
      shape = (row["bucket"],) * 3
      table.record("minplus", shape, "float32", "xla", (512,),
                   row["step_seconds"]["local"])
      for sched in SCHEDULES:
        table.record("minplus", shape, "float32", sched, dims,
                     row["step_seconds"][sched])
    table.save(args.cost_table)
    print(f"[shard_bench] recorded {(1 + len(SCHEDULES)) * len(rows)} "
          f"measured rows → {args.cost_table}")

  doc = {
      "schema": 1,
      "device": jax.default_backend(),
      "n_devices": len(jax.devices()),
      "mesh": list(dims),
      "buckets": rows,
  }
  with open(args.out, "w") as f:
    json.dump(doc, f, indent=2)
  print(f"[shard_bench] wrote {args.out}")

  biggest = rows[-1]
  assert biggest["speedup_best_vs_local"] > 1.0, (
      f"no distributed schedule beat the local path on the largest closure "
      f"bucket ({biggest['bucket']}): {biggest['seconds']}")
  return 0


if __name__ == "__main__":
  raise SystemExit(main())
