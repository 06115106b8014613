"""Sharded serving — one engine, small buckets local, big buckets on a mesh.

    PYTHONPATH=src python examples/serve_sharded.py

Runs on a laptop CPU: XLA_FLAGS is defaulted below to expose 8 fake host
devices before jax initializes.  The engine builds a (data=2, model=4) mesh
and routes each shape bucket by contraction size: APSP requests below the
``shard_flops`` cutoff execute on one device, the big ones run their closure
as a batched SUMMA squaring schedule across all 8 — same request API, same
results, one scheduler.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.apps import graphs, solvers  # noqa: E402
from repro.core.distributed import make_mesh  # noqa: E402
from repro.serve_mmo import MMOEngine, apsp_request  # noqa: E402


def main():
  n_dev = len(jax.devices())
  dims = (2, 4) if n_dev >= 8 else (1, n_dev)
  mesh = make_mesh(dims)
  print(f"mesh: data={dims[0]} × model={dims[1]} on {n_dev} "
        f"{jax.default_backend()} devices")

  # 2·16³ ≈ 8e3 flops stays local; 2·64³ ≈ 5e5 crosses the 1e5 cutoff
  eng = MMOEngine(backend="xla", mesh=mesh, schedule="summa",
                  shard_flops=1e5, max_batch=4)

  small = {n: graphs.weighted_digraph(n, 0.3, seed=n) for n in (9, 12, 14)}
  big = {n: graphs.weighted_digraph(n, 0.25, seed=n) for n in (49, 55, 62)}
  futs = {n: eng.submit(apsp_request(w)) for n, w in {**small, **big}.items()}
  eng.run_until_idle()

  placement = {k.shape[0]: s for k, s in eng._schedules.items()}
  for n, w in sorted({**small, **big}.items()):
    res = futs[n].result()
    ref, _ = solvers.apsp(w)
    np.testing.assert_allclose(res.value, np.asarray(ref), atol=1e-5)
    bucket = 1 << (n - 1).bit_length()
    print(f"apsp n={n:>2} → bucket {bucket:>3} [{placement[bucket]:>6}]  "
          f"closed in {res.extras['iterations']} iterations, matches solver")

  # steady state: repeat traffic replays cached executables on both paths
  misses = eng.cache.misses
  futs2 = [eng.submit(apsp_request(w)) for w in {**small, **big}.values()]
  eng.run_until_idle()
  assert all(f.done() for f in futs2)
  print(f"repeat traffic: {eng.cache.misses - misses} new compiles "
        f"(sharded + local executables cached independently)")
  print(eng.stats().summary())


if __name__ == "__main__":
  main()
