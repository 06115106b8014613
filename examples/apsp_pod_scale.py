"""Distributed APSP: the paper's flagship application at (simulated) pod
scale — the min-plus closure runs 2-D-sharded across a device mesh with
SUMMA semiring matmuls (core/distributed.py).

    PYTHONPATH=src python examples/apsp_pod_scale.py          # host devices
    XLA_FLAGS=--xla_force_host_platform_device_count=16 \
        PYTHONPATH=src python examples/apsp_pod_scale.py      # 16-way mesh
"""
import numpy as np


def main():
  import jax
  import jax.numpy as jnp
  from repro.apps import graphs
  from repro.apps.baselines import apsp_np
  from repro.core import prepare_adjacency
  from repro.core.distributed import distributed_leyzorek, make_mesh

  n_dev = len(jax.devices())
  model = 4 if n_dev % 4 == 0 and n_dev >= 4 else 1
  data = max(1, n_dev // model)
  mesh = make_mesh((data, model))
  print(f"mesh: data={data} × model={model} ({n_dev} devices)")

  n = 512
  w = graphs.weighted_digraph(n, 0.1, seed=7)
  adj = prepare_adjacency(jnp.asarray(w), op="minplus")
  dist = distributed_leyzorek(adj, op="minplus", mesh=mesh)

  ref = apsp_np(w)
  fin = np.isfinite(ref)
  err = np.abs(np.asarray(dist)[fin] - ref[fin]).max()
  print(f"APSP |V|={n}: sharded closure max err = {err:.2e} "
        f"(validated vs Floyd-Warshall)")
  print("C stays 2-D block-sharded across iterations; each squaring "
        "moves only SUMMA K-panels (all-gather row/col).")


if __name__ == "__main__":
  main()
