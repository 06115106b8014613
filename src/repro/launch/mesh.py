"""Production mesh construction (assignment-mandated shapes).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the batch is sharded
over (pod, data) — the pod axis is a pure data-parallel outer axis, so the
only cross-pod collective is the gradient all-reduce (DCN-friendly).

Functions, not module constants — importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax

from repro.core.distributed import make_mesh
from repro.models.common import Parallelism


def make_production_mesh(*, multi_pod: bool = False):
  shape = (2, 16, 16) if multi_pod else (16, 16)
  axes = ("pod", "data", "model") if multi_pod else ("data", "model")
  return make_mesh(shape, axes)


def make_parallelism(*, multi_pod: bool = False, fsdp: bool = True,
                     seq_shard_decode: bool = True,
                     remat: str = "none") -> Parallelism:
  return Parallelism(
      data_axes=("pod", "data") if multi_pod else ("data",),
      model_axis="model",
      tp_size=16,
      dp_size=32 if multi_pod else 16,
      fsdp=fsdp,
      seq_shard_decode=seq_shard_decode,
      remat=remat,
  )


def make_host_mesh(n_devices: int = 0, model: int = 2):
  """Small mesh over host devices (tests / examples)."""
  n = n_devices or len(jax.devices())
  model = min(model, n)
  return make_mesh((n // model, model), ("data", "model"))
