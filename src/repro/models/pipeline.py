"""Pipeline parallelism (GPipe schedule) via shard_map + collective_permute.

Completes the parallelism matrix (DP/TP/SP/expert-TP/FSDP + **PP**): the
layer stack is split into S stages sharded over a ``stage`` mesh axis; M
microbatches flow through the ring with one `ppermute` per tick
(T = M + S − 1 ticks; bubble fraction (S−1)/T).  Autodiff works through the
schedule (the transpose of ppermute is the reverse ppermute), so the same
function serves forward and training.

This composes with the other axes — e.g. mesh ("stage", "data", "model") —
because the stage axis only appears in the stacked-layer leading dim and the
activation ring.  Used standalone by tests/test_pipeline.py and available to
the launcher for depth-dominated models where TP×FSDP hits its collective
knee (a 1000+-node scaling option recorded in DESIGN.md)."""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def split_stages(stacked_params, n_stages: int):
  """(L, …) stacked layer params → (S, L/S, …)."""
  def re(t):
    l = t.shape[0]
    assert l % n_stages == 0, (l, n_stages)
    return t.reshape(n_stages, l // n_stages, *t.shape[1:])
  return jax.tree.map(re, stacked_params)


def pipeline(stage_fn: Callable, mesh: Mesh, *, axis: str = "stage",
             in_spec: P = None, x_spec: P = None):
  """Build pipelined_apply(stage_params, x_micro) → y_micro.

  stage_fn(params_one_stage, x) → y   (same shape; e.g. a scan over the
  stage's layer slice).  stage_params: (S, L/S, …) sharded on ``axis``;
  x_micro: (M, mb, …) replicated along ``axis`` (sharding over other axes is
  free to compose).
  """
  n_stage = mesh.shape[axis]
  perm = [(i, i + 1) for i in range(n_stage - 1)]

  def spmd(params_local, xs):
    # params_local: (1, L/S, …) — this stage's slice; xs: (M, mb, …)
    params_local = jax.tree.map(lambda t: t[0], params_local)
    sid = jax.lax.axis_index(axis)
    m = xs.shape[0]
    t_total = m + n_stage - 1
    zero = jnp.zeros_like(xs[0])
    outs0 = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")
    buf0 = jax.lax.pcast(zero, (axis,), to="varying")

    def tick(t, carry):
      buf, outs = carry
      # stage 0 injects microbatch t (clamped; masked out when t ≥ M)
      inject = xs[jnp.clip(t, 0, m - 1)]
      inject = jnp.where(t < m, inject, jnp.zeros_like(inject))
      cur = jnp.where(sid == 0, inject, buf)
      y = stage_fn(params_local, cur)
      # last stage emits microbatch t-(S-1)
      oidx = t - (n_stage - 1)
      valid = (sid == n_stage - 1) & (oidx >= 0)
      safe = jnp.clip(oidx, 0, m - 1)
      upd = jnp.where(valid, y, outs[safe])
      outs = outs.at[safe].set(upd)
      buf_next = jax.lax.ppermute(y, axis, perm)
      return buf_next, outs

    _, outs = jax.lax.fori_loop(0, t_total, tick, (buf0, outs0))
    # outputs live on the last stage (zeros elsewhere) → ⊕-collect
    return jax.lax.psum(outs, axis)

  in_spec = in_spec if in_spec is not None else P(axis)
  x_spec = x_spec if x_spec is not None else P()
  return jax.shard_map(spmd, mesh=mesh, in_specs=(in_spec, x_spec),
                       out_specs=x_spec)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
  return (n_stages - 1) / (n_micro + n_stages - 1)
