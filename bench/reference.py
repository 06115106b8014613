"""Plain references for every application, and the comparisons that decide
``correct``.

The references share no code with the program.  Reachability (GTC) is a
breadth-first search from every source and bottleneck paths (MST) come from
Kruskal's spanning tree — classic algorithms other than the engine's
closure, as in ``repro.apps.baselines``, copied here.  The dense path
problems at n = 4096 are too large for a host loop, so their reference is
the k-pivot Floyd-Warshall recurrence written in ``jax.numpy`` and run on
the device after the window: elementwise ⊕/⊗ with no matrix unit, so
float32 there is float32.

``precision`` is the dtype each reference computes in: float32 is the
configuration's; the control computes the same reference in bfloat16, the
next precision below, and has to fail the comparison.
"""
from __future__ import annotations

import functools

import numpy as np

EXACT = ("gtc", "mst", "mcp")   # answers are input values: exact in any order
_RINGS = {  # app → (⊕, ⊗) for the k-pivot recurrence
    "apsp": ("min", "add"), "aplp": ("max", "add"), "mcp": ("max", "min"),
    "maxrp": ("max", "mul"), "minrp": ("min", "mul"),
}


def gtc_np(adj: np.ndarray) -> np.ndarray:
  """Reflexive-transitive closure by BFS from every source."""
  n = adj.shape[0]
  out = np.zeros((n, n), dtype=bool)
  nbrs = [np.nonzero(adj[i])[0] for i in range(n)]
  for s in range(n):
    seen = np.zeros(n, dtype=bool)
    seen[s] = True
    frontier = [s]
    while frontier:
      nxt = []
      for u in frontier:
        for v in nbrs[u]:
          if not seen[v]:
            seen[v] = True
            nxt.append(v)
      frontier = nxt
    out[s] = seen
  return out


def _kruskal(w: np.ndarray) -> list:
  n = w.shape[0]
  parent = list(range(n))

  def find(x):
    while parent[x] != x:
      parent[x] = parent[parent[x]]
      x = parent[x]
    return x

  iu, ju = np.triu_indices(n, 1)
  finite = np.isfinite(w[iu, ju])
  edges = sorted(zip(w[iu[finite], ju[finite]].tolist(),
                     iu[finite].tolist(), ju[finite].tolist()))
  tree = []
  for wt, i, j in edges:
    ri, rj = find(i), find(j)
    if ri != rj:
      parent[ri] = rj
      tree.append((i, j, wt))
  return tree


def minimax_np(w: np.ndarray) -> np.ndarray:
  """Bottleneck (min-max) path matrix: the largest edge on the spanning
  tree path between each pair; -inf on the diagonal, +inf if unconnected."""
  n = w.shape[0]
  adj = [[] for _ in range(n)]
  for i, j, wt in _kruskal(w):
    adj[i].append((j, wt))
    adj[j].append((i, wt))
  out = np.full((n, n), np.inf)
  for s in range(n):
    out[s, s] = -np.inf
    stack = [(s, -np.inf)]
    seen = {s}
    while stack:
      u, mx = stack.pop()
      for v, wt in adj[u]:
        if v not in seen:
          seen.add(v)
          m2 = max(mx, wt)
          out[s, v] = m2
          stack.append((v, m2))
  return out


def _round(x: np.ndarray, precision: str) -> np.ndarray:
  if precision == "float32":
    return x
  import ml_dtypes
  return x.astype(getattr(ml_dtypes, precision)).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _fw_program(oplus: str, otimes: str):
  import jax
  import jax.numpy as jnp
  plus = {"min": jnp.minimum, "max": jnp.maximum}[oplus]
  times = {"add": jnp.add, "min": jnp.minimum, "mul": jnp.multiply}[otimes]

  @jax.jit
  def fw(d):
    def body(k, d):
      row = jax.lax.dynamic_slice_in_dim(d, k, 1, 0)
      col = jax.lax.dynamic_slice_in_dim(d, k, 1, 1)
      return plus(d, times(col, row))
    return jax.lax.fori_loop(0, d.shape[0], body, d)

  return fw


def floyd_warshall(app: str, adj: np.ndarray, precision: str):
  """k-pivot closure of a prepared adjacency, left on the default device."""
  import jax.numpy as jnp
  d = jnp.asarray(adj, dtype=jnp.dtype(precision))
  return _fw_program(*_RINGS[app])(d)


def reference(app: str, adj: np.ndarray, precision: str = "float32"):
  """The closure of ``adj`` (diagonal already holds the ring's self value)
  computed by the plain reference for ``app`` in ``precision``: a host
  array for GTC and MST, a device array for the path rings."""
  if app == "gtc":
    return gtc_np(adj)
  if app == "mst":
    return minimax_np(_round(np.asarray(adj, np.float32), precision))
  return floyd_warshall(app, adj, precision)


def number_name(app: str) -> str:
  return f"mismatch.{app}" if app in EXACT else f"max_rel_err.{app}"


def compare(app: str, got, want, perm=None) -> float:
  """The number compared for one answer: entries that differ (exact
  applications), or the largest relative gap |got − want| / |want| (a
  differing infinity, or a finite gap at want = 0, reads +inf), in float32.
  With ``perm`` the answer is for the relabelled graph and is compared with
  ``want[perm][:, perm]``.  A reference left on the device (the path rings)
  is compared there; a host reference on the host."""
  if isinstance(want, np.ndarray) and perm is None:
    xp = np
  else:
    import jax.numpy as xp
  got = xp.asarray(got)
  want = xp.asarray(want)
  if perm is not None:
    p = xp.asarray(perm)
    want = want[p[:, None], p[None, :]]
  if got.shape != want.shape:
    return float("inf")
  if app in EXACT:
    return float(xp.count_nonzero(got.astype(want.dtype) != want))
  got = got.astype(xp.float32)
  want = want.astype(xp.float32)
  with np.errstate(invalid="ignore", divide="ignore"):
    rel = xp.abs(got - want) / xp.abs(want)
  rel = xp.where(got == want, 0.0, rel)
  rel = xp.where(xp.isnan(rel), xp.inf, rel)
  return float(xp.max(rel))
