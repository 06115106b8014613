"""Inputs of the SIMD² paper's graph applications, made from a seed.

The generators follow the paper's conventions per ring (missing-edge
sentinel, self value), as ``repro.apps.graphs`` draws them; they are kept
here so that what the benchmark feeds the engine cannot change with the
program.  ``APPS`` maps each application of Table 4 to its semiring and its
generator; an application's ``degree`` (mean out-degree) comes from the
configuration.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def weighted_digraph(n: int, density: float, rng) -> np.ndarray:
  """APSP: weights in [1, 10), +inf where no edge, 0 on the diagonal."""
  w = rng.uniform(1.0, 10.0, (n, n)).astype(np.float32)
  w[rng.random((n, n)) >= density] = np.inf
  np.fill_diagonal(w, 0.0)
  return w


def dag(n: int, density: float, rng) -> np.ndarray:
  """APLP: edges i→j only for i < j (acyclic), -inf where no edge."""
  w = rng.uniform(1.0, 10.0, (n, n)).astype(np.float32)
  keep = (rng.random((n, n)) < density) & np.triu(np.ones((n, n), bool), 1)
  w = np.where(keep, w, -np.inf).astype(np.float32)
  np.fill_diagonal(w, 0.0)
  return w


def capacity_graph(n: int, density: float, rng) -> np.ndarray:
  """MCP: capacities in [1, 100), 0 where no edge, +inf on the diagonal."""
  c = rng.uniform(1.0, 100.0, (n, n)).astype(np.float32)
  c[rng.random((n, n)) >= density] = 0.0
  np.fill_diagonal(c, np.inf)
  return c


def reliability_graph(n: int, density: float, rng, *,
                      maximize: bool) -> np.ndarray:
  """MaxRP / MinRP: edge reliabilities in [0.05, 1), diagonal 1; missing is
  0 for max-mul and +inf for min-mul.  The min-mul graph is acyclic: with
  products below 1 a cycle has no fixed point under min."""
  p = rng.uniform(0.05, 1.0, (n, n)).astype(np.float32)
  missing = 0.0 if maximize else np.inf
  p[rng.random((n, n)) >= density] = missing
  if not maximize:
    p[np.tril_indices(n, 0)] = missing
  np.fill_diagonal(p, 1.0)
  return p


def undirected_weighted(n: int, density: float, rng) -> np.ndarray:
  """MST: symmetric, unique positive integer weights (exact in float32),
  +inf where no edge; a random spanning path keeps the graph connected."""
  w = np.full((n, n), np.inf, dtype=np.float32)
  iu = np.triu_indices(n, 1)
  keep = rng.random(len(iu[0])) < density
  vals = rng.permutation(len(iu[0])).astype(np.float32) + 1.0
  w[iu[0][keep], iu[1][keep]] = vals[keep]
  order = rng.permutation(n)
  a, b = order[:-1], order[1:]
  i, j = np.minimum(a, b), np.maximum(a, b)
  gap = ~np.isfinite(w[i, j])
  w[i[gap], j[gap]] = (len(vals) + 1 + np.nonzero(gap)[0]).astype(np.float32)
  w = np.minimum(w, w.T)
  np.fill_diagonal(w, 0.0)
  return w


def boolean_digraph(n: int, density: float, rng) -> np.ndarray:
  """GTC: boolean adjacency, True on the diagonal."""
  adj = rng.random((n, n)) < density
  np.fill_diagonal(adj, True)
  return adj


@dataclasses.dataclass(frozen=True)
class App:
  op: str        # the engine's semiring mnemonic
  make: object   # (n, density, rng) -> adjacency


APPS = {
    "apsp": App("minplus", weighted_digraph),
    "aplp": App("maxplus", dag),
    "mcp": App("maxmin", capacity_graph),
    "maxrp": App("maxmul", lambda n, d, rng: reliability_graph(
        n, d, rng, maximize=True)),
    "minrp": App("minmul", lambda n, d, rng: reliability_graph(
        n, d, rng, maximize=False)),
    "mst": App("minmax", undirected_weighted),
    "gtc": App("orand", boolean_digraph),
}


def make_input(app: str, n: int, degree: float, rng) -> np.ndarray:
  """One adjacency of ``app`` with ``n`` vertices and mean out-degree
  ``degree``."""
  return APPS[app].make(n, min(1.0, degree / n), rng)


def relabel(adj: np.ndarray, perm: np.ndarray) -> np.ndarray:
  """The same graph with vertex ``a`` renamed ``perm[a]``'s old label:
  ``out[a, b] = adj[perm[a], perm[b]]``.  Its closure is the closure of
  ``adj`` relabelled the same way, and it costs the same work."""
  return adj[perm[:, None], perm[None, :]]
