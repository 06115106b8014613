"""The traffic generator: one reader for every mix in ``bench/traffic/``.

A mix is a JSON file of parameters.  Two loops exist:

``"loop": "open"`` — independent users.  ``rate_per_s`` × the window gives
the number of requests; their inter-arrival gaps are exponential (Poisson
arrivals), their sizes ``n = floor(min_n · (1 + Pareto(shape)))`` capped at
``cap``, and the applications come in the ratio of ``apps``.  The multiset of
gaps and of (application, n) pairs is drawn from the mix's own
``mix_seed``; ``--seed`` only shuffles their order and draws the graphs.  So
every seed offers the same work at the same rate, in another order.

``"loop": "closed"`` — ``clients`` callers that each wait for the answer
before sending again.  ``inputs_per_app`` base graphs of each application,
of ``n`` vertices, are drawn from ``--seed``.  The sends come in rounds:
each round sends every application once, in a seeded order, taking that
application's base graphs in turn; every send is its base graph with the
vertices relabelled by a fresh seeded permutation, so no two sends carry
the same operands while each costs the same work.  The window ends at the
round boundary nearest ``--seconds``: every window holds whole rounds.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def rng_for(seed: int, *tags) -> np.random.Generator:
  """An independent stream for one purpose of one run."""
  return np.random.default_rng(np.random.SeedSequence([int(seed), *tags]))


@dataclasses.dataclass(frozen=True)
class Send:
  app: str
  n: int
  due_s: float   # offset from the window's start (open loop)


def open_schedule(mix: dict, seed: int, seconds: float) -> list:
  """The open loop's requests, due in [0, seconds), in due order."""
  count = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
  fixed = rng_for(int(mix["mix_seed"]), 1)
  gaps = fixed.exponential(1.0, count + 1)
  sizes = size_sample(mix["n"], count, fixed)
  apps = app_sample(mix["apps"], count)
  order = rng_for(seed, 2)
  gaps = order.permutation(gaps)
  pairs = order.permutation(count)
  dues = seconds * np.cumsum(gaps[:count]) / gaps.sum()
  dues -= dues[0]
  return [Send(apps[i], int(sizes[i]), float(d))
          for i, d in zip(pairs.tolist(), dues.tolist())]


def size_sample(spec: dict, count: int, rng) -> np.ndarray:
  if "fixed" in spec:
    return np.full(count, int(spec["fixed"]))
  n = spec["min"] * (1.0 + rng.pareto(float(spec["pareto_shape"]), count))
  return np.minimum(int(spec["cap"]), n.astype(int))


def app_sample(weights: dict, count: int) -> list:
  """``count`` application names in the ratio of ``weights`` (largest
  remainder), in a fixed order that the schedule then shuffles."""
  names = sorted(weights)
  total = float(sum(weights.values()))
  exact = [count * weights[a] / total for a in names]
  counts = [int(e) for e in exact]
  for i in sorted(range(len(names)), key=lambda i: counts[i] - exact[i])[
      :count - sum(counts)]:
    counts[i] += 1
  return [a for a, c in zip(names, counts) for _ in range(c)]


def closed_rounds(mix: dict, seed: int):
  """The closed loop's base inputs as (app, n, graph index), the round
  length, and an endless iterator over the base index of each next send."""
  apps = sorted(mix["apps"])
  per_app = int(mix["inputs_per_app"])
  base = [(a, int(mix["n"]["fixed"]), i) for a in apps for i in range(per_app)]

  def order():
    rng = rng_for(seed, 3)
    r = 0
    while True:
      for a in rng.permutation(len(apps)).tolist():
        yield a * per_app + r % per_app
      r += 1

  return base, len(apps), order()
