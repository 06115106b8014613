#!/usr/bin/env python3
"""The control of a cell's correctness check: the plain reference put in
the program's place and computed one precision below the configuration's
(bfloat16 for float32), then compared by the run's own comparison.  Its
readings must fail the cell's limits; the benchmark's runs do not run it.

    python3 bench/control.py --workload closures1024.steady \
        --seeds 11,12,13 --seconds 30

Prints one JSON line per seed: each number compared, its reading and its
limit.  The answers compared are those a run of that seed and length would
compare: in the open loop the same seeded sample of the window's requests,
in the closed loop every base input.
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float,
             precision: str = "bfloat16") -> dict:
  """Number name → the control's worst reading over one seed's answers."""
  from bench import generator, harness, inputs, reference
  cfg, mix = cell.config, cell.traffic
  data = generator.rng_for(seed, 4)
  bases = {}
  served = []
  if mix["loop"] == "open":
    for snd in generator.open_schedule(mix, seed, seconds):
      adj = inputs.make_input(snd.app, snd.n, harness.degree(cfg, snd.app),
                              data)
      served.append(harness.Served(snd.app, snd.n, None, adj, snd.due_s,
                                   outcome="done"))
  else:
    base, _, _ = generator.closed_rounds(mix, seed)
    bases = harness.base_inputs(cfg, base, seed)
    for b, (app, n, _) in enumerate(base):
      served.append(harness.Served(app, n, None, bases[b], 0.0, base=b,
                                   perm=np.arange(n), outcome="done"))
  sample = harness.check_sample(cfg, served, seed)
  for s in sample:
    s.result = types.SimpleNamespace(
        value=reference.reference(s.app, s.adj, precision))
  return harness.compare_served(cfg, sample, bases)


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seeds", required=True)
  ap.add_argument("--seconds", type=float, required=True)
  args = ap.parse_args(argv)
  from bench import spec
  cell = spec.load_cell(args.workload)
  limits = cell.config["check"]["limits"]
  for seed in (int(s) for s in args.seeds.split(",")):
    got = readings(cell, seed, args.seconds)
    print(json.dumps({"workload": args.workload, "seed": seed, "numbers": {
        k: {"value": v, "limit": limits[k]} for k, v in got.items()}}),
        flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
