#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/main.py --workload closures1024.steady --seed 7 \
        --seconds 10 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit
(also the last lines on standard error).  The run exits nonzero and prints
no result when JAX finds no TPU, or fewer TPU chips than the cell asks for.
JAX's persistent compilation cache lives in the checkout's ``.jax_cache/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  args = ap.parse_args(argv)

  from bench import spec
  cell = spec.load_cell(args.workload)
  cache = ROOT / ".jax_cache"
  cache.mkdir(exist_ok=True)
  os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
  import jax
  from repro.compile_cache import enable_compile_cache
  enable_compile_cache()
  # no size bound: the bounded cache keeps access-time files, and one
  # missing makes every later write fail (seen on the chip's machine)
  jax.config.update("jax_compilation_cache_max_size", -1)
  devices = jax.devices()
  if devices[0].platform != "tpu" or len(devices) < cell.chips:
    print(f"[bench] {args.workload} needs {cell.chips} TPU chip(s); JAX "
          f"reports {len(devices)} {devices[0].platform!r} device(s)",
          file=sys.stderr)
    return 2

  from bench import harness
  out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), devices=devices,
                         t_start=T_START, interpret=False)
  print(json.dumps(out), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
