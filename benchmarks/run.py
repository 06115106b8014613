"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Figure map:
  Fig 9   microbench_square     Fig 12  algo_opts
  Fig 10  microbench_shapes     Fig 13/14  sparse_bench
  Fig 11  apps_bench            Table 5 area_table
  §Roofline  roofline_table (from dry-run artifacts, if present)
  §Dispatch  dispatch_bench (auto vs fixed backends, ragged masked-K, and
             the fused fixpoint megakernel vs per-iteration dispatch →
             BENCH_dispatch.json)
  §Sharding  shard_bench (local vs distributed schedules → BENCH_shard.json;
             re-execs itself with 8 fake host devices on CPU)
  §QoS       qos_bench (deadline vs FIFO under bulk interference, admission
             bounding, scheduler pick cost → BENCH_qos.json)
  §Serving   serve_bench (bucketed engine vs naive loop, zero-recompile
             steady state, observability overhead < 5% → BENCH_serve.json)
  §Faults    resilience_bench (goodput + urgent p99 under injected execute
             faults vs fail-whole-batch, disabled-hook overhead < 2% →
             BENCH_resilience.json)
  §Arena     arena_bench (slot-based continuous batching vs bucket-cycle
             under open-loop Poisson arrivals, zero steady-state retraces →
             BENCH_arena.json)
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
  from repro.analysis.sanitize import maybe_enable_sanitize
  from repro.compile_cache import enable_compile_cache
  maybe_enable_sanitize()  # REPRO_SANITIZE=1: debug_nans + analyzer preflight
  enable_compile_cache()
  from benchmarks import (algo_opts, apps_bench, area_table, arena_bench,
                          dispatch_bench, microbench_shapes,
                          microbench_square, qos_bench, resilience_bench,
                          roofline_table, serve_bench, shard_bench,
                          sparse_bench)
  print("name,us_per_call,derived")
  suites = (
      ("fig9", microbench_square.main),
      ("fig10", microbench_shapes.main),
      ("fig11", apps_bench.main),
      ("fig12", algo_opts.main),
      ("fig13_14", sparse_bench.main),
      ("table5", area_table.main),
      ("roofline", roofline_table.main),
      ("dispatch", dispatch_bench.main),
      ("shard", shard_bench.main),
      ("qos", qos_bench.main),
      ("serve", serve_bench.main),
      ("resilience", resilience_bench.main),
      ("arena", arena_bench.main),
  )
  failed = []
  for name, fn in suites:
    try:
      fn()
    except Exception:  # noqa: BLE001
      failed.append(name)
      print(f"{name}/SUITE_FAILED,0.0,", file=sys.stderr)
      traceback.print_exc()
  if failed:
    raise SystemExit(f"failed suites: {failed}")


if __name__ == "__main__":
  main()
