"""On-chip benchmark of the SIMD² serving engine (see PERF.md).

Run one cell per process: ``python3 bench/main.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>``.  Cells, configurations, traffic mixes and
per-layer metrics are found by name: the cells in ``BENCHMARK.json``, the
rest in ``bench/configs/``, ``bench/traffic/`` and ``bench/metrics/``.
"""
