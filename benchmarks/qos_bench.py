"""QoS benchmark: deadline traffic under bulk interference, FIFO vs deadline
policy, adaptive vs static predictions under the service-time batch cap,
plus admission bounding and the scheduler pick microbench.

    PYTHONPATH=src python benchmarks/qos_bench.py [--out BENCH_qos.json]
    PYTHONPATH=src python benchmarks/qos_bench.py --smoke   # CI-sized

Four experiments land in one JSON perf-trajectory artifact:

  interference — a burst of bulk closure requests is submitted ahead of a
      trickle of small deadline-tagged problems (the latency-sensitive
      slice).  Both engines are prewarmed (no compile time in the numbers).
      Under FIFO the deadline slice waits behind every older bulk batch;
      under the deadline policy it is served first.  The artifact records
      p50/p99 per class per policy and asserts the headline claim: deadline
      policy p99 for deadline traffic >= 2x better than FIFO.

  adaptive — the live-feedback claim: two identically configured engines
      (deadline policy, service-time batch cap ``max_batch_seconds``) serve
      an urgent deadline-tagged trickle against a sustained bulk closure
      stream on the background loop.  The *static* engine prices the cap
      with cost-table/roofline predictions — on CPU those are orders of
      magnitude optimistic, so the cap never binds and each urgent arrival
      waits behind a full max_batch bulk batch.  The *adaptive* engine's
      EWMA estimator has learned real batch latency, the cap binds, bulk
      batches stay short, and urgent p99 drops.  Asserted: adaptive p99 >=
      1.5x better, with zero steady-state retraces in the measured window.

  admission — the same bulk burst thrown at an engine with ``max_queue``:
      queue depth stays at the cap, the overflow is rejected at submit (not
      queued forever), and everything admitted completes.  Asserted.

  pick_bench — scheduler bucket-pick cost vs bucket diversity: the lazy-heap
      picker (serve_mmo/policy.py) against the O(buckets) linear scan it
      replaced, at 16 / 256 / 1024 distinct buckets.  The heap's per-pick
      cost stays flat while the scan grows with diversity.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# script-mode friendliness: `python benchmarks/qos_bench.py` puts only
# benchmarks/ on sys.path — add the repo root so repro.* resolves via src
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
  if _p not in sys.path:
    sys.path.insert(0, _p)

RNG = np.random.default_rng(0)


def _mmo_req(n, **qos):
  from repro.serve_mmo import mmo_request
  a = RNG.standard_normal((n, n)).astype(np.float32)
  b = RNG.standard_normal((n, n)).astype(np.float32)
  return mmo_request(a, b, op="mma", **qos)


def _bulk_req(n, seed, **qos):
  from repro.apps import graphs
  from repro.serve_mmo import apsp_request
  return apsp_request(graphs.weighted_digraph(n, 0.3, seed=seed),
                      tenant="bulk", **qos)


def interference(policy: str, *, bulk_n: int, bulk_count: int,
                 urgent_count: int, max_batch: int = 4) -> dict:
  """Latency percentiles per traffic class for one policy."""
  from repro.serve_mmo import MMOEngine
  eng = MMOEngine(backend="xla", max_batch=max_batch, policy=policy)
  eng.prewarm([_bulk_req(bulk_n, seed=0), _mmo_req(12)])
  t0 = time.perf_counter()
  bulk = [eng.submit(_bulk_req(bulk_n - (i % 3), seed=i))
          for i in range(bulk_count)]
  urgent = [eng.submit(_mmo_req(12, deadline_s=120.0, priority=1,
                                tenant="interactive"))
            for _ in range(urgent_count)]
  eng.run_until_idle()
  wall = time.perf_counter() - t0
  assert all(f.state == "done" for f in bulk + urgent), "a request failed"
  recs = {r.request_id: r for r in eng._records}

  def pcts(futs):
    lat = [recs[f.request.request_id].latency_s for f in futs]
    return {"p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3}

  return {"policy": policy, "wall_s": wall,
          "deadline_traffic": pcts(urgent), "bulk_traffic": pcts(bulk)}


def adaptive_interference(*, bulk_n: int, bulk_count: int, urgent_count: int,
                          max_batch: int = 8) -> dict:
  """Urgent p99 under a sustained bulk stream: static vs adaptive
  predictions feeding the same service-time batch cap."""
  from repro.serve_mmo import MMOEngine
  from repro.serve_mmo.scheduler import request_bucket

  def build(adaptive, cap):
    eng = MMOEngine(backend="xla", max_batch=max_batch, policy="deadline",
                    adaptive=adaptive, max_batch_seconds=cap,
                    deadline_lookback_s=60.0)
    eng.prewarm([_bulk_req(bulk_n, seed=0), _mmo_req(12)])
    # feedback warmup: mixed waves so the estimator's (bucket, backend,
    # schedule) cells pass min_observations and the first *execution* of
    # every batch size the measured window will replay (bulk rb=1 under
    # the cap, rb=2, urgent rb=1) is out of the measured numbers
    for wave in range(4):
      for j in range(1 + wave % 2):
        eng.submit(_bulk_req(bulk_n, seed=100 + 4 * wave + j))
      eng.submit(_mmo_req(12, deadline_s=60.0, priority=1,
                          tenant="interactive"))
      eng.run_until_idle()
    eng.reset_stats()
    return eng

  # calibrate the cap from measured reality so the experiment is
  # machine-independent: ~1.6x one bulk request's measured service time,
  # i.e. the cap wants single-request bulk batches while urgents flow.
  # The estimator *records* on static engines too — only predictions
  # differ — so the calibration engine can be the static build.
  cal = build(adaptive=False, cap=None)
  bulk_key = request_bucket(_bulk_req(bulk_n, seed=0))
  backend, _ = cal.placement.resolve_backend(bulk_key)
  per_req = cal.estimator.predict(bulk_key, backend, "local", 0.0, 1.0)
  assert per_req.source == "ewma", "calibration estimator never warmed"
  cap = 1.6 * per_req.seconds

  def run(adaptive):
    eng = build(adaptive, cap)
    static_pred = eng.predict_request_seconds(bulk_key)
    misses_before = eng.cache.misses
    bulk = [eng.submit(_bulk_req(bulk_n - (i % 3), seed=i))
            for i in range(bulk_count)]
    eng.start()
    urgent = []
    for i in range(urgent_count):
      # pace urgents so each lands mid-bulk-batch, and replenish the bulk
      # stream so backlog pressure is sustained across the whole window
      time.sleep(3.0 * per_req.seconds)
      urgent.append(eng.submit(_mmo_req(12, deadline_s=30.0, priority=1,
                                        tenant="interactive")))
      bulk.append(eng.submit(_bulk_req(bulk_n, seed=1000 + i)))
      bulk.append(eng.submit(_bulk_req(bulk_n - 1, seed=2000 + i)))
    for f in urgent:
      f.result(timeout=300)
    eng.stop()  # drains the remaining bulk
    assert all(f.state == "done" for f in bulk + urgent), "a request failed"
    recompiles = eng.cache.misses - misses_before
    recs = {r.request_id: r for r in eng._records}
    lat = [recs[f.request.request_id].latency_s for f in urgent]
    bulk_batches = [recs[f.request.request_id].batch_size for f in bulk]
    return {
        "adaptive": adaptive,
        "max_batch_seconds": cap,
        "bulk_pred_ms_per_request": static_pred * 1e3,
        "urgent_p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "urgent_p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "mean_bulk_batch": float(np.mean(bulk_batches)),
        "recompiles_measured_window": recompiles,
        "estimator": eng.estimator.snapshot(),
    }

  rows = {"static": run(adaptive=False), "adaptive": run(adaptive=True)}
  rows["p99_speedup_adaptive_vs_static"] = (
      rows["static"]["urgent_p99_ms"] / rows["adaptive"]["urgent_p99_ms"])
  rows["measured_bulk_ms_per_request"] = per_req.seconds * 1e3
  return rows


def admission(*, bulk_n: int, offered: int, max_queue: int) -> dict:
  """Queue depth stays at the cap; overflow rejects instead of queueing."""
  from repro.serve_mmo import MMOEngine
  eng = MMOEngine(backend="xla", max_batch=4, max_queue=max_queue)
  eng.prewarm([_bulk_req(bulk_n, seed=0)])
  futs = [eng.submit(_bulk_req(bulk_n, seed=i)) for i in range(offered)]
  depth_at_burst = len(eng.scheduler)
  eng.run_until_idle()
  st = eng.stats()
  row = {"offered": offered, "max_queue": max_queue,
         "depth_at_burst": depth_at_burst,
         "admitted": sum(f.state != "rejected" for f in futs),
         "rejected": st.rejected, "completed": st.completed}
  assert depth_at_burst <= max_queue, row
  assert st.rejected == offered - max_queue, row
  assert st.completed == max_queue, row
  return row


def pick_bench(bucket_counts=(16, 256, 1024), picks: int = 2000) -> list:
  """ns/pick for the lazy-heap picker vs the linear scan it replaced.

  Pure scheduler work — requests are tiny and never execute.  Each bucket
  holds enough entries that picks never exhaust the queue mid-measurement.
  """
  from repro.serve_mmo import ProblemRequest
  from repro.serve_mmo.scheduler import BucketScheduler

  def fill(sched, n_buckets, per_bucket):
    a = np.zeros((4, 4), np.float32)
    for i in range(n_buckets):
      for _ in range(per_bucket):
        sched.add(ProblemRequest(kind="mmo", op="mma",
                                 arrays={"a": a, "b": a}, shape=(4, 4, 4),
                                 params=(False, "pickbench", i)))

  def linear_next(sched):  # the pre-heap implementation, kept for comparison
    best_key, best_seq = None, None
    for key, q in sched._buckets.items():
      if q and (best_seq is None or q[0].seq < best_seq):
        best_key, best_seq = key, q[0].seq
    return best_key

  rows = []
  for n_buckets in bucket_counts:
    per_bucket = max(2, picks // n_buckets + 2)
    sched = BucketScheduler(policy="fifo", max_batch=1)
    fill(sched, n_buckets, per_bucket)
    t0 = time.perf_counter()
    for _ in range(picks):
      sched.next_batch()
    heap_ns = (time.perf_counter() - t0) / picks * 1e9

    sched = BucketScheduler(policy="fifo", max_batch=1)
    fill(sched, n_buckets, per_bucket)
    t0 = time.perf_counter()
    for _ in range(picks):
      linear_next(sched)
    linear_ns = (time.perf_counter() - t0) / picks * 1e9

    rows.append({"buckets": n_buckets, "heap_ns_per_pick": heap_ns,
                 "linear_scan_ns_per_pick": linear_ns})
  return rows


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("--out", default="BENCH_qos.json")
  ap.add_argument("--smoke", action="store_true",
                  help="CI-sized: small bulk problems, few requests")
  ap.add_argument("--bulk-n", type=int, default=None,
                  help="bulk closure problem size (default 48; smoke 24)")
  ap.add_argument("--bulk-count", type=int, default=None)
  ap.add_argument("--urgent-count", type=int, default=None)
  args = ap.parse_args(argv)

  bulk_n = args.bulk_n or (24 if args.smoke else 48)
  bulk_count = args.bulk_count or (8 if args.smoke else 16)
  urgent_count = args.urgent_count or (6 if args.smoke else 12)

  rows = {p: interference(p, bulk_n=bulk_n, bulk_count=bulk_count,
                          urgent_count=urgent_count)
          for p in ("fifo", "deadline")}
  for p, row in rows.items():
    d, b = row["deadline_traffic"], row["bulk_traffic"]
    print(f"[qos_bench] policy={p:9s} deadline-traffic "
          f"p50={d['p50_ms']:8.1f}ms p99={d['p99_ms']:8.1f}ms | bulk "
          f"p50={b['p50_ms']:8.1f}ms p99={b['p99_ms']:8.1f}ms")
  fifo_p99 = rows["fifo"]["deadline_traffic"]["p99_ms"]
  ddl_p99 = rows["deadline"]["deadline_traffic"]["p99_ms"]
  speedup = fifo_p99 / ddl_p99
  print(f"[qos_bench] deadline-policy p99 {speedup:.1f}x better than FIFO "
        f"for deadline traffic under bulk interference")

  # the adaptive experiment needs bulk batches whose cost scales ~linearly
  # with occupancy (compute-dominated bucket), so it sizes independently of
  # --bulk-n: n=72 pads to the 128 closure bucket
  ada = adaptive_interference(bulk_n=72,
                              bulk_count=12 if args.smoke else 16,
                              urgent_count=10 if args.smoke else 16)
  for name in ("static", "adaptive"):
    row = ada[name]
    print(f"[qos_bench] predictions={name:8s} urgent "
          f"p50={row['urgent_p50_ms']:7.1f}ms p99={row['urgent_p99_ms']:7.1f}ms"
          f" | mean bulk batch={row['mean_bulk_batch']:.2f} "
          f"pred={row['bulk_pred_ms_per_request']:.4f}ms/req "
          f"recompiles={row['recompiles_measured_window']}")
  ada_speedup = ada["p99_speedup_adaptive_vs_static"]
  print(f"[qos_bench] adaptive predictions p99 {ada_speedup:.1f}x better than "
        f"static under the same max_batch_seconds="
        f"{ada['static']['max_batch_seconds'] * 1e3:.1f}ms cap "
        f"(measured bulk {ada['measured_bulk_ms_per_request']:.1f}ms/req)")

  adm = admission(bulk_n=bulk_n, offered=bulk_count + 8,
                  max_queue=bulk_count // 2)
  print(f"[qos_bench] admission: offered={adm['offered']} "
        f"cap={adm['max_queue']} depth_at_burst={adm['depth_at_burst']} "
        f"rejected={adm['rejected']} completed={adm['completed']}")

  picks = pick_bench(bucket_counts=(16, 64) if args.smoke
                     else (16, 256, 1024))
  for r in picks:
    print(f"[qos_bench] pick: buckets={r['buckets']:5d} "
          f"heap={r['heap_ns_per_pick']:8.0f}ns "
          f"linear={r['linear_scan_ns_per_pick']:8.0f}ns")

  doc = {
      "schema": 1,
      "smoke": bool(args.smoke),
      "bulk_n": bulk_n,
      "bulk_count": bulk_count,
      "urgent_count": urgent_count,
      "interference": rows,
      "deadline_p99_speedup_vs_fifo": speedup,
      "adaptive": ada,
      "admission": adm,
      "pick_bench": picks,
  }
  with open(args.out, "w") as f:
    json.dump(doc, f, indent=2)
  print(f"[qos_bench] wrote {args.out}")

  assert speedup >= 2.0, (
      f"deadline policy p99 only {speedup:.2f}x better than FIFO "
      f"({ddl_p99:.1f}ms vs {fifo_p99:.1f}ms) — expected >= 2x")
  assert ada_speedup >= 1.5, (
      f"adaptive predictions p99 only {ada_speedup:.2f}x better than static "
      f"({ada['adaptive']['urgent_p99_ms']:.1f}ms vs "
      f"{ada['static']['urgent_p99_ms']:.1f}ms) under the batch cap — "
      f"expected >= 1.5x")
  for name in ("static", "adaptive"):
    assert ada[name]["recompiles_measured_window"] == 0, (
        f"{name} run recompiled mid-measurement: "
        f"{ada[name]['recompiles_measured_window']} misses")
  return 0


if __name__ == "__main__":
  raise SystemExit(main())
