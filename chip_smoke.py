#!/usr/bin/env python3
"""Smoke run of the SIMD² serving engine on a TPU.

    python chip_smoke.py [--seed 0]    # one chip: batch path, megakernel + arena
    python chip_smoke.py --chips 4     # four chips: the dp and SUMMA mesh
                                       # schedules against the local path

Every phase drives ``serve_mmo.MMOEngine`` through ``submit()``, the entry
point a client calls, with ``interpret=False`` and breakers, retries and
bisection off: a kernel the chip refuses fails the run instead of being
served by another arm.  The batch phase runs the paper's Table-4 "small"
sizes (configs/simd2_apps.py).  Inputs come from ``apps/graphs.py`` and
``--seed``; every result is checked against the plain references in
``apps/baselines.py``.

The script exits nonzero, and never prints ``"ok": true``, when JAX finds no
TPU, when a reference check fails, when a retry, batch failure, breaker
open, failed future or post-warmup compile is counted, or when a bucket ran
on another arm than the one pinned.  Its last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything runs in this one process: it holds the chip, and it starts no
child that would need it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.apps import baselines, graphs  # noqa: E402
from repro.configs.simd2_apps import APP_SIZES  # noqa: E402
from repro.kernels import closure_megakernel  # noqa: E402
from repro.serve_mmo import (MMOEngine, apsp_request, knn_request,  # noqa: E402
                             mmo_request, reachability_request)
from repro.serve_mmo.metrics import bucket_label  # noqa: E402

# no fallback may hide the device: a refused kernel fails the run
STRICT = dict(breaker_threshold=None, transient_retries=0, bisect=False)

# f32 agreement with the float64 references, relative to the magnitude of
# the terms a (+)-ring sums or cancels: f32 accumulation keeps ~20 bits
# there, a single bf16 MXU pass only ~8
F32_REL_TOL = 2.0 ** -12


@dataclasses.dataclass(frozen=True)
class Sizes:
  """Problem sizes of every phase; ``Sizes()`` is the run on the chip."""
  apsp_n: int = APP_SIZES["apsp"]["small"]
  gtc_n: int = APP_SIZES["gtc"]["small"]
  knn_queries: int = APP_SIZES["knn"]["small"]
  knn_corpus: int = 16384
  knn_dim: int = 128
  knn_k: int = 8
  mmo_n: int = 2048
  per_kind: int = 2          # batch-phase requests of each kind
  burst: int = 32            # closure requests in the arena burst
  burst_min_n: int = 64
  burst_max_n: int = closure_megakernel.MAX_N
  dp_n: int = 1024           # four-chip phase: dp batch of minplus closures
  dp_batch: int = 8
  summa_n: int = 4096        # four-chip phase: one APSP under SUMMA
  full_ref_n: int = 1024     # largest n checked against a full O(n³) reference
  ref_rows: int = 64         # sampled rows checked above that


class Checks:
  """Collects every check; the run is ok only when none failed."""

  def __init__(self):
    self.failures: list = []
    self.passed = 0

  def expect(self, ok: bool, what: str) -> None:
    if ok:
      self.passed += 1
    else:
      self.failures.append(what)
      print(f"[chip_smoke] CHECK FAILED: {what}", flush=True)


def log(msg: str) -> None:
  print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# inputs and references
# ---------------------------------------------------------------------------


def apsp_graph(n: int, seed: int) -> np.ndarray:
  """Sparse weighted digraph (mean out-degree 4) with integer weights: every
  path sum is exact in f32, so min-plus results compare exactly."""
  w = graphs.weighted_digraph(n, min(1.0, 4.0 / n), seed=seed)
  return np.where(np.isfinite(w), np.rint(w), w).astype(np.float32)


def reach_graph(n: int, seed: int) -> np.ndarray:
  """Sparse boolean digraph (mean out-degree 1.5): reachability closes over
  many hops instead of saturating in two."""
  return graphs.boolean_digraph(n, min(1.0, 1.5 / n), seed=seed)


def sample_rows(n: int, k: int, seed: int) -> np.ndarray:
  if n <= k:
    return np.arange(n)
  return np.sort(np.random.default_rng(seed).choice(n, k, replace=False))


def check_apsp(checks, w, got, sizes, seed, what) -> str:
  n = w.shape[0]
  if n <= sizes.full_ref_n:
    rows, ref, how = np.arange(n), baselines.apsp_np(w), "apsp_np all rows"
  else:
    rows = sample_rows(n, sizes.ref_rows, seed)
    ref = np.stack([baselines.sssp_np(w, int(s)) for s in rows])
    how = f"sssp_np on {len(rows)} sampled source rows"
  checks.expect(np.array_equal(np.asarray(got, np.float64)[rows], ref),
                f"{what}: min-plus closure differs from {how}")
  return how


def check_reach(checks, adj, got, what) -> str:
  checks.expect(np.array_equal(got, baselines.gtc_np(adj)),
                f"{what}: reachability differs from gtc_np")
  return "gtc_np all rows"


def check_knn(checks, queries, corpus, k, got_d2, got_idx, sizes, seed,
              what) -> str:
  rows = sample_rows(queries.shape[0], sizes.ref_rows, seed)
  q = queries[rows]
  ref_d2 = np.concatenate([baselines.knn_np(corpus, q[i:i + 16], k)[0]
                           for i in range(0, len(rows), 16)])
  idx = np.asarray(got_idx)[rows]
  # exact distances of the neighbours returned: near-ties may swap places,
  # but each must be as near as the true i-th neighbour within tolerance
  d_at = ((q[:, None, :].astype(np.float64)
           - corpus[idx].astype(np.float64)) ** 2).sum(-1)
  scale = ((q.astype(np.float64) ** 2).sum(-1)[:, None]
           + (corpus[idx].astype(np.float64) ** 2).sum(-1))
  tol = F32_REL_TOL * scale
  checks.expect(bool(np.all(np.abs(np.asarray(got_d2)[rows] - ref_d2)
                            <= tol)),
                f"{what}: distances differ from knn_np beyond f32 tolerance")
  checks.expect(bool(np.all(np.abs(d_at - ref_d2) <= tol)),
                f"{what}: neighbours are not the k nearest per knn_np")
  checks.expect(all(len(set(r)) == k for r in idx.tolist()),
                f"{what}: repeated neighbour indices")
  return f"knn_np on {len(rows)} sampled queries"


_RINGS = {"mma": (np.add, np.multiply), "maxmin": (np.maximum, np.minimum)}


def check_mmo(checks, op, a, b, got, sizes, seed, what) -> str:
  rows = sample_rows(a.shape[0], sizes.ref_rows, seed)
  ref = baselines.mmo_np(a[rows], b, *_RINGS[op])
  got = np.asarray(got, np.float64)[rows]
  if op == "mma":
    rms = [np.sqrt(np.mean(np.square(x, dtype=np.float64))) for x in (a, b)]
    scale = np.sqrt(a.shape[1]) * rms[0] * rms[1]
    err = float(np.max(np.abs(got - ref)))
    checks.expect(err <= F32_REL_TOL * scale,
                  f"{what}: max |err| {err:.3g} > f32 tolerance "
                  f"{F32_REL_TOL * scale:.3g} vs mmo_np")
  else:
    checks.expect(np.array_equal(got, ref),
                  f"{what}: differs from mmo_np")
  return f"mmo_np on {len(rows)} sampled rows"


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve(engine, reqs):
  """Submit every request, drain the engine; (results, failed futures, s).
  Each pass submits fresh copies, so a request runs once per pass."""
  t0 = time.perf_counter()
  futs = [engine.submit(dataclasses.replace(r)) for r in reqs]
  engine.run_until_idle()
  secs = time.perf_counter() - t0
  results, failed = [], 0
  for f in futs:
    try:
      results.append(f.result())
    except Exception as e:  # noqa: BLE001 — counted, and fails the run
      failed += 1
      results.append(None)
      log(f"request {f.request.request_id} ({f.request.kind}/"
          f"{f.request.op}) {f.state}: {e!r}")
  return results, failed, secs


def serve_twice(checks, engine, reqs, what, pinned):
  """A cold pass (compiles included) then a warm pass, which must compile
  nothing and reproduce the cold results bit for bit."""
  cold, failed_cold, cold_s = serve(engine, reqs)
  compile_s = engine.cache.compile_s
  misses = engine.cache.misses
  warm, failed_warm, warm_s = serve(engine, reqs)
  warm_misses = engine.cache.misses - misses
  for i, (c, w) in enumerate(zip(cold, warm)):
    if c is not None and w is not None:
      checks.expect(np.array_equal(c.value, w.value)
                    and c.extras.keys() == w.extras.keys()
                    and all(np.array_equal(c.extras[k], w.extras[k])
                            for k in c.extras),
                    f"{what}: request {i} differs between cold and warm pass")
  report_engine(checks, engine, what, failed=failed_cold + failed_warm,
                warm_misses=warm_misses, pinned=pinned)
  log(f"{what}: cold {cold_s:.3f}s (compile {compile_s:.3f}s in "
      f"{misses} programs), warm {warm_s:.3f}s")
  return cold, {"cold_s": cold_s, "compile_s": compile_s,
                "programs": misses, "warm_s": warm_s}


def report_engine(checks, engine, what, *, failed: int, warm_misses=None,
                  pinned=None) -> None:
  """Print and check the engine's fault counters and the arms it ran."""
  snap = engine.metrics_snapshot()
  retries = snap["counters"]["retries"]
  failures = sum(snap["batch_failures_by_kind"].values())
  opens = sum(c["opens"] for c in engine.resilience.snapshot())
  log(f"{what}: retries={retries} batch_failures={failures} "
      f"breaker_opens={opens} failed_futures={failed} "
      f"cache_misses_after_warmup={warm_misses}")
  checks.expect(retries == 0, f"{what}: {retries} retries")
  checks.expect(failures == 0, f"{what}: {failures} batch failures")
  checks.expect(opens == 0, f"{what}: {opens} breaker opens")
  checks.expect(failed == 0, f"{what}: {failed} failed futures")
  if warm_misses is not None:
    checks.expect(warm_misses == 0,
                  f"{what}: {warm_misses} compiles after warmup")
  st = engine.stats()
  for (label, backend, schedule), n in sorted(st.arms.items()):
    log(f"{what}: bucket {label} ran {n} launches on {backend}/{schedule}")
  if pinned is not None:
    off = {a: n for a, n in st.arms.items() if a[1:] != pinned}
    checks.expect(not off, f"{what}: buckets off the pinned arm "
                  f"{'/'.join(pinned)}: {off}")
  if st.over_cap:
    log(f"{what}: {st.over_cap} closures above the megakernel cap "
        f"({closure_megakernel.MAX_N}) served on the batch path")


def peak_bytes(device) -> int | None:
  stats = device.memory_stats()
  return None if stats is None else int(stats["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def batch_phase(checks, sizes: Sizes, seed: int, interpret: bool) -> dict:
  """Table-4 "small" problems through the pinned Pallas batch path."""
  rng_seed = seed * 1000
  apsp = [apsp_graph(sizes.apsp_n, rng_seed + i) for i in range(sizes.per_kind)]
  reach = [reach_graph(sizes.gtc_n, rng_seed + 100 + i)
           for i in range(sizes.per_kind)]
  knn = [graphs.knn_points(sizes.knn_corpus, sizes.knn_queries,
                           sizes.knn_dim, seed=rng_seed + 200 + i)
         for i in range(sizes.per_kind)]
  rng = np.random.default_rng(rng_seed + 300)
  mmos = [(op, rng.standard_normal((sizes.mmo_n, sizes.mmo_n), np.float32),
           rng.standard_normal((sizes.mmo_n, sizes.mmo_n), np.float32))
          for op in ("mma", "maxmin") for _ in range(sizes.per_kind)]

  reqs = ([apsp_request(w) for w in apsp]
          + [reachability_request(a) for a in reach]
          + [knn_request(q, c, k=sizes.knn_k) for c, q in knn]
          + [mmo_request(a, b, op=op) for op, a, b in mmos])
  engine = MMOEngine(backend="pallas", interpret=interpret,
                     max_batch=sizes.per_kind, **STRICT)
  out, timing = serve_twice(checks, engine, reqs, "batch",
                            ("pallas", "local"))

  res = iter(out)
  how = set()
  for i, w in enumerate(apsp):
    r = next(res)
    if r is not None:
      how.add(check_apsp(checks, w, r.value, sizes, seed + i, f"apsp[{i}]"))
  for i, a in enumerate(reach):
    r = next(res)
    if r is not None:
      how.add(check_reach(checks, a, r.value, f"reach[{i}]"))
  for i, (c, q) in enumerate(knn):
    r = next(res)
    if r is not None:
      how.add(check_knn(checks, q, c, sizes.knn_k, r.value,
                        r.extras["indices"], sizes, seed + i, f"knn[{i}]"))
  for i, (op, a, b) in enumerate(mmos):
    r = next(res)
    if r is not None:
      how.add(check_mmo(checks, op, a, b, r.value, sizes, seed + i,
                        f"mmo-{op}[{i}]"))
  log(f"batch: references used: {sorted(how)}")
  return timing


def burst_requests(sizes: Sizes, seed: int):
  """Heavy-tailed closure burst: n from a Pareto tail above burst_min_n,
  capped at the megakernel's size cap; minplus and orand alternate."""
  rng = np.random.default_rng(seed * 1000 + 400)
  ns = np.minimum(sizes.burst_max_n,
                  (sizes.burst_min_n * (1.0 + rng.pareto(1.2, sizes.burst)))
                  .astype(int))
  out = []
  for i, n in enumerate(ns.tolist()):
    if i % 2 == 0:
      w = apsp_graph(n, seed * 1000 + 500 + i)
      out.append(("minplus", w, apsp_request(w)))
    else:
      a = reach_graph(n, seed * 1000 + 500 + i)
      out.append(("orand", a, reachability_request(a)))
  return out


def arena_phase(checks, sizes: Sizes, seed: int, interpret: bool) -> dict:
  """A closure burst through the device-resident arena (megakernel ticks),
  checked against the references and, bit for bit with iteration counts,
  against the per-iteration batch path and the batched megakernel."""
  burst = burst_requests(sizes, seed)
  reqs = [r for _, _, r in burst]
  log(f"arena: burst n = {sorted(int(r.shape[0]) for r in reqs)}")
  arena = MMOEngine(backend="pallas", mode="arena", interpret=interpret,
                    **STRICT)
  out, timing = serve_twice(checks, arena, reqs, "arena", ("arena", "local"))
  checks.expect(arena.stats().over_cap == 0,
                "arena: a burst closure left the arena")

  for backend in ("pallas", "megakernel"):
    what = f"batch-{backend}"
    engine = MMOEngine(backend=backend, interpret=interpret, **STRICT)
    ref, failed, secs = serve(engine, reqs)
    report_engine(checks, engine, what, failed=failed,
                  pinned=(backend, "local"))
    log(f"{what}: {secs:.3f}s for the same burst")
    for i, (a, b) in enumerate(zip(out, ref)):
      if a is not None and b is not None:
        checks.expect(np.array_equal(a.value, b.value)
                      and a.extras["iterations"] == b.extras["iterations"],
                      f"arena vs {what}: request {i} (n={reqs[i].shape[0]}) "
                      f"differs in value or iteration count")

  how = set()
  for i, ((op, g, _), r) in enumerate(zip(burst, out)):
    if r is None:
      continue
    if op == "minplus":
      how.add(check_apsp(checks, g, r.value, sizes, seed + i,
                         f"arena-apsp[{i}]"))
    else:
      how.add(check_reach(checks, g, r.value, f"arena-reach[{i}]"))
  log(f"arena: references used: {sorted(how)}")
  return timing


def mesh_phase(checks, sizes: Sizes, seed: int, interpret: bool,
               mesh) -> dict:
  """dp over a batch of closures and SUMMA over one APSP on ``mesh``,
  each checked bit for bit against the one-device local path."""
  rows = {}
  cases = (
      ("dp", [apsp_graph(sizes.dp_n, seed * 1000 + 600 + i)
              for i in range(sizes.dp_batch)]),
      ("summa", [apsp_graph(sizes.summa_n, seed * 1000 + 700)]),
  )
  for schedule, ws in cases:
    reqs = [apsp_request(w) for w in ws]
    local = MMOEngine(backend="pallas", interpret=interpret,
                      max_batch=len(reqs), **STRICT)
    sharded = MMOEngine(backend="pallas", interpret=interpret,
                        max_batch=len(reqs), mesh=mesh, schedule=schedule,
                        shard_flops=0.0, **STRICT)
    want, failed_l, local_s = serve(local, reqs)
    report_engine(checks, local, f"{schedule}-local", failed=failed_l,
                  pinned=("pallas", "local"))
    log(f"{schedule}-local: {local_s:.3f}s, one cold pass on device 0")
    got, timing = serve_twice(checks, sharded, reqs, f"{schedule}-mesh",
                              ("pallas", schedule))
    for i, (a, b) in enumerate(zip(got, want)):
      if a is not None and b is not None:
        checks.expect(np.array_equal(a.value, b.value)
                      and a.extras["iterations"] == b.extras["iterations"],
                      f"{schedule}: request {i} differs from the local path")
    if got[0] is not None:
      check_apsp(checks, ws[0], got[0].value, sizes, seed,
                 f"{schedule}-apsp[0]")
    # the bucket's program must place its output on every mesh device
    for key, compiled in sharded.cache.executables().items():
      if key[4] == "local":
        continue
      shardings = compiled.output_shardings
      shardings = shardings if isinstance(shardings, (list, tuple)) else [
          shardings]
      for s in shardings:
        checks.expect(len(s.device_set) == mesh.size,
                      f"{schedule}: output on {len(s.device_set)} of "
                      f"{mesh.size} devices")
      log(f"{schedule}: {bucket_label(key[0])} outputs on "
          f"{[len(s.device_set) for s in shardings]} devices, fully "
          f"replicated={[s.is_fully_replicated for s in shardings]}")
    timing["local_s"] = local_s
    rows[schedule] = timing
  return rows


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def require_tpu(count: int):
  import jax
  devices = jax.devices()
  if devices[0].platform != "tpu":
    raise SystemExit(f"[chip_smoke] no TPU found: JAX reports platform "
                     f"{devices[0].platform!r}; this run needs a TPU and "
                     f"does not fall back to another backend")
  if len(devices) < count:
    raise SystemExit(f"[chip_smoke] --chips {count} needs {count} TPU "
                     f"devices, JAX reports {len(devices)}")
  return devices


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                  help="4: run only the mesh phase on a (2, 2) mesh")
  args = ap.parse_args(argv)

  devices = require_tpu(args.chips)
  from repro.compile_cache import enable_compile_cache
  log(f"compile cache: {enable_compile_cache()}")
  dev = devices[0]
  log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
      f"megakernel cap n <= {closure_megakernel.MAX_N}")

  sizes, checks = Sizes(), Checks()
  t0 = time.perf_counter()
  if args.chips == 4:
    from repro.core.distributed import make_mesh
    mesh = make_mesh((2, 2), devices=devices[:4])
    mesh_phase(checks, sizes, args.seed, False, mesh)
    # every device must have held its shard of the iterate
    need = min(sizes.dp_batch // 4 * sizes.dp_n ** 2,
               sizes.summa_n ** 2 // 4) * 4
    for d in devices[:4]:
      peak = peak_bytes(d)
      log(f"device {d.id}: peak_bytes_in_use={peak}")
      checks.expect(peak is not None and peak >= need,
                    f"device {d.id} peak {peak} B < one shard ({need} B)")
  else:
    batch_phase(checks, sizes, args.seed, False)
    log(f"peak_bytes_in_use after batch: {peak_bytes(dev)}")
    arena_phase(checks, sizes, args.seed, False)
    log(f"peak_bytes_in_use after arena: {peak_bytes(dev)}")
  log(f"{checks.passed} checks passed, {len(checks.failures)} failed, "
      f"{time.perf_counter() - t0:.1f}s")
  if checks.failures:
    for f in checks.failures:
      print(f"[chip_smoke] FAILED: {f}", file=sys.stderr)
    return 1
  print(json.dumps({"ok": True, "device": {
      "platform": dev.platform, "kind": dev.device_kind,
      "count": len(devices)}}))
  return 0


if __name__ == "__main__":
  sys.exit(main())
