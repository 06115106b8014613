"""The dp replica router on a four-device (2, 2) mesh: every closure batch
runs on the mesh, its size rounded up to a multiple of the devices, and the
padding slots are inert.

One subprocess with 4 fake host devices (the main process keeps seeing 1)
serves mixed GTC (or-and reachability) and MST (min-max bottleneck path)
batches of each size through a dp engine and a one-device local engine;
each batch size is one case.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SIZES = (1, 2, 3, 5, 8)

_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    from repro.core.distributed import make_mesh
    from repro.serve_mmo import MMOEngine, closure_request

    SIZES = json.loads(sys.argv[1])
    STRICT = dict(backend="xla", max_batch=8, breaker_threshold=None,
                  transient_retries=0, bisect=False)
    dp = MMOEngine(mesh=make_mesh((2, 2)), schedule="dp", shard_flops=0.0,
                   **STRICT)
    local = MMOEngine(**STRICT)
    rng = np.random.default_rng(20260418)

    def gtc_graph(n):
        adj = rng.random((n, n)) < 1.5 / n
        np.fill_diagonal(adj, True)
        return adj

    def mst_graph(n):
        w = np.full((n, n), np.inf, np.float32)
        iu = np.triu_indices(n, 1)
        keep = rng.random(len(iu[0])) < 4.0 / n
        w[iu[0][keep], iu[1][keep]] = (
            rng.permutation(len(iu[0])).astype(np.float32) + 1.0)[keep]
        return np.minimum(w, w.T)

    def bfs_closure(adj):
        n = len(adj)
        out = np.zeros((n, n), bool)
        for s in range(n):
            seen, frontier = {s}, [s]
            while frontier:
                frontier = [v for u in frontier
                            for v in np.nonzero(adj[u])[0] if v not in seen
                            and not seen.add(v)]
            out[s, sorted(seen)] = True
        return out

    def minimax_closure(w):
        d = w.astype(np.float32).copy()
        np.fill_diagonal(d, -np.inf)
        for k in range(len(d)):
            d = np.minimum(d, np.maximum(d[:, k:k + 1], d[k:k + 1, :]))
        return d

    APPS = {"gtc": ("orand", gtc_graph, bfs_closure, (33, 64)),
            "mst": ("minmax", mst_graph, minimax_closure, (17, 32))}

    out = {}
    for size in SIZES:
        dp.reset_stats()
        dp.tracer.clear()
        graphs, futs = [], {"dp": [], "local": []}
        for app, (op, make, _, (lo, hi)) in APPS.items():
            for _ in range(size):
                adj = make(int(rng.integers(lo, hi + 1)))
                graphs.append((app, adj))
                for name, eng in (("dp", dp), ("local", local)):
                    futs[name].append(eng.submit(closure_request(adj, op=op)))
        dp.run_until_idle()
        local.run_until_idle()
        got = [f.result() for f in futs["dp"]]
        ref = [f.result() for f in futs["local"]]
        stats = dp.stats()
        dispatch = [ev["args"] for ev in dp.tracer.events()
                    if ev.get("name") == "batch_dispatch"]
        out[size] = {
            "matches_reference": [
                bool(np.array_equal(r.value, APPS[app][2](adj)))
                for (app, adj), r in zip(graphs, got)],
            "matches_local": [bool(np.array_equal(a.value, b.value))
                              for a, b in zip(got, ref)],
            "iterations": [r.extras["iterations"] for r in got],
            "local_iterations": [r.extras["iterations"] for r in ref],
            "schedules": sorted({s for (_, _, s) in stats.arms}),
            "launches": sum(stats.arms.values()),
            "live": stats.dp_live_slots, "inert": stats.dp_inert_slots,
            "dispatch": dispatch,
        }

    # per-request seconds fed to the estimator: a dp batch of one (rb 4,
    # three inert slots) against the local path's batch of one, on clocks
    # that tick once a read, so both batches last the same ticks
    adj = gtc_graph(40)
    ewma = {}
    for name, kw in (("dp", dict(mesh=make_mesh((2, 2)), schedule="dp",
                                 shard_flops=0.0)), ("local", {})):
        ticks = iter(range(1 << 30))
        eng = MMOEngine(clock=lambda: float(next(ticks)), **STRICT, **kw)
        eng.submit(closure_request(adj, op="orand"))
        eng.run_until_idle()
        ((arm, cell),) = eng.estimator._cells.items()
        ewma[name] = [arm[2], cell.value, eng.stats().dp_inert_slots]
    out["ewma"] = ewma
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def served():
  env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
  proc = subprocess.run([sys.executable, "-c", _SCRIPT, json.dumps(SIZES)],
                        capture_output=True, text=True, env=env, timeout=300)
  assert proc.returncode == 0, proc.stderr[-3000:]
  line = next(ln for ln in proc.stdout.splitlines()
              if ln.startswith("RESULT "))
  return {(int(k) if k.isdigit() else k): v
          for k, v in json.loads(line[7:]).items()}


@pytest.mark.parametrize("size", SIZES)
def test_dp_batch_runs_on_the_mesh_with_inert_padding(served, size):
  got = served[size]
  rb = 4 if size <= 4 else 8
  # answers: the plain references, and bit for bit the one-device path
  assert all(got["matches_reference"]) and len(got["matches_reference"]) == (
      2 * size)
  assert all(got["matches_local"])
  assert got["iterations"] == got["local_iterations"]
  # one GTC and one MST batch, both on the dp arm: no device-0 fallback
  assert got["schedules"] == ["dp"] and got["launches"] == 2
  assert got["live"] == 2 * size and got["inert"] == 2 * (rb - size)
  assert got["live"] + got["inert"] == 2 * rb
  for args in got["dispatch"]:
    assert args == {"schedule": "dp", "rb": rb, "live": size,
                    "chips_live": -(-size // (rb // 4))}
  assert len(got["dispatch"]) == 2


def test_dp_batch_of_one_feeds_the_estimator_its_whole_cost(served):
  """The inert slots of a dp batch do not dilute its seconds: a request
  alone in a batch of four slots records what it records on the local
  path alone."""
  dp, local = served["ewma"]["dp"], served["ewma"]["local"]
  assert dp[0] == "dp" and dp[2] == 3
  assert local[0] == "local"
  assert dp[1] == local[1] > 0


RINGS = ("minplus", "maxplus", "minmul", "maxmul", "minmax", "maxmin",
         "orand")


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("op", RINGS)
def test_inert_slot_is_its_own_fixpoint(op, backend):
  """An inert slot leaves the batched fixpoint after its first step
  unchanged, in every closure ring and on the Pallas kernel's skipped
  (live-K 0) path too, and the request beside it closes as it does
  alone."""
  import numpy as np

  from repro.core.closure import batched_leyzorek_closure
  from repro.serve_mmo import batching, closure_request
  from repro.serve_mmo.scheduler import request_bucket
  rng = np.random.default_rng(7)
  w = rng.uniform(0.1, 0.9, (11, 11)).astype(np.float32)
  w[rng.random((11, 11)) < 0.7] = np.inf if op.startswith("min") else 0.0
  if op == "maxplus":
    w = np.where(np.triu(np.ones((11, 11), bool), 1) & np.isfinite(w)
                 & (w > 0), w, -np.inf).astype(np.float32)
  req = closure_request(w > 0.5 if op == "orand" else w, op=op)
  key = request_bucket(req)
  adj, valid = batching.stack_batch(key, [req], inert=3)
  assert valid.tolist() == [11, 0, 0, 0]
  out, iters = batched_leyzorek_closure(adj, op=op, backend=backend,
                                        valid_n=valid)
  out, iters = np.asarray(out), np.asarray(iters)
  assert np.array_equal(out[1:], adj[1:]) and iters[1:].tolist() == [1] * 3
  alone, it_alone = batched_leyzorek_closure(adj[:1], op=op,
                                             backend=backend,
                                             valid_n=valid[:1])
  assert np.array_equal(out[0], np.asarray(alone)[0])
  assert iters[0] == int(np.asarray(it_alone)[0])


def _kind_requests(kind, rng):
  from repro.serve_mmo import knn_request, mmo_request
  if kind == "knn":
    return [knn_request(rng.standard_normal((5, 8)).astype("float32"),
                        rng.standard_normal((20 + i, 8)).astype("float32"),
                        k=3) for i in range(3)]
  has_c = kind == "mmo+c"
  return [mmo_request(rng.uniform(1, 9, (10, 12 - i)).astype("float32"),
                      rng.uniform(1, 9, (12 - i, 9)).astype("float32"),
                      rng.uniform(1, 9, (10, 9)).astype("float32")
                      if has_c else None, op="minplus") for i in range(3)]


@pytest.mark.parametrize("kind", ["mmo", "mmo+c", "knn"])
def test_dp_inert_slots_of_every_kind(kind):
  """Raw contractions and KNN batches take inert slots on the dp path too
  (3 requests → rb 4 on a one-device mesh) and answer as the local path
  does."""
  import numpy as np

  from repro.core.distributed import make_mesh
  from repro.serve_mmo import MMOEngine
  dp = MMOEngine(backend="xla", mesh=make_mesh((1, 1)), schedule="dp",
                 shard_flops=0.0)
  local = MMOEngine(backend="xla")
  reqs = _kind_requests(kind, np.random.default_rng(3))
  got = [dp.submit(r) for r in reqs]
  want = [local.submit(r) for r in reqs]
  dp.run_until_idle()
  local.run_until_idle()
  for g, w in zip(got, want):
    np.testing.assert_array_equal(g.result().value, w.result().value)
  st = dp.stats()
  assert {s for (_, _, s) in st.arms} == {"dp"}
  assert (st.dp_live_slots, st.dp_inert_slots) == (3, 1)
