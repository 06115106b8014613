"""Sharded bucket execution: mesh routing, dispatch mesh rows, and the
batched distributed schedules.

Quick tests run in-process on a trivial (1, 1) mesh (a real Mesh over the
single host device — the full sharded code path, no subprocess).  The
multi-device suite runs in a subprocess with 8 fake host devices, like
tests/test_distributed.py, so the main process keeps seeing 1 device.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.apps import graphs, solvers
from repro.core.distributed import make_mesh
from repro.serve_mmo import MMOEngine, apsp_request, mmo_request
from repro.serve_mmo.scheduler import request_bucket
from repro.tuning import (CostTable, prior_seconds, resolve,
                          sharded_prior_seconds)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _mesh11():
  return make_mesh((1, 1))


# ---------------------------------------------------------------------------
# sharded roofline prior + dispatch mesh rows (host-side, no devices needed)
# ---------------------------------------------------------------------------


def test_ring_traffic_bytes_model():
  from repro.roofline.collectives import ring_traffic_bytes
  assert ring_traffic_bytes("all-reduce", 100.0, 4) == pytest.approx(150.0)
  assert ring_traffic_bytes("all-gather", 100.0, 4) == pytest.approx(75.0)
  assert ring_traffic_bytes("collective-permute", 100.0, 4) == 100.0
  with pytest.raises(ValueError):
    ring_traffic_bytes("gossip", 1.0, 2)


@pytest.mark.parametrize("schedule", ["dp", "kspan", "summa", "ring"])
def test_sharded_prior_finite_and_positive(schedule):
  s = sharded_prior_seconds("minplus", (256, 256, 256), "float32", schedule,
                            (2, 4))
  assert 0.0 < s < 1.0
  with pytest.raises(ValueError):
    sharded_prior_seconds("minplus", (256,) * 3, "float32", "nope", (2, 4))


def test_prior_crossover_small_local_big_sharded():
  """The model's whole point: collectives lose on small contractions and win
  on big ones (VPU-bound minplus at 512³ vs 16³ on the v5e constants)."""
  small = resolve("minplus", 16, 16, 16, "float32", table=CostTable(),
                  mesh_shape=(2, 4))
  assert small.backend in ("xla", "vector", "pallas")
  big = resolve("minplus", 512, 512, 512, "float32", table=CostTable(),
                mesh_shape=(2, 4))
  assert big.backend in ("kspan", "summa", "ring")
  assert big.cfg == (2, 4)
  # and the sharded prior really is below the local prior at the big point
  assert (sharded_prior_seconds("minplus", (512,) * 3, "float32", big.backend,
                                (2, 4))
          < prior_seconds("minplus", (512,) * 3, "float32", "xla"))


def test_measured_mesh_row_beats_unmeasured_prior_arm():
  """A measured sharded row must win over a sibling arm's idealized prior,
  and a measured sharded row competes directly with a measured local row."""
  t = CostTable(device="test")
  t.record("minplus", (16, 16, 16), "float32", "xla", (512,), 1.0)
  t.record("minplus", (16, 16, 16), "float32", "kspan", (2, 4), 1e-6)
  d = resolve("minplus", 16, 16, 16, "float32", table=t, mesh_shape=(2, 4))
  assert (d.backend, d.cfg, d.source) == ("kspan", (2, 4), "measured")
  # restricting the schedules hides the kspan row → prior-vs-prior → local
  d2 = resolve("minplus", 16, 16, 16, "float32", table=t, mesh_shape=(2, 4),
               schedules=("summa",))
  assert d2.backend == "xla"
  with pytest.raises(ValueError):
    resolve("minplus", 16, 16, 16, "float32", table=t, mesh_shape=(2, 4),
            schedules=("gossip",))


def test_resolve_without_mesh_unchanged():
  t = CostTable(device="test")
  t.record("minplus", (16, 16, 16), "float32", "vector", (128,), 1e-6)
  assert resolve("minplus", 16, 16, 16, "float32", table=t).backend == "vector"


# ---------------------------------------------------------------------------
# engine routing (trivial (1, 1) mesh — full sharded path on one device)
# ---------------------------------------------------------------------------


def test_schedule_fits_divisibility():
  from repro.core.distributed import schedule_fits
  mesh = _mesh11()
  assert schedule_fits("summa", 16, 16, 16, mesh)
  # dp has no problem-axis constraint (request divisibility is the engine's
  # per-batch check)
  assert schedule_fits("dp", 17, 23, 3, mesh)
  assert not schedule_fits("nope", 16, 16, 16, mesh)


def test_engine_requires_mesh_for_pinned_schedule():
  with pytest.raises(ValueError, match="needs a mesh"):
    MMOEngine(schedule="summa")
  # a typo'd schedule must fail loudly, not silently serve local
  with pytest.raises(ValueError, match="unknown schedule"):
    MMOEngine(schedule="suma")
  with pytest.raises(ValueError, match="unknown schedule"):
    MMOEngine(mesh=_mesh11(), schedule="suma")


def test_router_threshold_and_pinned_schedule():
  mesh = _mesh11()
  # below the cutoff → local even with a pinned schedule
  eng = MMOEngine(backend="xla", mesh=mesh, schedule="summa",
                  shard_flops=1e12)
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)))
  assert eng.resolve_schedule(key) == "local"
  # above the cutoff → the pinned schedule
  eng2 = MMOEngine(backend="xla", mesh=mesh, schedule="summa", shard_flops=0.0)
  assert eng2.resolve_schedule(key) == "summa"
  # closure buckets never route to kspan/ring (iterate must stay in place)
  eng3 = MMOEngine(backend="xla", mesh=mesh, schedule="ring", shard_flops=0.0)
  assert eng3.resolve_schedule(key) == "local"
  # mmo buckets may
  mkey = request_bucket(mmo_request(np.zeros((12, 12), np.float32),
                                    np.zeros((12, 12), np.float32),
                                    op="minplus"))
  assert eng3.resolve_schedule(mkey) == "ring"
  # dp (independent per-device fixpoints) is allowed for closures
  eng4 = MMOEngine(backend="xla", mesh=mesh, schedule="dp", shard_flops=0.0)
  assert eng4.resolve_schedule(key) == "dp"
  # ... and the placement never falls back on a 1-device mesh (rb % 1 == 0)
  assert eng4.resolve_placement(key, 3)[2] == "dp"


def test_sharded_and_local_executables_never_collide():
  """The (schedule, mesh) placement is part of the executable-cache key."""
  eng = MMOEngine(backend="xla", mesh=_mesh11(), schedule="summa",
                  shard_flops=0.0)
  key = request_bucket(apsp_request(graphs.weighted_digraph(10, 0.3, seed=0)))
  local_key = eng._exec_key(key, 1, "xla", (), "local")
  shard_key = eng._exec_key(key, 1, "xla", (), "summa")
  assert local_key != shard_key
  assert local_key[-1] is None and shard_key[-1] == (("data", 1), ("model", 1))


def test_engine_sharded_path_matches_solver_on_trivial_mesh():
  """End-to-end through stack→compile→execute→split with schedule='summa'
  on a (1, 1) mesh: same results as the direct solvers, zero retraces on
  repeat traffic, and the memoized placement is sharded."""
  eng = MMOEngine(backend="xla", mesh=_mesh11(), schedule="summa",
                  shard_flops=0.0, max_batch=4)

  def traffic():
    futs = [eng.submit(apsp_request(graphs.weighted_digraph(n, 0.3, seed=n)))
            for n in (9, 11, 13)]
    eng.run_until_idle()
    return futs

  futs = traffic()
  assert set(eng._schedules.values()) == {"summa"}
  for fut, n in zip(futs, (9, 11, 13)):
    ref, _ = solvers.apsp(graphs.weighted_digraph(n, 0.3, seed=n))
    np.testing.assert_allclose(fut.result().value, np.asarray(ref), atol=1e-5)
  misses = eng.cache.misses
  assert misses > 0
  futs2 = traffic()  # steady state: sharded executables replay
  assert eng.cache.misses == misses
  assert all(f.done() for f in futs2)


def test_prewarm_sharded_matches_step():
  eng = MMOEngine(backend="xla", mesh=_mesh11(), schedule="summa",
                  shard_flops=0.0, max_batch=2)
  eng.prewarm([apsp_request(graphs.weighted_digraph(10, 0.3, seed=0))])
  misses = eng.cache.misses
  eng.submit(apsp_request(graphs.weighted_digraph(12, 0.3, seed=1)))
  eng.run_until_idle()
  assert eng.cache.misses == misses


# ---------------------------------------------------------------------------
# multi-device suite (subprocess, 8 fake host devices)
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import semiring as sr_mod
    from repro.core import mmo_batched, mmo_reference
    from repro.core import pad_adjacency, prepare_adjacency
    from repro.core.closure import batched_leyzorek_closure
    from repro.core.distributed import (make_mesh, mmo_kspan_batched,
                                        ring_mmo_batched,
                                        sharded_closure_batched,
                                        summa_mmo_batched)

    mesh = make_mesh((2, 4))
    rng = np.random.default_rng(0)
    R, M, K, N = 3, 16, 32, 24

    # summa gathers K-panels over BOTH axes — a K that doesn't divide the
    # mesh must be rejected by the fit check, not crash inside shard_map
    from repro.core.distributed import schedule_fits
    assert schedule_fits("summa", 16, 32, 16, mesh)
    assert not schedule_fits("summa", 16, 2, 16, mesh)

    # --- 1. every registered op: batched schedules == local batched path ---
    # min/max/or rings are bit-identical (⊕ is order-independent); the two
    # (+)-reductions see cross-device summation order, so tight allclose.
    for op in sr_mod.ALL_OPS:
        sr = sr_mod.get(op)
        a = rng.standard_normal((R, M, K)).astype(np.float32)
        b = rng.standard_normal((R, K, N)).astype(np.float32)
        c = rng.standard_normal((R, M, N)).astype(np.float32)
        if op in ("minmul", "maxmul"):
            a, b = np.abs(np.tanh(a)), np.abs(np.tanh(b))
        if sr.boolean:
            a, b, c = a > 0.3, b > 0.3, c > 0.8
        kv = np.asarray([K, K - 8, K - 16], np.int32)
        pa, pb = sr_mod.contraction_pads(op)
        if sr.boolean:
            pa = pb = False
        for i, k in enumerate(kv):  # honor the k_valid contract
            a[i, :, k:] = pa
            b[i, k:, :] = pb
        a, b, c = jnp.asarray(a), jnp.asarray(b), jnp.asarray(c)
        kvj = jnp.asarray(kv)
        local = np.asarray(mmo_batched(a, b, c, op=op, backend="xla",
                                       k_valid=kvj))
        for fn in (mmo_kspan_batched, summa_mmo_batched, ring_mmo_batched):
            got = np.asarray(fn(a, b, c, op=op, mesh=mesh, k_valid=kvj))
            if sr.oplus in (jnp.minimum, jnp.maximum, jnp.logical_or):
                assert np.array_equal(got, local), (op, fn.__name__)
            else:
                np.testing.assert_allclose(got, local, atol=1e-4,
                                           err_msg=f"{op} {fn.__name__}")
            nokv = np.asarray(fn(a, b, c, op=op, mesh=mesh))
            np.testing.assert_allclose(nokv, np.asarray(
                mmo_reference(a, b, c, op=op)), atol=1e-4)
    print("SCHEDULES_ALLOPS_OK")

    # --- 1b. dp: request-sharded contraction == local, divisibility check --
    from repro.core.distributed import mmo_dp_batched
    a = rng.standard_normal((8, M, K)).astype(np.float32)
    b = rng.standard_normal((8, K, N)).astype(np.float32)
    kv = np.asarray([K - 8 * (i % 3) for i in range(8)], np.int32)
    pa, pb = sr_mod.contraction_pads("minplus")
    for i, k in enumerate(kv):
        a[i, :, k:] = pa
        b[i, k:, :] = pb
    a, b, kvj = jnp.asarray(a), jnp.asarray(b), jnp.asarray(kv)
    got = np.asarray(mmo_dp_batched(a, b, op="minplus", mesh=mesh,
                                    k_valid=kvj))
    want = np.asarray(mmo_batched(a, b, op="minplus", backend="xla",
                                  k_valid=kvj))
    assert np.array_equal(got, want)
    try:
        mmo_dp_batched(a[:3], b[:3], op="minplus", mesh=mesh)
        raise SystemExit("dp accepted a request axis that does not divide")
    except ValueError:
        pass
    print("DP_MMO_OK")

    # --- 2. sharded batched closure == local batched closure -------------
    sizes = [20, 26, 32]
    nb = 32
    ws = []
    for n in sizes:
        w = rng.uniform(1, 10, (n, n)).astype(np.float32)
        w = np.where(rng.random((n, n)) < 0.6, np.inf, w)
        ws.append(np.asarray(prepare_adjacency(jnp.asarray(w), op="minplus")))
    stack = jnp.stack([pad_adjacency(w, nb, op="minplus") for w in ws])
    valid = jnp.asarray(sizes, jnp.int32)
    loc, it_l = batched_leyzorek_closure(stack, op="minplus", backend="xla",
                                         valid_n=valid)
    sh, it_s = sharded_closure_batched(stack, op="minplus", mesh=mesh,
                                       valid_n=valid)
    assert np.array_equal(np.asarray(sh), np.asarray(loc))
    assert np.array_equal(np.asarray(it_s), np.asarray(it_l))

    # dp closure: one independent fixpoint per device, same results and
    # same per-request iteration counts as the coupled local fixpoint
    sizes8 = [20, 26, 32, 24, 30, 22, 28, 32]
    ws8 = []
    for i, n in enumerate(sizes8):
        w = rng.uniform(1, 10, (n, n)).astype(np.float32)
        w = np.where(rng.random((n, n)) < 0.6, np.inf, w)
        ws8.append(np.asarray(prepare_adjacency(jnp.asarray(w),
                                                op="minplus")))
    stack8 = jnp.stack([pad_adjacency(w, nb, op="minplus") for w in ws8])
    valid8 = jnp.asarray(sizes8, jnp.int32)
    loc8, it_l8 = batched_leyzorek_closure(stack8, op="minplus",
                                           backend="xla", valid_n=valid8)
    dp8, it_d8 = sharded_closure_batched(stack8, op="minplus", mesh=mesh,
                                         schedule="dp", valid_n=valid8)
    assert np.array_equal(np.asarray(dp8), np.asarray(loc8))
    assert np.array_equal(np.asarray(it_d8), np.asarray(it_l8))
    print("SHARDED_CLOSURE_OK")

    # --- 3. engine: threshold splits placement; results match solvers ----
    from repro.apps import graphs, solvers
    from repro.serve_mmo import MMOEngine, apsp_request
    # 16-bucket (2·16³ ≈ 8e3 flops) stays local, 64-bucket (5e5) shards
    eng = MMOEngine(backend="xla", mesh=mesh, schedule="summa",
                    shard_flops=1e5, max_batch=4)
    small = {n: graphs.weighted_digraph(n, 0.3, seed=n) for n in (9, 12)}
    big = {n: graphs.weighted_digraph(n, 0.25, seed=n) for n in (49, 60)}
    futs = {n: eng.submit(apsp_request(w))
            for n, w in {**small, **big}.items()}
    eng.run_until_idle()
    scheds = {k.shape[0]: s for k, s in eng._schedules.items()}
    assert scheds == {16: "local", 64: "summa"}, scheds
    for n, w in {**small, **big}.items():
        ref, _ = solvers.apsp(w)
        np.testing.assert_allclose(futs[n].result().value, np.asarray(ref),
                                   atol=1e-5)
    print("ENGINE_ROUTING_OK")

    # --- 4. prewarm → steady-state sharded traffic: zero retraces --------
    eng2 = MMOEngine(backend="xla", mesh=mesh, schedule="summa",
                     shard_flops=1e5, max_batch=4)
    sample = [apsp_request(graphs.weighted_digraph(n, 0.25, seed=0))
              for n in (50, 10)]
    eng2.prewarm(sample)
    misses = eng2.cache.misses
    for i in range(6):
        eng2.submit(apsp_request(
            graphs.weighted_digraph(45 + i, 0.25, seed=i)))
        eng2.submit(apsp_request(graphs.weighted_digraph(9 + i, 0.3, seed=i)))
    eng2.run_until_idle()
    assert eng2.cache.misses == misses, (eng2.cache.misses, misses)
    print("PREWARM_ZERO_RETRACE_OK")

    # --- 5. dp engine: full batches shard, partial batches fall back ------
    eng3 = MMOEngine(backend="xla", mesh=mesh, schedule="dp",
                     shard_flops=1e5, max_batch=8)
    ws = {n: graphs.weighted_digraph(n, 0.25, seed=n) for n in range(49, 57)}
    futs3 = {n: eng3.submit(apsp_request(w)) for n, w in ws.items()}
    eng3.run_until_idle()
    assert set(eng3._schedules.values()) == {"dp"}
    for n, w in ws.items():
        ref, _ = solvers.apsp(w)
        np.testing.assert_allclose(futs3[n].result().value, np.asarray(ref),
                                   atol=1e-5)
    # 3 requests pad to rb=4, which does not divide the 8 devices: the
    # memoized bucket schedule stays dp but the executed placement is local
    eng4 = MMOEngine(backend="xla", mesh=mesh, schedule="dp",
                     shard_flops=1e5, max_batch=8)
    futs4 = [eng4.submit(apsp_request(
        graphs.weighted_digraph(50 + i, 0.25, seed=i))) for i in range(3)]
    eng4.run_until_idle()
    for i, f in enumerate(futs4):
        ref, _ = solvers.apsp(graphs.weighted_digraph(50 + i, 0.25, seed=i))
        np.testing.assert_allclose(f.result().value, np.asarray(ref),
                                   atol=1e-5)
    (key4,) = eng4._schedules
    assert eng4._schedules[key4] == "dp"
    assert eng4.resolve_placement(key4, 4)[2] == "local"
    assert eng4.resolve_placement(key4, 8)[2] == "dp"
    print("DP_ENGINE_OK")
""")


@pytest.mark.slow
def test_sharded_serving_suite():
  env = dict(os.environ, PYTHONPATH=SRC)
  r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                     text=True, env=env, timeout=900)
  assert r.returncode == 0, r.stderr[-3000:]
  for marker in ("SCHEDULES_ALLOPS_OK", "DP_MMO_OK", "SHARDED_CLOSURE_OK",
                 "ENGINE_ROUTING_OK", "PREWARM_ZERO_RETRACE_OK",
                 "DP_ENGINE_OK"):
    assert marker in r.stdout
