"""Parity suite for the fused Pallas closure megakernel.

The megakernel's contract is *bit-identity* with the per-iteration
``_batched_fixpoint`` path — outputs AND per-request iteration counts —
for every ring with a ⊗-identity, under ragged ``valid_n``, mixed
convergence speeds, and chunk lengths that do not divide the trip count.
Everything here runs the kernel in interpret mode (CPU CI); on TPU the
same calls compile to the real fused program.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from fixtures import closure_corpus as corpus
from fixtures.closure_corpus import IDENTITY_RINGS, line_graph

from repro.core import closure as cl_mod


def _rand_adj(op, n, r, seed=0):
  return jnp.asarray(corpus.rand_adj(op, n, r, seed=seed))


def _assert_parity(op, algorithm, adj, *, valid_n=None, g=3, max_iters=None):
  """Reference vs megakernel: outputs and iteration counts bit-identical."""
  solver = (cl_mod.batched_leyzorek_closure if algorithm == "leyzorek"
            else cl_mod.batched_bellman_ford_closure)
  ref_out, ref_it = solver(adj, op=op, backend="xla", valid_n=valid_n,
                           max_iters=max_iters)
  mk_out, mk_it = solver(adj, op=op, fixpoint_backend="megakernel",
                         megakernel_g=g, valid_n=valid_n,
                         max_iters=max_iters, interpret=True)
  np.testing.assert_array_equal(np.asarray(mk_out), np.asarray(ref_out))
  np.testing.assert_array_equal(np.asarray(mk_it), np.asarray(ref_it))
  return np.asarray(ref_it)


@pytest.mark.parametrize("algorithm", ("leyzorek", "bellman_ford"))
@pytest.mark.parametrize("op", IDENTITY_RINGS)
def test_parity_all_rings(op, algorithm):
  adj = _rand_adj(op, 12, 2, seed=hash(op) % 1000)
  _assert_parity(op, algorithm, adj, g=3)


@pytest.mark.parametrize("case", corpus.CORPUS, ids=corpus.CASE_IDS)
def test_corpus_parity_megakernel(case):
  """The shared adversarial corpus, megakernel vs reference: every case the
  serving paths are pinned on must hold through the fused kernel too."""
  solver = (cl_mod.batched_leyzorek_closure if case.algorithm == "leyzorek"
            else cl_mod.batched_bellman_ford_closure)
  stack, valid = corpus.stacked(case)
  ref_out, ref_it = corpus.reference(case)
  mk_out, mk_it = solver(stack, op=case.op, fixpoint_backend="megakernel",
                         megakernel_g=3, valid_n=valid,
                         max_iters=case.max_iters, interpret=True)
  np.testing.assert_array_equal(np.asarray(mk_out), ref_out)
  np.testing.assert_array_equal(np.asarray(mk_it), ref_it)


def _line_graph(n, seed=0):
  return line_graph(n, seed=seed)


def test_parity_ragged_valid_n():
  """Mixed true sizes inside one padded bucket: the kernel's scalar-
  prefetched per-request live-n must reproduce the reference's masked-K
  semantics exactly."""
  nb = 16
  sizes = (9, 11, 16)
  prepared = [cl_mod.prepare_adjacency(jnp.asarray(_line_graph(n, seed=n)),
                                       op="minplus") for n in sizes]
  stack = jnp.stack([jnp.asarray(cl_mod.pad_adjacency(p, nb, op="minplus"))
                     for p in prepared])
  valid = jnp.asarray(sizes, jnp.int32)
  for algorithm in ("leyzorek", "bellman_ford"):
    _assert_parity("minplus", algorithm, stack, valid_n=valid, g=4)


def test_parity_converged_slot_freezes():
  """An already-closed request co-batched with a straggler: both paths must
  stop its counter at 1 (the probe iteration that detects no change) while
  the straggler keeps iterating.  Unit edge weights keep every path sum
  exactly representable, so the closure is a bit-stable fixpoint (random
  float weights re-associate by one ulp under a different hop split)."""
  n = 10
  w = np.full((n, n), np.inf, np.float32)
  w[np.arange(n - 1), np.arange(1, n)] = 1.0
  line = cl_mod.prepare_adjacency(jnp.asarray(w), op="minplus")
  closed, _ = cl_mod.batched_bellman_ford_closure(line[None], op="minplus",
                                                  backend="xla")
  stack = jnp.concatenate([closed, line[None]])
  it = _assert_parity("minplus", "bellman_ford", stack, g=4)
  assert it[0] == 1
  assert it[1] > it[0]


@pytest.mark.parametrize("g", (1, 3, 4, 7, 64))
def test_parity_g_not_dividing_trip_count(g):
  """A line graph's Bellman-Ford runs ~n iterations; sweep chunk lengths
  that undershoot, straddle, and overshoot it — the per-chunk live budget
  must keep the max_iters cap and the counters exact."""
  n = 10
  adj = cl_mod.prepare_adjacency(jnp.asarray(_line_graph(n)),
                                 op="minplus")[None]
  it = _assert_parity("minplus", "bellman_ford", adj, g=g)
  # diameter n−1: the last change lands on step n−2, the no-change probe
  # that freezes the request is step n−1 — one short of the max_iters cap
  assert it[0] == n - 1


def test_parity_max_iters_cap():
  """max_iters smaller than the natural trip count: both paths stop at the
  cap, even when G does not divide it."""
  n = 12
  adj = cl_mod.prepare_adjacency(jnp.asarray(_line_graph(n)),
                                 op="minplus")[None]
  it = _assert_parity("minplus", "bellman_ford", adj, g=5, max_iters=7)
  assert it[0] == 7


def test_nan_aware_changed_regression():
  """A NaN edge weight used to spin the fixpoint to max_iters: NaN != NaN
  made ``_changed`` report progress forever.  After the fix, NaN cells
  compare equal to themselves and the request converges normally — and the
  megakernel's in-kernel reduction agrees bit-for-bit."""
  n = 8
  w = _line_graph(n)
  w[0, 1] = np.nan
  adj = cl_mod.prepare_adjacency(jnp.asarray(w), op="minplus")[None]
  ref_out, ref_it = cl_mod.batched_bellman_ford_closure(adj, op="minplus",
                                                        backend="xla")
  assert int(ref_it[0]) < n, "NaN request must converge before the cap"
  assert np.isnan(np.asarray(ref_out)).any()
  _assert_parity("minplus", "bellman_ford", adj, g=3)


def test_backend_alias_routes_to_megakernel():
  """backend='megakernel' (the cost-table spelling) and
  fixpoint_backend='megakernel' are the same arm."""
  adj = _rand_adj("minplus", 8, 2, seed=3)
  a_out, a_it = cl_mod.batched_leyzorek_closure(
      adj, op="minplus", backend="megakernel", interpret=True)
  b_out, b_it = cl_mod.batched_leyzorek_closure(
      adj, op="minplus", fixpoint_backend="megakernel", interpret=True)
  np.testing.assert_array_equal(np.asarray(a_out), np.asarray(b_out))
  np.testing.assert_array_equal(np.asarray(a_it), np.asarray(b_it))


def test_addnorm_refused():
  adj = jnp.zeros((1, 8, 8), jnp.float32)
  with pytest.raises(ValueError, match="⊗-identity"):
    cl_mod.batched_leyzorek_closure(adj, op="addnorm",
                                    fixpoint_backend="megakernel",
                                    interpret=True)


def test_unknown_fixpoint_backend_refused():
  adj = jnp.zeros((1, 8, 8), jnp.float32)
  with pytest.raises(ValueError, match="fixpoint_backend"):
    cl_mod.batched_leyzorek_closure(adj, op="minplus",
                                    fixpoint_backend="nope")


def test_mmo_refuses_megakernel_backend():
  """A single contraction cannot run a fused fixpoint — mmo points callers
  at the closure entry points instead of silently falling back."""
  from repro.core.mmo import mmo
  a = jnp.zeros((8, 8), jnp.float32)
  with pytest.raises(ValueError, match="megakernel"):
    mmo(a, a, op="minplus", backend="megakernel")


# ---------------------------------------------------------------------------
# dispatch containment: the megakernel arm competes only where a closure-
# owning dispatcher opts in
# ---------------------------------------------------------------------------


def test_cost_table_prior_amortizes_bandwidth():
  """For a bandwidth-bound point the fused arm's prior divides the HBM term
  by G, so at G=8 it must undercut the per-iteration pallas prior."""
  from repro.tuning import prior_seconds
  shape = (64, 64, 64)
  pal = prior_seconds("minplus", shape, "float32", "pallas", (128,))
  mk8 = prior_seconds("minplus", shape, "float32", "megakernel", (8,))
  assert mk8 < pal


def test_best_default_order_excludes_megakernel():
  from repro.tuning import CLOSURE_BACKENDS, CostTable
  table = CostTable(device="test")
  shape = (16, 16, 16)
  table.record("minplus", shape, "float32", "xla", (), 1.0)
  table.record("minplus", shape, "float32", "megakernel", (8,), 1e-9)
  d = table.best("minplus", shape, "float32")
  assert d.backend == "xla", "default pool must never surface megakernel"
  d = table.best("minplus", shape, "float32", backends=CLOSURE_BACKENDS)
  assert d.backend == "megakernel" and d.cfg == (8,)


def test_engine_routes_closure_bucket_to_megakernel():
  """End to end: a cost table that says the fused arm wins a closure bucket
  → placement.resolve_backend picks it for closure only → the batch executes through
  the megakernel (interpret mode) and returns the exact reference APSP."""
  from repro.serve_mmo import MMOEngine, apsp_request
  from repro.tuning import CostTable
  table = CostTable(device="test")
  nb = (16, 16, 16)
  table.record("minplus", nb, "float32", "xla", (), 1.0, source="measured")
  table.record("minplus", nb, "float32", "megakernel", (4,), 1e-9,
               source="measured")
  eng = MMOEngine(backend="auto", max_batch=4, cost_table=table)
  w = _line_graph(12, seed=5)
  fut = eng.submit(apsp_request(w))
  eng.run_until_idle()
  key = next(iter(eng.placement._decisions))
  assert eng.placement.resolve_backend(key) == ("megakernel", (4,))
  ref, ref_it = cl_mod.batched_leyzorek_closure(
      cl_mod.prepare_adjacency(jnp.asarray(w), op="minplus")[None],
      op="minplus", backend="xla")
  got = fut.result()
  np.testing.assert_array_equal(got.value, np.asarray(ref[0]))
  assert got.extras["iterations"] == int(ref_it[0])


def test_engine_mmo_bucket_never_sees_megakernel():
  """The same winning row must NOT leak into a plain contraction bucket:
  its pool is the per-contraction backends."""
  from repro.serve_mmo import MMOEngine, mmo_request
  from repro.tuning import CostTable
  table = CostTable(device="test")
  nb = (16, 16, 16)
  table.record("minplus", nb, "float32", "xla", (), 1.0, source="measured")
  table.record("minplus", nb, "float32", "megakernel", (4,), 1e-9,
               source="measured")
  eng = MMOEngine(backend="auto", max_batch=4, cost_table=table)
  rng = np.random.default_rng(0)
  a = rng.standard_normal((12, 12)).astype(np.float32)
  fut = eng.submit(mmo_request(a, a, op="minplus"))
  eng.run_until_idle()
  assert all(b != "megakernel" for b, _ in eng.placement._decisions.values())
  assert fut.done()


@pytest.fixture
def small_cap(monkeypatch):
  """Shrink the megakernel's size cap to 16 so a CPU-sized closure sits
  above it (the engine and dispatch read the cap at routing time)."""
  from repro.kernels import closure_megakernel as mk
  monkeypatch.setattr(mk, "MAX_N", 16)
  return 16


def test_megakernel_refuses_padded_n_above_cap(small_cap):
  adj = _rand_adj("minplus", 20, 1)
  with pytest.raises(ValueError, match="megakernel serves padded n"):
    cl_mod.batched_leyzorek_closure(adj, op="minplus",
                                    fixpoint_backend="megakernel",
                                    interpret=True)


def test_engine_never_routes_megakernel_above_cap(small_cap):
  """A cost table where the fused arm wins every closure bucket: auto keeps
  it for the bucket at the cap and drops it above; a megakernel-pinned
  engine serves the above-cap bucket on the per-iteration Pallas path."""
  from repro.serve_mmo import MMOEngine, apsp_request
  from repro.serve_mmo.scheduler import request_bucket
  from repro.tuning import CostTable
  table = CostTable(device="test")
  for nb in (16, 32):
    table.record("minplus", (nb,) * 3, "float32", "xla", (), 1.0,
                 source="measured")
    table.record("minplus", (nb,) * 3, "float32", "megakernel", (4,), 1e-9,
                 source="measured")
  at_cap = request_bucket(apsp_request(_line_graph(12, seed=1)))
  above = request_bucket(apsp_request(_line_graph(20, seed=2)))
  auto = MMOEngine(backend="auto", cost_table=table)
  assert auto.placement.resolve_backend(at_cap)[0] == "megakernel"
  assert auto.placement.resolve_backend(above)[0] == "xla"
  pinned = MMOEngine(backend="megakernel")
  assert pinned.placement.resolve_backend(at_cap)[0] == "megakernel"
  assert pinned.placement.resolve_backend(above)[0] == "pallas"


def test_arena_serves_closures_above_cap_on_batch_path(small_cap):
  """Arena mode admits the bucket at the cap into slots and serves the one
  above it on the batch path — same results as batch mode, counted in
  stats().over_cap, each bucket on its own arm."""
  from repro.serve_mmo import MMOEngine, apsp_request
  graphs = [_line_graph(12, seed=3), _line_graph(20, seed=4)]
  arena = MMOEngine(backend="pallas", mode="arena")
  batch = MMOEngine(backend="pallas")
  got = [arena.submit(apsp_request(w)) for w in graphs]
  want = [batch.submit(apsp_request(w)) for w in graphs]
  arena.run_until_idle()
  batch.run_until_idle()
  for g, w in zip(got, want):
    np.testing.assert_array_equal(g.result().value, w.result().value)
    assert g.result().extras["iterations"] == w.result().extras["iterations"]
  st = arena.stats()
  assert st.over_cap == 1
  assert sorted(b for _, b, _ in st.arms) == ["arena", "pallas"]
  assert "over_cap=1" in st.summary()
