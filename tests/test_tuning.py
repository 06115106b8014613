"""Cost-table dispatch: schema, precedence, equivalence, engine integration."""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import mmo, mmo_reference
from repro.tuning import (CostTable, SCHEMA_VERSION, prior_seconds, resolve,
                          signature, tune, use_cost_table)
from repro.tuning.cost_table import bucket_shape

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# table: signatures, JSON round-trip, precedence
# ---------------------------------------------------------------------------


def test_signature_buckets_raw_shapes():
  """Raw shapes that land in the same bucket share one table entry — the
  key is the bucket signature, not the raw shape (DESIGN.md §Dispatch)."""
  s1 = signature("minplus", (9, 11, 13), "float32", "vector", (128,))
  s2 = signature("minplus", (16, 16, 16), "float32", "vector", (128,))
  assert s1 == s2
  assert bucket_shape((9, 11, 13)) == (16, 16, 16)
  assert signature("minplus", (17, 16, 16), "float32", "vector",
                   (128,)) != s1  # 17 buckets to 32


def test_json_round_trip(tmp_path):
  t = CostTable(device="cpu:test")
  t.record("mma", (64, 64, 64), "float32", "xla", (512,), 1.5e-4)
  t.record("minplus", (9, 11, 13), "float32", "vector", (128,), 2.5e-4)
  t.record("orand", (16, 16, 16), "bool", "pallas", (128, 128, 128), 3e-3,
           source="prior")
  path = tmp_path / "table.json"
  t.save(path)
  back = CostTable.load(path)
  assert back.device == t.device and back.version == SCHEMA_VERSION
  assert back.entries == t.entries
  # the on-disk form is versioned, sorted JSON
  doc = json.loads(path.read_text())
  assert doc["schema_version"] == SCHEMA_VERSION
  assert list(doc["entries"]) == sorted(doc["entries"])


def test_from_json_rejects_wrong_schema():
  with pytest.raises(ValueError, match="schema_version"):
    CostTable.from_json(json.dumps({"schema_version": 999, "entries": {}}))
  bad = {"schema_version": SCHEMA_VERSION,
         "entries": {"mma|64x64x64|float32|xla|-":
                     {"seconds": -1.0, "source": "measured"}}}
  with pytest.raises(ValueError, match="seconds"):
    CostTable.from_json(json.dumps(bad))


def test_measured_beats_prior_precedence():
  t = CostTable()
  point = ("minplus", (16, 16, 16), "float32", "vector", (128,))
  assert t.record(*point, 1.0, source="prior")
  assert t.record(*point, 2.0, source="measured")  # measured overwrites prior
  assert t.lookup(*point).seconds == 2.0
  assert not t.record(*point, 0.5, source="prior")  # prior can't claw back
  assert t.lookup(*point).source == "measured"
  assert t.lookup(*point).seconds == 2.0
  assert t.record(*point, 3.0, source="measured")  # re-measure always wins
  assert t.lookup(*point).seconds == 3.0


def test_best_is_argmin_with_deterministic_ties():
  t = CostTable()
  t.record("minplus", (16, 16, 16), "float32", "xla", (512,), 2e-4)
  t.record("minplus", (16, 16, 16), "float32", "vector", (128,), 1e-4)
  t.record("minplus", (16, 16, 16), "float32", "vector", (512,), 3e-4)
  d = t.best("minplus", (10, 12, 14), "float32")  # raw shape → same bucket
  assert (d.backend, d.cfg, d.seconds) == ("vector", (128,), 1e-4)
  # restricting candidates honors the restriction
  d = t.best("minplus", (16, 16, 16), "float32", backends=("xla",))
  assert d.backend == "xla"
  # nothing known for this bucket → None → resolve falls back to 'xla'
  assert t.best("minplus", (64, 64, 64), "float32") is None
  assert resolve("minplus", 64, 64, 64, "float32", table=t).backend == "xla"


def test_best_tie_ignores_insertion_order(tmp_path):
  """Two configs of one backend at equal seconds: the same choice whichever
  was recorded first, and after a save → load round trip."""
  point = ("addnorm", (8, 8, 8), "float32", "xla")
  picks = []
  for cfgs in (((), (512,)), ((512,), ())):
    t = CostTable()
    for cfg in cfgs:
      t.record(*point, cfg, 1e-4)
    path = tmp_path / "t.json"
    t.save(str(path))
    for table in (t, CostTable.load(str(path))):
      picks.append(table.best("addnorm", (8, 8, 8), "float32").cfg)
  assert len(set(picks)) == 1, picks


def test_prior_prefers_mxu_rewrites():
  """The analytic prior knows which ops ride the MXU per backend."""
  fast = prior_seconds("mma", (256, 256, 256), "float32", "xla")
  slow = prior_seconds("mma", (256, 256, 256), "float32", "vector")
  assert fast < slow  # matmul rewrite vs VPU broadcast-reduce
  assert prior_seconds("minplus", (256, 256, 256), "float32", "xla") == \
      prior_seconds("minplus", (256, 256, 256), "float32", "vector")


# ---------------------------------------------------------------------------
# dry-prior tuner sweep + dispatch equivalence: whatever the table picks,
# the result must match the reference oracle
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def prior_table():
  return tune(dry_prior=True, shapes=((16, 16, 16), (8, 16, 8)))


def test_dry_prior_tune_round_trips(prior_table, tmp_path):
  assert len(prior_table) > 0
  assert prior_table.counts()["measured"] == 0
  path = tmp_path / "prior.json"
  prior_table.save(path)
  assert len(CostTable.load(path)) == len(prior_table)


def test_dry_prior_mesh_sweep_records_schedule_rows(tmp_path):
  """The --mesh sweep fills every distributed-schedule arm with the sharded
  roofline prior (no devices needed), keyed on the mesh shape — the rows
  dispatch.resolve(mesh_shape=…) reads for sharded serving."""
  from repro.tuning import SCHEDULE_ARMS
  from repro.tuning.autotune import tune_mesh
  dims = (2, 4)
  table = tune_mesh(dims=dims, ops=("minplus", "orand"),
                    shapes=((64, 64, 64),), dry_prior=True)
  for op, dtype in (("minplus", "float32"), ("orand", "bool")):
    for sched in SCHEDULE_ARMS:
      entry = table.lookup(op, (64, 64, 64), dtype, sched, dims)
      assert entry is not None and entry.source == "prior", (op, sched)
  # round-trips like any other table, and a measured row later wins
  path = tmp_path / "mesh.json"
  table.save(path)
  loaded = CostTable.load(path)
  assert loaded.record("minplus", (64, 64, 64), "float32", "dp", dims, 1e-9)
  d = resolve("minplus", 64, 64, 64, "float32", table=loaded,
              mesh_shape=dims)
  assert d.backend == "dp" and d.source == "measured"
  with pytest.raises(ValueError, match="unknown schedule"):
    tune_mesh(dims=dims, schedules=("warp",), dry_prior=True)


@pytest.mark.parametrize("op", ["mma", "minplus", "maxmin", "maxmul",
                                "orand", "addnorm"])
@pytest.mark.parametrize("shape", [(7, 11, 5), (16, 16, 16)])
def test_dispatch_equivalence(prior_table, op, shape):
  """For every (op, shape, dtype): the chosen backend's output must match
  mmo_reference — dispatch may change *where* an op runs, never its value."""
  m, k, n = shape
  a = RNG.standard_normal((m, k)).astype(np.float32)
  b = RNG.standard_normal((k, n)).astype(np.float32)
  c = RNG.standard_normal((m, n)).astype(np.float32)
  if op == "orand":
    a, b, c = a > 0.3, b > 0.3, c > 0.8
  d = resolve(op, m, k, n, a.dtype, table=prior_table)
  assert d.source == "prior"
  got = mmo(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c), op=op,
            backend=d.backend, block=d.cfg)
  ref = mmo_reference(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c), op=op)
  np.testing.assert_allclose(np.asarray(got, np.float64),
                             np.asarray(ref, np.float64), atol=1e-4)


def test_env_var_table_round_trip(tmp_path, monkeypatch):
  """$REPRO_COST_TABLE ships a persisted table into dispatch; an explicit
  use_cost_table(None) still really means 'no table' under it."""
  from repro.tuning import dispatch as dp
  t = CostTable(device="env")
  t.record("minplus", (16, 16, 16), "float32", "vector", (128,), 1e-6)
  path = tmp_path / "env_table.json"
  t.save(path)
  monkeypatch.setenv(dp.ENV_VAR, str(path))
  dp.clear_cost_table()  # re-arm the env lookup
  try:
    loaded = dp.get_cost_table()
    assert loaded is not None and len(loaded) == 1
    assert resolve("minplus", 16, 16, 16, "float32").backend == "vector"
    with use_cost_table(None):  # explicit None wins over the env var
      assert dp.get_cost_table() is None
      assert resolve("minplus", 16, 16, 16, "float32").backend == "xla"
  finally:
    monkeypatch.delenv(dp.ENV_VAR)
    dp.clear_cost_table()


def test_auto_backend_follows_global_table():
  """backend='auto' consults the installed table per call signature."""
  t = CostTable()
  # claim vector is the winner for this bucket so auto must take that path
  t.record("minplus", (16, 16, 16), "float32", "vector", (8,), 1e-6)
  t.record("minplus", (16, 16, 16), "float32", "xla", (512,), 1.0)
  a = RNG.standard_normal((13, 14)).astype(np.float32)
  b = RNG.standard_normal((14, 11)).astype(np.float32)
  ref = mmo_reference(jnp.asarray(a), jnp.asarray(b), op="minplus")
  with use_cost_table(t):
    got = mmo(jnp.asarray(a), jnp.asarray(b), op="minplus", backend="auto")
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
  # without a table, auto falls back to the historical default and still works
  got = mmo(jnp.asarray(a), jnp.asarray(b), op="minplus", backend="auto")
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
