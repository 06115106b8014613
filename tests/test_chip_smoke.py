"""chip_smoke.py's phases at toy sizes, on CPU with the kernels in interpret
mode, checked against the same references the chip run uses — and its entry
point's refusal to run anywhere but on a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = cs  # dataclasses resolve annotations through it
_spec.loader.exec_module(cs)

# apsp above full_ref_n exercises the sampled single-source reference, the
# burst spans several buckets of both rings, the mesh cases divide over 4
TOY = dict(apsp_n=48, gtc_n=40, knn_queries=20, knn_corpus=64, knn_dim=8,
           knn_k=3, mmo_n=24, per_kind=2, burst=6, burst_min_n=8,
           burst_max_n=64, dp_n=16, dp_batch=8, summa_n=32, full_ref_n=32,
           ref_rows=8)


def test_batch_phase_matches_references():
  checks = cs.Checks()
  timing = cs.batch_phase(checks, cs.Sizes(**TOY), 0, True)
  assert checks.failures == []
  assert checks.passed > 20
  assert timing["programs"] == 5 and timing["cold_s"] >= timing["warm_s"]


def test_arena_phase_matches_references_and_batch_paths():
  checks = cs.Checks()
  cs.arena_phase(checks, cs.Sizes(**TOY), 0, True)
  assert checks.failures == []
  assert checks.passed > 20


def test_checks_catch_a_wrong_closure():
  """The reference checks are live: one flipped distance fails the run."""
  sizes = cs.Sizes(**TOY)
  w = cs.apsp_graph(20, 0)
  from repro.apps.baselines import apsp_np
  got = apsp_np(w).astype("float32")
  checks = cs.Checks()
  cs.check_apsp(checks, w, got, sizes, 0, "ok")
  got[3, 5] += 1.0
  cs.check_apsp(checks, w, got, sizes, 0, "bad")
  assert checks.failures == ["bad: min-plus closure differs from "
                             "apsp_np all rows"]


_MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util, jax
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    cs = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = cs
    spec.loader.exec_module(cs)
    from repro.core.distributed import make_mesh
    checks = cs.Checks()
    mesh = make_mesh((2, 2), devices=jax.devices()[:4])
    cs.mesh_phase(checks, cs.Sizes(**%r), 0, True, mesh)
    assert checks.failures == [], checks.failures
    print("MESH_OK", checks.passed)
""" % TOY)


def test_mesh_phase_matches_local_path():
  """dp and SUMMA on a (2, 2) mesh of virtual CPU devices, bit-identical to
  the local path, outputs placed on all four devices (own process: the
  device count is fixed when jax initializes)."""
  env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
  r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT,
                      os.path.join(ROOT, "chip_smoke.py")],
                     capture_output=True, text=True, env=env, timeout=600)
  assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
  assert "MESH_OK" in r.stdout


def test_entry_point_refuses_a_host_without_tpu():
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                     capture_output=True, text=True, env=env, timeout=300)
  assert r.returncode != 0
  assert "no TPU found" in r.stderr
  for line in r.stdout.splitlines():
    assert '"ok": true' not in line
    if line.startswith("{"):
      assert json.loads(line).get("ok") is not True
