"""Every piece is found by name from its files alone, the result line has
exactly the contract's keys, and a host without a TPU gets no result."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, reference, spec
from conftest import ROOT, run_tiny

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in DOC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_pieces_are_found_by_name(name):
  cell = spec.load_cell(name)
  assert cell.traffic["loop"] in ("open", "closed")
  assert set(cell.config["check"]["limits"]) == {
      reference.number_name(a) for a in cell.config["apps"]}
  assert set(cell.traffic["apps"]) <= set(cell.config["apps"])
  e2e = {m["name"] for m in cell.end_to_end}
  assert "setup_s" in e2e and len(e2e) >= 2
  assert cell.per_layer, "every cell reports a per-layer metric"
  for m in cell.per_layer:
    assert m["moves"] in e2e
    assert callable(spec.metric_reader(m["name"]))


def test_names_units_and_files():
  for group in ("configs", "workloads", "end_to_end", "per_layer"):
    names = [e["name"] for e in DOC[group]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
  for m in DOC["end_to_end"] + DOC["per_layer"]:
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
  for c in DOC["configs"]:
    assert (ROOT / c["file"]).is_file()
    assert spec.load_json(ROOT / c["file"])["name"] == c["name"]
  for m in DOC["per_layer"]:
    assert all(w in CELLS for w in m.get("workloads", CELLS))


@pytest.mark.parametrize("name", ["closures1024.steady", "paths4096.replay"])
def test_result_line_keys_and_a_correct_tiny_run(name):
  out = run_tiny(name)
  assert list(out) == ["correct", "attempted", "failed", "metrics", "device",
                       "checks"]
  assert out["correct"] is True and out["failed"] == 0
  assert out["attempted"] > 0
  cell = spec.load_cell(name)
  assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
  for m in out["metrics"].values():
    assert set(m) == {"value", "unit"} and m["value"] > 0
  assert set(out["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
  assert all(set(c) == {"value", "limit"} for c in out["checks"].values())


def test_nearest_rank_counts_failures_as_missing_every_limit():
  assert harness.nearest_rank([1.0, 2.0, 3.0, 4.0], 50) == 2.0
  assert harness.nearest_rank([1.0] * 19 + [float("inf")], 95) == 1.0
  assert harness.nearest_rank([1.0] * 18 + [float("inf")] * 2, 95) == float(
      "inf")


def test_main_refuses_a_host_without_a_tpu():
  proc = subprocess.run(
      [sys.executable, str(ROOT / "bench" / "main.py"), "--workload",
       CELLS[0], "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
      capture_output=True, text=True, timeout=300,
      env={**os.environ, "JAX_PLATFORMS": "cpu"})
  assert proc.returncode != 0
  assert proc.stdout.strip() == ""
  assert "TPU" in proc.stderr


def test_main_fails_without_the_program(tmp_path):
  """A directory holding only BENCHMARK.json and the benchmark's files has
  no system under test: no result, nonzero exit."""
  import shutil
  shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
  shutil.copytree(ROOT / "bench", tmp_path / "bench",
                  ignore=shutil.ignore_patterns("__pycache__"))
  proc = subprocess.run(
      [sys.executable, str(tmp_path / "bench" / "main.py"), "--workload",
       CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
      capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
      env={**os.environ, "JAX_PLATFORMS": "cpu"})
  assert proc.returncode != 0
  assert proc.stdout.strip() == ""
  assert "No module named 'repro'" in proc.stderr
