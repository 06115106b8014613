"""Find every piece of the benchmark by its name.

The cells and metrics are the entries of ``BENCHMARK.json``; a cell names
its configuration (``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``); each per-layer metric is read by
``bench/metrics/<name>.py``.  Adding a cell, a mix or a metric is adding
files and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
  name: str
  chips: int
  config_name: str
  config: dict
  traffic_name: str
  traffic: dict
  end_to_end: list   # BENCHMARK.json end_to_end entries this cell reports
  per_layer: list    # per_layer entries this cell reports


def _reports(entry: dict, cell: str, e2e_names) -> bool:
  if "workloads" in entry:
    return cell in entry["workloads"]
  return "moves" not in entry or entry["moves"] in e2e_names


def load_json(path: Path) -> dict:
  with open(path) as f:
    return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
  doc = load_json(root / "BENCHMARK.json")
  cells = {w["name"]: w for w in doc["workloads"]}
  if name not in cells:
    raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
  w = cells[name]
  configs = {c["name"]: c for c in doc["configs"]}
  e2e = [m for m in doc["end_to_end"] if _reports(m, name, ())]
  names = {m["name"] for m in e2e}
  layer = [m for m in doc["per_layer"] if _reports(m, name, names)]
  return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
              config=load_json(root / configs[w["config"]]["file"]),
              traffic_name=w["traffic"],
              traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
              end_to_end=e2e, per_layer=layer)


def metric_reader(name: str):
  """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
  path = BENCH / "metrics" / f"{name}.py"
  spec = importlib.util.spec_from_file_location(
      "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module.read
