"""Versioned JSON cost table with an analytic roofline prior.

One entry per *point* — (op, contraction shape bucket, dtype, backend, block
config) — holding the best-of wall seconds observed on the live device, or a
roofline-model estimate for points nobody has measured yet.  Measured entries
always beat prior entries at the same point (``record`` enforces the
precedence); across points, ``best`` is a plain argmin over seconds.

The table key is the **bucket signature**, not the raw shape: the serving
scheduler pads every problem up to its power-of-two bucket before executing,
so two raw shapes that land in the same bucket run the *same* executable and
therefore must share one dispatch decision.  Keying on raw shapes would both
fragment the table (one entry per arrival shape) and let two requests that
share an executable disagree about which backend to run it on.  See
DESIGN.md §Dispatch.

The analytic prior reuses the roofline constants (``roofline/hw.py``): an op
contracts 2·M·K·N flops on the MXU when an exact rewrite exists for the
backend, else on the VPU at ``peak/16`` with a ×2 structural port hazard for
fused min/max / or-and pairs, bounded below by HBM traffic; the Pallas arm
adds a per-grid-step overhead so tiny problems prefer the XLA path.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import NamedTuple, Optional, Sequence

import numpy as np

from repro.core import semiring as sr_mod
from repro.roofline import hw

SCHEMA_VERSION = 1

MIN_BUCKET = 8  # canonical bucket floor; serve_mmo.scheduler re-exports it

# Candidate block configs swept per backend: 'pallas' tunes the (bm, bn, bk)
# tile, 'vector'/'xla' tune the K block of the blocked broadcast-reduce
# (irrelevant for MXU-rewritten ops, which ignore it), 'megakernel' tunes the
# fused chunk length G (fixpoint iterations per kernel launch).
DEFAULT_CONFIGS = {
    "vector": ((128,), (512,)),
    "xla": ((512,),),
    "pallas": ((128, 128, 128), (128, 128, 256), (256, 128, 128)),
    "megakernel": ((2,), (4,), (8,)),
}

# The backend pool closure buckets dispatch over: the per-contraction arms
# plus the fused whole-fixpoint megakernel (kernels/closure_megakernel.py).
# ``best``'s default order deliberately EXCLUDES 'megakernel' — a single
# mmo call can't run a fused fixpoint, so plain contraction dispatch must
# never pick it; only callers that own a whole closure loop (the serving
# engine's closure buckets, the batched solvers) pass this pool explicitly.
CLOSURE_BACKENDS = ("xla", "vector", "pallas", "megakernel")

# Per-grid-step launch/pipeline overhead charged to the Pallas arms.
_PALLAS_STEP_OVERHEAD_S = 1e-7


def bucket_dim(n: int, min_bucket: int = MIN_BUCKET) -> int:
  """Round ``n`` up to the next power of two, with a floor."""
  if n <= 0:
    raise ValueError(f"dimension must be positive, got {n}")
  b = min_bucket
  while b < n:
    b *= 2
  return b


def bucket_shape(shape: tuple, min_bucket: int = MIN_BUCKET) -> tuple:
  return tuple(bucket_dim(int(d), min_bucket) for d in shape)


def signature(op: str, shape: Sequence[int], dtype, backend: str,
              cfg: tuple = ()) -> str:
  """Canonical string key for one table point; ``shape`` is (M, K, N) and is
  bucketed here, so raw call shapes and pre-bucketed shapes collide onto the
  same entry by construction."""
  m, k, n = bucket_shape(tuple(shape))
  cfg_s = "x".join(str(int(c)) for c in cfg) if cfg else "-"
  return f"{sr_mod.get(op).name}|{m}x{k}x{n}|{np.dtype(dtype)}|{backend}|{cfg_s}"


def _parse_cfg(cfg_s: str) -> tuple:
  return () if cfg_s == "-" else tuple(int(c) for c in cfg_s.split("x"))


class Decision(NamedTuple):
  """One dispatch outcome: which backend runs the bucket, with which blocks."""
  backend: str
  cfg: tuple
  seconds: float
  source: str  # 'measured' | 'prior' | 'default'


@dataclasses.dataclass
class CostEntry:
  seconds: float
  source: str  # 'measured' | 'prior'


# Distributed-schedule arms the table can hold rows for (core.distributed
# batched schedules); their cfg column is the mesh shape, e.g. '2x4'.
SCHEDULE_ARMS = ("dp", "kspan", "summa", "ring")

# Per-shard program launch + shard_map sync cost charged to the dp arm: dp
# moves no bytes, so without it the model would shard every batch down to
# trivially small contractions where launch overhead actually dominates.
DP_OVERHEAD_S = 50e-6


def _local_point_seconds(sr, m: int, k: int, n: int, itemsize: int,
                         backend: str, cfg: tuple) -> float:
  """Roofline seconds for one single-device (m, k, n) contraction — the
  shared core of ``prior_seconds`` and the per-shard compute term of
  ``sharded_prior_seconds`` (unbucketed: sharded shapes are already exact)."""
  flops = 2.0 * m * k * n
  bytes_ = itemsize * (m * k + k * n) + 4 * m * n  # fp32 out
  t_mem = bytes_ / hw.HBM_BW

  if backend == "xla":
    on_mxu = sr.mxu_rewrite is not None
  elif backend in ("pallas", "megakernel", "arena"):
    on_mxu = sr.name in ("mma", "addnorm")  # in-kernel MXU rewrites
  else:  # 'vector'
    on_mxu = False

  if on_mxu:
    t_comp = flops / hw.PEAK_FLOPS_BF16
  else:
    t_comp = flops * hw.vpu_hazard(sr.name) / (
        hw.PEAK_FLOPS_BF16 * hw.VPU_RATIO)

  if backend in ("megakernel", "arena"):
    # fused whole-fixpoint arm: the iterate stays VMEM-resident across the
    # chunk, so the table's one-contraction unit pays the HBM round-trip
    # only once per G iterations — compute-bound contractions price the
    # same as pallas, bandwidth-bound ones price ~G× cheaper, which is the
    # whole reason the arm exists (TCU model: off-chip traffic bounds
    # iterative matrix algorithms, not FLOPs).  The request arena
    # (serve_mmo/arena.py) runs the same fused chunk over its slot buffer,
    # so its per-contraction slot-second prior is the same roofline
    g = int(cfg[0]) if cfg else 8
    t = max(t_comp, t_mem / max(g, 1))
    # one grid step per output row-block per iteration, request dim amortized
    t += math.ceil(m / 128) * _PALLAS_STEP_OVERHEAD_S
    return t

  t = max(t_comp, t_mem)
  if backend == "pallas":
    bm, bn, bk = (cfg + (128, 128, 128))[:3] if cfg else (128, 128, 128)
    grid = math.ceil(m / bm) * math.ceil(n / bn) * math.ceil(k / bk)
    t += grid * _PALLAS_STEP_OVERHEAD_S
  return t


def prior_seconds(op: str, shape: Sequence[int], dtype, backend: str,
                  cfg: tuple = ()) -> float:
  """Analytic roofline prior for one point (v5e constants, seconds)."""
  sr = sr_mod.get(op)
  m, k, n = bucket_shape(tuple(shape))
  return _local_point_seconds(sr, m, k, n, np.dtype(dtype).itemsize,
                              backend, cfg)


def sharded_prior_seconds(op: str, shape: Sequence[int], dtype,
                          schedule: str, mesh_shape: Sequence[int], *,
                          backend: str = "xla") -> float:
  """Analytic prior for one distributed schedule on a (rows, cols) mesh:
  per-shard roofline compute + ring-model collective traffic over one ICI
  link (formulas shared with roofline.collectives.ring_traffic_bytes).

  This is the fallback ``dispatch.resolve`` compares against the local prior
  when the table has no measured mesh row — the model that decides whether
  the collective is worth it before anyone has benchmarked the mesh.
  """
  from repro.roofline.collectives import ring_traffic_bytes
  sr = sr_mod.get(op)
  m, k, n = bucket_shape(tuple(shape))
  dims = tuple(int(d) for d in mesh_shape)
  rows, cols = dims[0], dims[-1]
  itemsize = np.dtype(dtype).itemsize

  if schedule == "dp":
    # requests sharded over every device: per-device work is the whole
    # contraction over 1/P of the batch, no collectives — the arm's cost is
    # throughput-normalized like the others (whole-bucket work over P)
    ndev = 1
    for d in dims:
      ndev *= max(d, 1)
    return (_local_point_seconds(sr, m, k, n, itemsize, backend, ()) / ndev
            + DP_OVERHEAD_S)
  if schedule == "kspan":
    t = _local_point_seconds(sr, m, max(k // cols, 1), n, itemsize,
                             backend, ())
    coll = ring_traffic_bytes("all-reduce", 4.0 * m * n, cols)
  elif schedule == "summa":
    t = _local_point_seconds(sr, max(m // rows, 1), k, max(n // cols, 1),
                             itemsize, backend, ())
    coll = (ring_traffic_bytes("all-gather",
                               itemsize * (m // max(rows, 1)) * k, cols)
            + ring_traffic_bytes("all-gather",
                                 itemsize * k * (n // max(cols, 1)), rows))
  elif schedule == "ring":
    t = cols * _local_point_seconds(sr, m, max(k // cols, 1),
                                    max(n // cols, 1), itemsize, backend, ())
    coll = cols * ring_traffic_bytes(
        "collective-permute", itemsize * max(k // cols, 1) * n, cols)
  else:
    raise ValueError(f"unknown schedule {schedule!r}; one of {SCHEDULE_ARMS}")
  return t + coll / hw.ICI_BW_PER_LINK


class CostTable:
  """In-memory cost table with JSON (de)serialization."""

  def __init__(self, *, device: str = "unknown"):
    self.version = SCHEMA_VERSION
    self.device = device
    self.entries: dict[str, CostEntry] = {}
    self._best_cache: dict = {}  # memoized best() — cleared on record()

  def __len__(self) -> int:
    return len(self.entries)

  # -- writes ----------------------------------------------------------------

  def record(self, op: str, shape, dtype, backend: str, cfg: tuple,
             seconds: float, *, source: str = "measured") -> bool:
    """Insert one point.  A prior never overwrites a measurement; a
    measurement overwrites anything.  Returns whether the entry was stored."""
    if source not in ("measured", "prior"):
      raise ValueError(f"source must be 'measured' or 'prior', got {source!r}")
    if not (seconds > 0.0 and math.isfinite(seconds)):
      raise ValueError(f"seconds must be positive and finite, got {seconds}")
    sig = signature(op, shape, dtype, backend, cfg)
    old = self.entries.get(sig)
    if old is not None and old.source == "measured" and source == "prior":
      return False
    self.entries[sig] = CostEntry(seconds=float(seconds), source=source)
    self._best_cache.clear()
    return True

  # -- reads -----------------------------------------------------------------

  def lookup(self, op: str, shape, dtype, backend: str,
             cfg: tuple = ()) -> Optional[CostEntry]:
    return self.entries.get(signature(op, shape, dtype, backend, cfg))

  def best(self, op: str, shape, dtype,
           backends: Optional[Sequence[str]] = None) -> Optional[Decision]:
    """Cheapest (backend, cfg) for one bucketed call signature, or None when
    the table holds nothing for it.  Ties break toward the earlier backend in
    ``backends`` order, then the smaller signature, so the choice does not
    depend on insertion order and survives a save → load round trip."""
    order = tuple(backends) if backends else ("xla", "vector", "pallas")
    m, k, n = bucket_shape(tuple(shape))
    prefix = f"{sr_mod.get(op).name}|{m}x{k}x{n}|{np.dtype(dtype)}|"
    cache_key = (prefix, order)
    if cache_key in self._best_cache:  # hot path: mmo resolves per call
      return self._best_cache[cache_key]
    choice: Optional[Decision] = None
    rank = None
    for sig, entry in self.entries.items():
      if not sig.startswith(prefix):
        continue
      backend, cfg_s = sig[len(prefix):].split("|")
      if backend not in order:
        continue
      cand_rank = (entry.seconds, order.index(backend), sig)
      if rank is None or cand_rank < rank:
        choice = Decision(backend, _parse_cfg(cfg_s), entry.seconds,
                          entry.source)
        rank = cand_rank
    self._best_cache[cache_key] = choice
    return choice

  def counts(self) -> dict:
    out = {"measured": 0, "prior": 0}
    for e in self.entries.values():
      out[e.source] += 1
    return out

  # -- persistence -----------------------------------------------------------

  def to_json(self) -> str:
    return json.dumps({
        "schema_version": self.version,
        "device": self.device,
        "entries": {sig: {"seconds": e.seconds, "source": e.source}
                    for sig, e in sorted(self.entries.items())},
    }, indent=2, sort_keys=True)

  @classmethod
  def from_json(cls, text: str) -> "CostTable":
    doc = json.loads(text)
    version = doc.get("schema_version")
    if version != SCHEMA_VERSION:
      raise ValueError(
          f"cost table schema_version {version!r} != {SCHEMA_VERSION} "
          "(re-run the autotuner to regenerate the table)")
    table = cls(device=doc.get("device", "unknown"))
    for sig, e in doc.get("entries", {}).items():
      entry = CostEntry(seconds=float(e["seconds"]), source=str(e["source"]))
      if entry.source not in ("measured", "prior"):
        raise ValueError(f"bad entry source {entry.source!r} at {sig!r}")
      if not (entry.seconds > 0.0 and math.isfinite(entry.seconds)):
        raise ValueError(f"bad entry seconds {entry.seconds!r} at {sig!r}")
      table.entries[sig] = entry
    return table

  def save(self, path) -> None:
    with open(path, "w") as f:
      f.write(self.to_json() + "\n")

  @classmethod
  def load(cls, path) -> "CostTable":
    with open(path) as f:
      return cls.from_json(f.read())
