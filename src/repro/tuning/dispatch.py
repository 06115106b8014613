"""The brain of ``backend="auto"``: cost-table-driven backend selection.

``resolve(op, m, k, n, dtype)`` returns the cheapest (backend, block config)
the active cost table knows for the call's bucket signature, falling back to
the historical default ('xla') when no table is loaded or the table has no
entry for the point.  Resolution is host-side dict work — cheap enough for
the ``mmo`` wrapper to run per call, and deterministic so the serving
engine's per-bucket memoization and the executable cache agree.

The active table is process-global (``set_cost_table`` / ``use_cost_table``)
and can be seeded from the ``REPRO_COST_TABLE`` environment variable, which
is how a warmed, persisted table ships into a serving job.  Callers that
need isolation (the engine, tests) pass ``table=`` explicitly instead.
"""
from __future__ import annotations

import contextlib
import math
import os
import threading
from typing import Optional, Sequence, Union

from repro.tuning.cost_table import (CLOSURE_BACKENDS, SCHEDULE_ARMS,
                                     CostTable, Decision, prior_seconds,
                                     sharded_prior_seconds)

ENV_VAR = "REPRO_COST_TABLE"
DEFAULT_BACKEND = "xla"

# CLOSURE_BACKENDS (re-exported above) is the pool for dispatchers that own
# a whole closure fixpoint (the serving engine's closure buckets): the
# per-contraction arms plus the fused 'megakernel' arm, whose cfg is the
# chunk length G.  ``resolve`` with its default ``backends`` never returns
# 'megakernel' — a single mmo call can't run a fused fixpoint — so the arm
# only competes where a caller passes this pool explicitly.

_lock = threading.Lock()
_table: Optional[CostTable] = None
_env_checked = False


def set_cost_table(table: Union[CostTable, str, None]) -> None:
  """Install the process-global cost table (a CostTable or a JSON path).
  ``None`` means *explicitly no table* — the env-var lookup stays disarmed,
  so ``use_cost_table(None)`` really scopes to table-less dispatch even when
  ``$REPRO_COST_TABLE`` is set.  Use ``clear_cost_table`` to re-arm the env
  default instead."""
  global _table, _env_checked
  with _lock:
    if isinstance(table, (str, os.PathLike)):
      table = CostTable.load(table)
    _table = table
    _env_checked = True


def clear_cost_table() -> None:
  """Drop the installed table and re-arm the ``$REPRO_COST_TABLE`` lookup
  (process-default state)."""
  global _table, _env_checked
  with _lock:
    _table = None
    _env_checked = False


def get_cost_table() -> Optional[CostTable]:
  """Active global table; loads ``$REPRO_COST_TABLE`` once if set."""
  global _table, _env_checked
  with _lock:
    if _table is None and not _env_checked:
      _env_checked = True
      path = os.environ.get(ENV_VAR)
      if path:
        _table = CostTable.load(path)
    return _table


@contextlib.contextmanager
def use_cost_table(table: Union[CostTable, str, None]):
  """Scoped ``set_cost_table`` (restores the previous table on exit)."""
  prev = get_cost_table()
  set_cost_table(table)
  try:
    yield get_cost_table()
  finally:
    set_cost_table(prev)


def closure_backends(n: int) -> tuple:
  """The closure-bucket backend pool at bucket dim ``n``: the fused
  'megakernel' arm competes only up to its VMEM-resident size cap."""
  from repro.kernels import closure_megakernel as mk
  if n <= mk.MAX_N:
    return CLOSURE_BACKENDS
  return tuple(b for b in CLOSURE_BACKENDS if b != "megakernel")


def contraction_seconds(op: str, m: int, k: int, n: int, dtype, *,
                        backend: str = "auto",
                        table: Optional[CostTable] = None) -> tuple:
  """(backend, cfg, seconds) — the *static* per-contraction cost estimate
  for one bucket signature: the cost table's cheapest row (measured beats
  prior) under ``backend="auto"``, that backend's best table row for a
  fixed backend, and the analytic roofline prior when the table holds
  nothing for the point.  Seconds are always finite.

  This is the hand-off point between dispatch and the serving engine's
  adaptive estimator (serve_mmo/estimator.py): the value returned here is
  the estimator's cold-start prior, which live EWMA observations then
  correct.  Keeping it beside ``resolve`` pins the invariant that the
  prediction prior and the dispatch decision read the same table the same
  way.
  """
  if backend == "auto":
    d = resolve(op, m, k, n, dtype, table=table)
    chosen, cfg, s = d.backend, d.cfg, d.seconds
  else:
    chosen, cfg, s = backend, (), float("inf")
    table = table if table is not None else get_cost_table()
    best = table.best(op, (m, k, n), dtype,
                      backends=(backend,)) if table else None
    if best is not None:
      cfg, s = best.cfg, best.seconds
  if not math.isfinite(s):
    s = prior_seconds(op, (m, k, n), dtype, chosen, cfg)
  return chosen, cfg, s


def resolve(op: str, m: int, k: int, n: int, dtype, *,
            table: Optional[CostTable] = None,
            backends: Optional[Sequence[str]] = None,
            mesh_shape: Optional[Sequence[int]] = None,
            schedules: Optional[Sequence[str]] = None) -> Decision:
  """Dispatch decision for one call signature (raw or bucketed shape).

  With ``mesh_shape`` (a (rows, cols) device-mesh shape), distributed
  schedule arms compete too: the returned Decision's ``backend`` may then be
  a schedule name from ``SCHEDULE_ARMS`` with the mesh shape as its ``cfg``.
  Measured mesh rows in the table (backend = schedule, cfg = mesh shape) are
  compared against the local choice directly; when the mesh is unmeasured,
  the sharded roofline prior competes against the *local prior* — model vs
  model, never a v5e model against a live-device measurement — so an
  untuned mesh only wins when the collective model says it should.
  ``schedules`` restricts which arms may compete (e.g. closures pass
  ('dp', 'summa') — independent per-device fixpoints, or the one contraction
  schedule that keeps C sharded in place).
  """
  table = table if table is not None else get_cost_table()
  local = table.best(op, (m, k, n), dtype, backends=backends) \
      if table is not None else None
  if local is None:
    local = Decision(DEFAULT_BACKEND, (), float("inf"), "default")
  if mesh_shape is None:
    return local

  dims = tuple(int(d) for d in mesh_shape)
  arms = []
  for sched in (schedules if schedules is not None else SCHEDULE_ARMS):
    if sched not in SCHEDULE_ARMS:
      raise ValueError(f"unknown schedule {sched!r}; one of {SCHEDULE_ARMS}")
    entry = table.lookup(op, (m, k, n), dtype, sched, dims) \
        if table is not None else None
    if entry is not None:
      arms.append(Decision(sched, dims, entry.seconds, entry.source))
    else:
      arms.append(Decision(
          sched, dims,
          sharded_prior_seconds(op, (m, k, n), dtype, sched, dims), "prior"))
  if not arms:
    return local
  # measured-beats-prior inside the sharded pool too: an unmeasured arm's
  # (idealized-hardware) prior must not shadow a row someone benchmarked
  measured = [a for a in arms if a.source == "measured"]
  best_sharded = min(measured or arms, key=lambda a: a.seconds)

  # like-for-like comparison: a sharded prior beats the local *prior*, a
  # sharded measurement beats whatever the local arm actually holds
  local_s = local.seconds
  if best_sharded.source == "prior" and local.source != "prior":
    local_s = prior_seconds(op, (m, k, n), dtype, local.backend, local.cfg)
  if not math.isfinite(local_s):  # 'default' local: no table at all
    local_s = prior_seconds(op, (m, k, n), dtype, local.backend, local.cfg)
  return best_sharded if best_sharded.seconds < local_s else local
