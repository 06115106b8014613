"""AOT executable cache — steady-state traffic never retraces.

Programs are compiled ahead-of-time (``jax.jit(fn).lower(shapes).compile()``)
and keyed by (BucketKey, batch size, backend): the engine asks the cache
before every batch, so after warmup every bucket's traffic replays a stored
executable and the hit/miss counters *prove* zero recompiles (asserted in
benchmarks/serve_bench.py).  Batch sizes are part of the key; the scheduler's
max_batch bounds how many variants one bucket can create.

Thread-safety: the cache is shared between the caller thread (``prewarm``)
and the serving loop, so every ``_entries``/``_misses`` touch happens under
``_lock``.  Compilation itself runs *outside* the lock — it can take
hundreds of milliseconds and must not stall the serving loop's hits on other
keys.  Two threads missing the same key may therefore both compile; the
first insert wins, the loser's work is discarded, and the counters stay
consistent (misses counts compile *attempts*, so `misses >= executables`).

With a ``recorder`` (the engine's ``FlightRecorder``) every miss also
leaves a ``compile`` span — the key's label and the seconds it took — so
the trace says which program compiled, and when.  Its ``kernels`` arg names
each Pallas kernel of the program that states its geometry (the VPU
semiring kernel's DMA block and accumulator strip), so the trace also says
which kernel layout ran.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import jax

from repro.serve_mmo.observability import FlightRecorder


def _kernel_metadata(jaxpr) -> dict:
  """Kernel name → metadata of every Pallas kernel in ``jaxpr`` (a
  ``Jaxpr`` or ``ClosedJaxpr``, nested programs included) that carries
  some."""
  found, stack = {}, [getattr(jaxpr, "jaxpr", jaxpr)]
  while stack:
    for eqn in stack.pop().eqns:
      if eqn.primitive.name == "pallas_call":
        if eqn.params.get("metadata"):
          found[eqn.params["name"]] = dict(eqn.params["metadata"])
        continue
      for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
          sub = getattr(sub, "jaxpr", sub)
          if hasattr(sub, "eqns"):
            stack.append(sub)
  return found


@dataclasses.dataclass
class CacheEntry:
  compiled: Callable
  compile_s: float
  hits: int = 0


class ExecutableCache:
  def __init__(self, recorder: Optional[FlightRecorder] = None):
    self.recorder = recorder
    self._lock = threading.Lock()
    self._entries: dict = {}
    self._misses = 0

  @property
  def misses(self) -> int:
    with self._lock:
      return self._misses

  @property
  def hits(self) -> int:
    with self._lock:
      return sum(e.hits for e in self._entries.values())

  @property
  def compiles(self) -> int:
    return self.misses

  @property
  def compile_s(self) -> float:
    with self._lock:
      return sum(e.compile_s for e in self._entries.values())

  def __len__(self) -> int:
    with self._lock:
      return len(self._entries)

  def get_or_compile(self, exec_key, make_fn: Callable, args,
                     label: Optional[str] = None) -> Callable:
    """Return the compiled program for ``exec_key``, compiling on first use.

    ``make_fn`` builds the pure function; ``args`` are example (or abstract)
    operands fixing shapes/dtypes; ``label`` names the program in the
    ``compile`` span (default: the key's text).
    """
    with self._lock:
      entry = self._entries.get(exec_key)
      if entry is not None:
        entry.hits += 1
        return entry.compiled
      self._misses += 1
    recorder = self.recorder
    traced = recorder is not None and recorder.enabled
    span_t0 = recorder.now() if traced else 0.0
    t0 = time.perf_counter()
    abstract = tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)
    program = jax.jit(make_fn()).trace(*abstract)
    compiled = program.lower().compile()
    elapsed = time.perf_counter() - t0
    if traced:
      args = {"key": str(exec_key) if label is None else label,
              "seconds": elapsed}
      kernels = _kernel_metadata(program.jaxpr)
      if kernels:
        args["kernels"] = kernels
      recorder.span("compile", cat="cache", t0_s=span_t0,
                    t1_s=recorder.now(), args=args)
    with self._lock:
      entry = self._entries.get(exec_key)
      if entry is not None:  # lost the compile race: first insert wins
        entry.hits += 1
        return entry.compiled
      self._entries[exec_key] = CacheEntry(compiled=compiled,
                                           compile_s=elapsed)
    return compiled

  def executables(self) -> dict:
    """exec key → compiled program, for callers that inspect what ran (the
    output shardings of a mesh-placed bucket, say)."""
    with self._lock:
      return {k: e.compiled for k, e in self._entries.items()}

  def stats(self) -> dict:
    with self._lock:
      return {
          "executables": len(self._entries),
          "hits": sum(e.hits for e in self._entries.values()),
          "misses": self._misses,
          "compile_s": round(
              sum(e.compile_s for e in self._entries.values()), 3),
      }
