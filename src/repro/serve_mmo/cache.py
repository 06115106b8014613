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
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable

import jax


@dataclasses.dataclass
class CacheEntry:
  compiled: Callable
  compile_s: float
  hits: int = 0


class ExecutableCache:
  def __init__(self):
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

  def get_or_compile(self, exec_key, make_fn: Callable, args) -> Callable:
    """Return the compiled program for ``exec_key``, compiling on first use.

    ``make_fn`` builds the pure function; ``args`` are example (or abstract)
    operands fixing shapes/dtypes.
    """
    with self._lock:
      entry = self._entries.get(exec_key)
      if entry is not None:
        entry.hits += 1
        return entry.compiled
      self._misses += 1
    t0 = time.perf_counter()
    abstract = tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)
    compiled = jax.jit(make_fn()).lower(*abstract).compile()
    elapsed = time.perf_counter() - t0
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
