"""The correctness check must fail a timed path broken on purpose: a run
that skips the look for a chip and serves a tiny cell through the engine
with its answers damaged where they are produced (the arena's eviction
sweep, or the batcher's split of a batch's output) must end ``correct``
false — once for each fault the cell can have.

The faults: a fixpoint that returns its state unchanged (the answer is the
input); half of a batch left out (every other slot's answer is its input);
and an answer altered (one entry of every answer flipped).  The closed-loop
cell sends one request at a time, so a batch there never holds two and has
no half to leave out; no cell spans chips, so none has an exchange between
chips to leave out.
"""
import numpy as np
import pytest

from conftest import run_tiny


def _input(req):
  return np.asarray(req.arrays["adj"])


def _altered(value):
  v = np.array(value, copy=True)
  if v.dtype == bool:
    v[0, -1] = ~v[0, -1]
  else:
    v[0, -1] = v[0, -1] + 1.0 if np.isfinite(v[0, -1]) else 1.0
  return v


FAULTS = {
    "state_unchanged": lambda i, req, v, rb: _input(req),
    "half_batch": lambda i, req, v, rb: _input(req) if i % 2 else v,
    "answer_altered": lambda i, req, v, rb: _altered(v),
}


def break_timed_path(monkeypatch, fault):
  """Damage every answer where the engine produces it."""
  from repro.serve_mmo import arena, batching
  damage = FAULTS[fault]
  sweep, split = arena.RequestArena.sweep, batching.split_results

  def broken_sweep(self):
    return [ev._replace(value=damage(ev.slot, ev.request, ev.value,
                                     self.capacity))
            for ev in sweep(self)]

  def broken_split(key, reqs, out):
    results = split(key, reqs, out)
    rb = np.asarray(out[0]).shape[0]
    for i, (r, res) in enumerate(zip(reqs, results)):
      res.value = damage(i, r, res.value, rb)
    return results

  monkeypatch.setattr(arena.RequestArena, "sweep", broken_sweep)
  monkeypatch.setattr(batching, "split_results", broken_split)


@pytest.mark.parametrize("name,fault", [
    ("closures1024.steady", "state_unchanged"),
    ("closures1024.steady", "half_batch"),
    ("closures1024.steady", "answer_altered"),
    ("paths4096.replay", "state_unchanged"),
    ("paths4096.replay", "answer_altered"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
  break_timed_path(monkeypatch, fault)
  out = run_tiny(name)
  assert out["correct"] is False
  assert out["checks"]["unanswered"]["value"] == 0
  assert any(v["value"] > v["limit"] for k, v in out["checks"].items()
             if k.startswith(("mismatch.", "max_rel_err.")))

