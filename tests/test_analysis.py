"""repro.analysis tests: fixture good/bad pairs per rule, suppressions,
baseline round-trip, and the self-run gate (the shipped tree must be clean).
"""
from __future__ import annotations

import json
import textwrap

import pytest

from repro import analysis
from repro.analysis.__main__ import main as cli_main


def _tree(tmp_path, files: dict):
  for rel, src in files.items():
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src), encoding="utf-8")
  return tmp_path


def _run(root, rules):
  return analysis.run(root, rules=rules)


# --- semiring family --------------------------------------------------------

GOOD_TABLE = """
    _T = {"mma": 1, "minplus": 2, "maxplus": 3, "minmul": 4, "maxmul": 5,
          "minmax": 6, "maxmin": 7, "orand": 8, "addnorm": 9}
"""

BAD_TABLE = """
    _T = {"mma": 1, "minplus": 2, "maxplus": 3, "minmul": 4, "maxmul": 5,
          "minmax": 6, "maxmin": 7, "orand": 8, "addnrm": 9}
"""


def test_table_coverage_good(tmp_path):
  root = _tree(tmp_path, {"mod.py": GOOD_TABLE})
  assert _run(root, "semiring-table-coverage").findings == []


def test_table_coverage_bad(tmp_path):
  root = _tree(tmp_path, {"mod.py": BAD_TABLE})
  found = _run(root, "semiring-table-coverage").findings
  msgs = " ".join(f.message for f in found)
  assert "addnorm" in msgs      # missing registered op
  assert "addnrm" in msgs       # unknown key


def test_pad_consistency_flags_broken_pair(tmp_path):
  # minplus pads must satisfy pa + pb == +inf (the ⊕-identity); (0.0, 0.0)
  # sums to 0.0 and would corrupt padded lanes
  root = _tree(tmp_path, {"mod.py": """
      import numpy as np
      _PADS = {"mma": (0.0, 0.0), "minplus": (0.0, 0.0),
               "maxplus": (0.0, float(-np.inf)),
               "minmul": (float(np.inf), float(np.inf)),
               "maxmul": (float(-np.inf), float(np.inf)),
               "minmax": (float(np.inf), float(np.inf)),
               "maxmin": (float(-np.inf), float(-np.inf)),
               "orand": (0.0, 0.0), "addnorm": (0.0, 0.0)}
  """})
  found = _run(root, "semiring-pad-consistency").findings
  assert any("minplus" in f.message for f in found)
  assert not any("'mma'" in f.message for f in found)


def test_hardcoded_identity_scoped_to_contraction_modules(tmp_path):
  src = """
      import numpy as np
      ACC = float(np.inf)
  """
  flagged = _tree(tmp_path / "a", {"core/closure.py": src})
  unflagged = _tree(tmp_path / "b", {"core/other.py": src})
  assert len(_run(flagged, "semiring-hardcoded-identity").findings) == 1
  assert _run(unflagged, "semiring-hardcoded-identity").findings == []


def test_semiring_laws_pass_on_live_registry(tmp_path):
  # the numeric family runs against the live registry regardless of the
  # scanned tree; an empty tree keeps the AST rules quiet
  root = _tree(tmp_path, {"empty.py": ""})
  rep = _run(root, "semiring-laws,semiring-closure-pads")
  assert rep.findings == []


# --- locks family -----------------------------------------------------------

LOCKED_CACHE = """
    import threading

    class ExecutableCache:
      def __init__(self):
        self._lock = threading.Lock()
        self._entries = {}
        self._misses = 0

      def get(self, k):
        with self._lock:
          return self._entries.get(k)

      def _insert_locked(self, k, v):
        self._entries[k] = v
"""

UNLOCKED_CACHE = """
    import threading

    class ExecutableCache:
      def __init__(self):
        self._lock = threading.Lock()
        self._entries = {}
        self._misses = 0

      def get(self, k):
        return self._entries.get(k)
"""


def test_lock_discipline_good(tmp_path):
  root = _tree(tmp_path, {"serve_mmo/cache.py": LOCKED_CACHE})
  assert _run(root, "lock-discipline").findings == []


def test_lock_discipline_bad(tmp_path):
  root = _tree(tmp_path, {"serve_mmo/cache.py": UNLOCKED_CACHE})
  found = _run(root, "lock-discipline").findings
  assert len(found) == 1
  assert "ExecutableCache.get" in found[0].message
  assert "_entries" in found[0].message


def test_lock_discipline_nested_def_not_protected(tmp_path):
  # a closure built under the lock may run after the lock is released
  root = _tree(tmp_path, {"serve_mmo/cache.py": """
      import threading

      class ExecutableCache:
        def __init__(self):
          self._lock = threading.Lock()
          self._entries = {}
          self._misses = 0

        def get(self, k):
          with self._lock:
            def later():
              return self._entries.get(k)
          return later
  """})
  found = _run(root, "lock-discipline").findings
  assert len(found) == 1


# --- trace family -----------------------------------------------------------

GOOD_JIT = """
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("n",))
    def f(x, n):
      if n > 2:              # static: fine
        x = x * 2
      for i in range(x.shape[0]):  # shape extraction is static
        x = x + i
      return jnp.sum(x)
"""

BAD_JIT = """
    import jax
    import numpy as np

    @jax.jit
    def f(x):
      if x > 0:              # traced branch
        x = x + 1
      y = float(x)           # host coercion
      z = np.sum(x)          # host numpy on traced
      return y + z
"""


def test_trace_safety_good(tmp_path):
  root = _tree(tmp_path, {"mod.py": GOOD_JIT})
  assert _run(root, "trace-safety").findings == []


def test_trace_safety_bad(tmp_path):
  root = _tree(tmp_path, {"mod.py": BAD_JIT})
  msgs = [f.message for f in _run(root, "trace-safety").findings]
  assert any("`if`" in m for m in msgs)
  assert any("float()" in m for m in msgs)
  assert any("np.sum" in m for m in msgs)


def test_trace_safety_propagates_through_helpers(tmp_path):
  root = _tree(tmp_path, {"mod.py": """
      import jax

      def helper(v):
        if v.any():          # only bad because f passes a tracer in
          return v * 2
        return v

      @jax.jit
      def f(a):
        return helper(a)
  """})
  found = _run(root, "trace-safety").findings
  assert any("helper" in f.message for f in found)


def test_cache_key_coverage_flags_unkeyed_knob(tmp_path):
  root = _tree(tmp_path, {"serve_mmo/placement.py": """
      from repro.serve_mmo import batching

      class Placement:
        def __init__(self):
          self.interpret = False
          self.flavor = "x"

        def exec_key(self, key, rb, backend):
          return (key, rb, backend)

        def program(self, key, rb, backend, block):
          return self.cache.get_or_compile(
              self.exec_key(key, rb, backend),
              lambda: batching.make_batch_fn(
                  key, backend=backend, block=block,
                  interpret=self.interpret, mesh=self.mesh),
              ())

        def reroute(self, mesh):
          self.mesh = mesh
  """, "serve_mmo/engine.py": """
      from repro.serve_mmo import batching

      class MMOEngine:
        def prewarm(self, key):
          return batching.make_batch_fn(key, backend="xla")
  """})
  found = _run(root, "cache-key-coverage").findings
  msgs = [f.message for f in found]
  assert any("`block`" in m for m in msgs)       # name not in key tuple
  # mesh/interpret are declared constants: not flagged as loose knobs...
  assert not any("self.interpret" in m for m in msgs)
  assert not any("consumes `self.mesh`" in m for m in msgs)
  # ...but a constant that is reassigned is
  assert any("reassigns self.mesh" in m for m in msgs)
  # a compile outside Placement.program bypasses the checked key
  assert [f.path for f in found if "outside Placement" in f.message] == [
      "serve_mmo/engine.py"]


def test_cache_key_coverage_clean_engine_passes(tmp_path):
  root = _tree(tmp_path, {"serve_mmo/placement.py": """
      from repro.serve_mmo import batching

      class Placement:
        def exec_key(self, key, rb, backend):
          return (key, rb, backend, self._mesh_sig)

        def program(self, key, rb, backend):
          return self.cache.get_or_compile(
              self.exec_key(key, rb, backend),
              lambda: batching.make_batch_fn(key, backend=backend,
                                             interpret=self.interpret),
              ())
  """, "serve_mmo/engine.py": """
      class MMOEngine:
        def prewarm(self, key, rb):
          return self.placement.program(key, rb, "xla")
  """})
  assert _run(root, "cache-key-coverage").findings == []


# --- suppressions -----------------------------------------------------------


def test_suppression_same_line_and_line_above(tmp_path):
  root = _tree(tmp_path, {"core/closure.py": """
      import numpy as np
      A = float(np.inf)  # repro: ignore[semiring-hardcoded-identity]
      # repro: ignore[semiring-hardcoded-identity]
      B = float(np.inf)
      C = float(np.inf)
  """})
  rep = _run(root, "semiring-hardcoded-identity")
  assert len(rep.findings) == 1          # only C
  assert rep.suppressed == 2


def test_bare_suppression_silences_all_rules(tmp_path):
  root = _tree(tmp_path, {"core/closure.py": """
      import numpy as np
      A = float(np.inf)  # repro: ignore
  """})
  rep = _run(root, "semiring-hardcoded-identity")
  assert rep.findings == [] and rep.suppressed == 1


def test_wrong_rule_suppression_does_not_silence(tmp_path):
  root = _tree(tmp_path, {"core/closure.py": """
      import numpy as np
      A = float(np.inf)  # repro: ignore[lock-discipline]
  """})
  assert len(_run(root, "semiring-hardcoded-identity").findings) == 1


# --- baseline ---------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
  root = _tree(tmp_path, {"serve_mmo/cache.py": UNLOCKED_CACHE})
  first = analysis.run(root, rules="lock-discipline")
  assert len(first.findings) == 1
  bl = tmp_path / "baseline.json"
  analysis.save_baseline(bl, first.findings)
  again = analysis.run(root, rules="lock-discipline",
                       baseline=analysis.load_baseline(bl))
  assert again.findings == [] and len(again.baselined) == 1
  assert again.ok


def test_baseline_survives_line_shifts(tmp_path):
  root = _tree(tmp_path, {"serve_mmo/cache.py": UNLOCKED_CACHE})
  bl = tmp_path / "baseline.json"
  analysis.save_baseline(bl, analysis.run(root,
                                          rules="lock-discipline").findings)
  # unrelated edit above the finding moves its line; fingerprint must hold
  shifted = "# a new comment line\n# another\n" + textwrap.dedent(
      UNLOCKED_CACHE)
  (root / "serve_mmo" / "cache.py").write_text(shifted, encoding="utf-8")
  again = analysis.run(root, rules="lock-discipline",
                       baseline=analysis.load_baseline(bl))
  assert again.findings == [] and len(again.baselined) == 1


def test_baseline_rejects_unknown_version(tmp_path):
  bl = tmp_path / "baseline.json"
  bl.write_text(json.dumps({"version": 99, "findings": []}))
  with pytest.raises(ValueError, match="version"):
    analysis.load_baseline(bl)


# --- CLI + self-run ---------------------------------------------------------


def test_cli_exits_zero_on_shipped_tree(capsys):
  assert cli_main([]) == 0
  out = capsys.readouterr().out
  assert "OK" in out


def test_cli_json_output_is_machine_readable(capsys):
  assert cli_main(["--json"]) == 0
  doc = json.loads(capsys.readouterr().out)
  assert doc["ok"] is True
  assert doc["findings"] == []
  assert set(doc["rules"]) >= {"lock-discipline", "trace-safety",
                               "semiring-laws"}


def test_cli_exits_nonzero_on_bad_tree(tmp_path, capsys):
  root = _tree(tmp_path, {"serve_mmo/cache.py": UNLOCKED_CACHE})
  assert cli_main(["--root", str(root), "--no-baseline"]) == 1
  assert "lock-discipline" in capsys.readouterr().out


def test_cli_update_baseline_then_clean(tmp_path, capsys):
  root = _tree(tmp_path, {"serve_mmo/cache.py": UNLOCKED_CACHE})
  bl = tmp_path / "bl.json"
  assert cli_main(["--root", str(root), "--baseline", str(bl),
                   "--update-baseline"]) == 0
  assert cli_main(["--root", str(root), "--baseline", str(bl)]) == 0
  capsys.readouterr()


def test_cli_rules_selector_rejects_unknown(capsys):
  with pytest.raises(SystemExit):
    cli_main(["--rules", "no-such-rule"])
  capsys.readouterr()


def test_self_run_is_fast_and_clean():
  """The acceptance gate: all three families over src/repro, zero new
  findings, under 10 seconds."""
  from repro.analysis.__main__ import DEFAULT_BASELINE, DEFAULT_ROOT
  report = analysis.run(DEFAULT_ROOT,
                        baseline=analysis.load_baseline(DEFAULT_BASELINE))
  assert report.findings == [], "\n".join(str(f) for f in report.findings)
  assert report.elapsed_s < 10.0
  fams = {analysis.all_rules()[r].family for r in report.rules_run}
  assert fams == set(analysis.FAMILIES)
