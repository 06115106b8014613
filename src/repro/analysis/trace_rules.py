"""Trace-safety rules: host/trace boundary hygiene + cache-key coverage.

``trace-safety`` analyzes every jit-reachable function — functions
decorated with ``jax.jit`` (bare or via ``functools.partial`` with
``static_argnames``), kernels handed to ``pl.pallas_call``, and
same-module functions that receive traced values from one of those roots
(one-module call-graph propagation) — and flags the two classic
trace-time bugs:

  * **Python control flow on a traced value** — ``if``/``while``/``for``/
    ``assert`` over an abstract tracer raises at trace time at best and
    silently specializes at worst.  Branching on *static* values is the
    whole point of ``static_argnames``, so the pass tracks which names are
    statically known: static parameters, literals, and shape/dtype
    extractions (``x.shape``, ``x.ndim``, ``x.dtype``, ``x.size``,
    ``len(x)`` are static under tracing even on traced ``x``), propagated
    through local assignments.  ``is None`` tests are always host-static.
  * **host coercions** — ``float()`` / ``int()`` / ``bool()`` / ``.item()``
    / ``.tolist()`` / ``np.*`` on a traced value forces a device sync
    (or a concretization error) inside the traced region.

``cache-key-coverage`` is the retrace-bug gate for serve_mmo/placement.py:
every knob fed to ``batching.make_batch_fn`` (the function the executable
cache compiles) must either appear in the ``exec_key`` tuple or be one of
``Placement``'s declared immutable attributes (set in ``__init__`` and
never reassigned — which the rule also verifies).  A knob that varies without
being keyed means two different programs share one cache slot; a knob in
neither set is exactly the bug class PRs 2–7 had to hand-audit.
"""
from __future__ import annotations

import ast
from typing import Optional

from repro.analysis.core import Context, Finding, rule

__all__ = ["jit_roots", "analyze_function"]

_STATIC_ATTRS = ("shape", "ndim", "dtype", "size")
_STATIC_CALLS = ("len", "isinstance", "range", "min", "max", "int", "tuple",
                 "list", "sorted", "enumerate", "zip", "abs", "type")
_COERCIONS = ("float", "bool")
_HOST_METHODS = ("item", "tolist")


# ---------------------------------------------------------------------------
# root discovery
# ---------------------------------------------------------------------------


def _is_jax_jit(node) -> bool:
  if isinstance(node, ast.Attribute) and node.attr == "jit":
    return True
  return isinstance(node, ast.Name) and node.id == "jit"


def _static_argnames(call: ast.Call) -> set:
  for kw in call.keywords:
    if kw.arg != "static_argnames":
      continue
    v = kw.value
    if isinstance(v, ast.Constant) and isinstance(v.value, str):
      return {v.value}
    if isinstance(v, (ast.Tuple, ast.List)):
      return {e.value for e in v.elts
              if isinstance(e, ast.Constant) and isinstance(e.value, str)}
  return set()


def jit_roots(tree) -> list:
  """(FunctionDef, static-param-name set) for every jit-decorated function
  and every kernel passed positionally to ``pl.pallas_call``."""
  roots = []
  for node in ast.walk(tree):
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
      for deco in node.decorator_list:
        if _is_jax_jit(deco):
          roots.append((node, set()))
        elif isinstance(deco, ast.Call):
          if _is_jax_jit(deco.func):
            roots.append((node, set()))
          elif (isinstance(deco.func, (ast.Name, ast.Attribute))
                and (deco.func.id if isinstance(deco.func, ast.Name)
                     else deco.func.attr) == "partial"
                and deco.args and _is_jax_jit(deco.args[0])):
            roots.append((node, _static_argnames(deco)))
  # kernels: pl.pallas_call(kernel_name, ...) — resolve the Name to a
  # same-scope FunctionDef; its Ref params are traced
  defs = {n.name: n for n in ast.walk(tree)
          if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
  rooted = {fn.name for fn, _ in roots}
  for node in ast.walk(tree):
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
        and node.func.attr == "pallas_call" and node.args
        and isinstance(node.args[0], ast.Name)):
      fn = defs.get(node.args[0].id)
      if fn is not None and fn.name not in rooted:
        rooted.add(fn.name)
        roots.append((fn, set()))
  return roots


def _param_names(fn) -> list:
  a = fn.args
  return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]


# ---------------------------------------------------------------------------
# per-function analysis
# ---------------------------------------------------------------------------


def analyze_function(fn, traced_params: set, *, path: str) -> tuple:
  """(findings, calls) — ``calls`` maps callee name → list of per-call
  arg-traced tuples (positional) for call-graph propagation."""
  findings = []
  calls: dict = {}
  traced = set(traced_params)

  def is_traced(node) -> bool:
    if node is None:
      return False
    if isinstance(node, ast.Name):
      return node.id in traced
    if isinstance(node, ast.Attribute):
      if node.attr in _STATIC_ATTRS:
        return False  # shape/dtype extraction is static under tracing
      return is_traced(node.value)
    if isinstance(node, ast.Compare):
      if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
        return False  # `x is None` tests the Python object, not the value
      return any(is_traced(c) for c in (node.left, *node.comparators))
    if isinstance(node, ast.Call):
      fname = _call_name(node)
      if fname in _STATIC_CALLS:
        return False  # len(x)/range(...) etc. produce host values
      # method calls propagate through the receiver too: `v.any()` is
      # traced when `v` is, even with no arguments
      recv = (is_traced(node.func.value)
              if isinstance(node.func, ast.Attribute) else False)
      return (recv or any(is_traced(a) for a in node.args)
              or any(is_traced(kw.value) for kw in node.keywords))
    return any(is_traced(c) for c in ast.iter_child_nodes(node))

  def _call_name(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Name):
      return f.id
    if isinstance(f, ast.Attribute):
      return f.attr
    return None

  def bind(target, value_traced: bool):
    for name in _target_names(target):
      if value_traced:
        traced.add(name)
      else:
        traced.discard(name)

  def _target_names(target):
    if isinstance(target, ast.Name):
      yield target.id
    elif isinstance(target, (ast.Tuple, ast.List)):
      for e in target.elts:
        yield from _target_names(e)

  def flag(node, msg):
    findings.append(Finding(rule="trace-safety", path=path,
                            line=node.lineno, message=msg))

  def record_call(node: ast.Call):
    f = node.func
    if isinstance(f, ast.Name):
      calls.setdefault(f.id, []).append(
          tuple(is_traced(a) for a in node.args))

  def scan_expr(node):
    """Flag host coercions anywhere inside an expression."""
    for sub in ast.walk(node):
      if not isinstance(sub, ast.Call):
        continue
      record_call(sub)
      fname = _call_name(sub)
      args_traced = (any(is_traced(a) for a in sub.args)
                     or any(is_traced(kw.value) for kw in sub.keywords))
      if fname in _COERCIONS and args_traced:
        flag(sub, f"`{fname}()` on a traced value inside a jit-reachable "
                  f"function forces host concretization "
                  f"(`{fn.name}`)")
      elif (fname in _HOST_METHODS and isinstance(sub.func, ast.Attribute)
            and is_traced(sub.func.value)):
        flag(sub, f"`.{fname}()` on a traced value inside a jit-reachable "
                  f"function forces a device sync (`{fn.name}`)")
      elif (isinstance(sub.func, ast.Attribute)
            and isinstance(sub.func.value, ast.Name)
            and sub.func.value.id in ("np", "numpy") and args_traced):
        flag(sub, f"`np.{sub.func.attr}` on a traced value inside a "
                  f"jit-reachable function runs on the host "
                  f"(`{fn.name}`; use jnp)")

  def scan_stmt(node):
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
      # nested defs (lax.scan/while_loop bodies): params are traced values
      for p in _param_names(node):
        traced.add(p)
      for s in node.body:
        scan_stmt(s)
      return
    if isinstance(node, ast.Assign):
      scan_expr(node.value)
      vt = is_traced(node.value)
      for t in node.targets:
        bind(t, vt)
      return
    if isinstance(node, (ast.AugAssign, ast.AnnAssign)):
      if node.value is not None:
        scan_expr(node.value)
        bind(node.target, is_traced(node.value)
             or (isinstance(node, ast.AugAssign) and is_traced(node.target)))
      return
    if isinstance(node, (ast.If, ast.While)):
      scan_expr(node.test)
      if is_traced(node.test):
        kind = "if" if isinstance(node, ast.If) else "while"
        flag(node.test,
             f"Python `{kind}` on a traced value in jit-reachable "
             f"`{fn.name}` — use lax.cond/select (or make the operand "
             f"static)")
      for s in (*node.body, *node.orelse):
        scan_stmt(s)
      return
    if isinstance(node, ast.For):
      scan_expr(node.iter)
      if is_traced(node.iter):
        flag(node.iter,
             f"Python `for` over a traced value in jit-reachable "
             f"`{fn.name}` — use lax.fori_loop/scan")
      bind(node.target, is_traced(node.iter))
      for s in (*node.body, *node.orelse):
        scan_stmt(s)
      return
    if isinstance(node, ast.Assert):
      scan_expr(node.test)
      if is_traced(node.test):
        flag(node.test,
             f"`assert` on a traced value in jit-reachable `{fn.name}` — "
             f"asserts concretize; use checkify or move to the host")
      return
    for sub in ast.iter_child_nodes(node):
      if isinstance(sub, ast.expr):
        scan_expr(sub)
      elif isinstance(sub, ast.stmt):
        scan_stmt(sub)

  for stmt in fn.body:
    scan_stmt(stmt)
  return findings, calls


@rule("trace-safety", family="trace")
def _rule_trace_safety(ctx: Context) -> list:
  """No Python control flow or host coercions on traced values."""
  out = []
  for mod in ctx.modules:
    roots = jit_roots(mod.tree)
    if not roots:
      continue
    defs = {n.name: n for n in ast.walk(mod.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    # worklist: function name → set of traced param names (unioned over
    # call sites); seeded by the jit roots, propagated one module deep
    traced_by_fn: dict = {}
    for fn, static in roots:
      traced_by_fn[fn.name] = {p for p in _param_names(fn)
                               if p not in static and p != "self"}
    findings_by_fn: dict = {}
    for _ in range(10):  # fixpoint over the same-module call graph
      changed = False
      for name, tp in sorted(traced_by_fn.items()):
        fn = defs.get(name)
        if fn is None:
          continue
        findings, calls = analyze_function(fn, tp, path=mod.relpath)
        findings_by_fn[name] = findings
        for callee, sites in calls.items():
          target = defs.get(callee)
          if target is None or callee in (r.name for r, _ in roots):
            continue
          params = [p for p in _param_names(target) if p != "self"]
          newly = {params[i]
                   for site in sites for i, t in enumerate(site)
                   if t and i < len(params)}
          if not newly:
            continue
          cur = traced_by_fn.setdefault(callee, set())
          if not newly <= cur:
            cur |= newly
            changed = True
      if not changed:
        break
    seen = set()
    for findings in findings_by_fn.values():
      for f in findings:
        key = (f.line, f.message)
        if key not in seen:
          seen.add(key)
          out.append(f)
  return out


# ---------------------------------------------------------------------------
# cache-key coverage (serve_mmo/placement.py)
# ---------------------------------------------------------------------------

# Placement attributes allowed to feed make_batch_fn WITHOUT being in the
# executable-cache key: immutable after __init__ (verified below).  ``mesh``
# is covered by ``_mesh_sig`` inside the key; ``interpret`` is a
# process-lifetime debug switch.
_CONSTANT_ATTRS = ("interpret", "mesh", "_mesh_sig")


def _names_and_self_attrs(node):
  names, attrs = set(), set()
  for sub in ast.walk(node):
    if isinstance(sub, ast.Attribute) and isinstance(sub.value, ast.Name) \
        and sub.value.id == "self":
      attrs.add(sub.attr)
    elif isinstance(sub, ast.Name) and sub.id != "self":
      names.add(sub.id)
  return names, attrs


def _is_make_batch_fn_call(node) -> bool:
  return (isinstance(node, ast.Call)
          and isinstance(node.func, (ast.Name, ast.Attribute))
          and (node.func.id if isinstance(node.func, ast.Name)
               else node.func.attr) == "make_batch_fn")


@rule("cache-key-coverage", family="trace")
def _rule_cache_key_coverage(ctx: Context) -> list:
  """Every make_batch_fn knob must be in exec_key or a Placement constant."""
  mod = ctx.module("serve_mmo/placement.py")
  if mod is None:
    return []
  out = []
  placement = next((n for n in ast.walk(mod.tree)
                    if isinstance(n, ast.ClassDef) and n.name == "Placement"),
                   None)
  if placement is None:
    return out
  exec_key = next((n for n in placement.body
                   if isinstance(n, ast.FunctionDef)
                   and n.name == "exec_key"), None)
  if exec_key is None:
    return [Finding(rule="cache-key-coverage", path=mod.relpath,
                    line=placement.lineno,
                    message="Placement has no exec_key method — the "
                            "executable cache has no keying discipline to "
                            "check")]
  key_names: set = set()
  key_attrs: set = set()
  for node in ast.walk(exec_key):
    if isinstance(node, ast.Return) and node.value is not None:
      n, a = _names_and_self_attrs(node.value)
      key_names |= n
      key_attrs |= a

  # sub-check: the declared constants must really be constant — assigned
  # in __init__ only
  for item in placement.body:
    if not isinstance(item, ast.FunctionDef) or item.name == "__init__":
      continue
    for node in ast.walk(item):
      targets = []
      if isinstance(node, ast.Assign):
        targets = node.targets
      elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        targets = [node.target]
      for t in targets:
        if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name) \
            and t.value.id == "self" and t.attr in _CONSTANT_ATTRS:
          out.append(Finding(
              rule="cache-key-coverage", path=mod.relpath, line=node.lineno,
              message=f"Placement.{item.name} reassigns self.{t.attr}, "
                      f"which cache-key coverage declares immutable — "
                      f"either stop reassigning it or add it to exec_key"))

  # every make_batch_fn call: each arg's free names must come from the key
  # (lambda defaults like ``lambda s=schedule:`` are resolved through)
  lambda_defaults: dict = {}
  for node in ast.walk(placement):
    if isinstance(node, ast.Lambda):
      args = node.args
      pos = (*args.posonlyargs, *args.args)
      for p, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        if isinstance(d, ast.Name):
          lambda_defaults[p.arg] = d.id
  for node in ast.walk(placement):
    if not _is_make_batch_fn_call(node):
      continue
    for value in (*node.args, *(kw.value for kw in node.keywords)):
      names, attrs = _names_and_self_attrs(value)
      names = {lambda_defaults.get(n, n) for n in names}
      loose_names = names - key_names
      loose_attrs = attrs - key_attrs - set(_CONSTANT_ATTRS)
      for n in sorted(loose_names):
        out.append(Finding(
            rule="cache-key-coverage", path=mod.relpath, line=value.lineno,
            message=f"make_batch_fn consumes `{n}`, which is not in the "
                    f"exec_key tuple — two programs differing in `{n}` "
                    f"would share one executable-cache slot"))
      for a in sorted(loose_attrs):
        out.append(Finding(
            rule="cache-key-coverage", path=mod.relpath, line=value.lineno,
            message=f"make_batch_fn consumes `self.{a}`, which is neither "
                    f"in exec_key nor a declared Placement constant — "
                    f"retrace/stale-program hazard"))
  # the key is checked only where Placement builds it: a make_batch_fn call
  # in another serve_mmo module compiles behind an unchecked key
  for other in ctx.modules:
    if other is mod or "serve_mmo/" not in other.relpath:
      continue
    for node in ast.walk(other.tree):
      if _is_make_batch_fn_call(node):
        out.append(Finding(
            rule="cache-key-coverage", path=other.relpath, line=node.lineno,
            message="make_batch_fn called outside Placement.program — "
                    "compile batch programs through it, behind exec_key"))
  return out
