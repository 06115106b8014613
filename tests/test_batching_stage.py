"""Batch staging: each slot written once, and no host write at all for a
lone closure request that already fills its bucket (``batching.zero_copy``).

The reference is the construction single-write staging replaced: a padded
copy of every request, then ``np.stack``'s second copy of all of them."""
import numpy as np
import pytest

from fixtures import closure_corpus as corpus
from repro.core import closure as cl_mod
from repro.core import semiring as sr_mod
from repro.serve_mmo import (MMOEngine, batching, closure_request,
                             knn_request, mmo_request)
from repro.serve_mmo.scheduler import request_bucket


def _pad2d(x, rows, cols, pad):
  out = np.full((rows, cols), pad, dtype=x.dtype)
  out[:x.shape[0], :x.shape[1]] = x
  return out


def _padded_then_stacked(key, reqs, inert):
  if key.kind == "closure":
    (nb,) = key.shape
    empty = cl_mod.pad_adjacency(np.zeros((0, 0), key.dtypes[0]), nb,
                                 op=key.op)
    adj = np.stack([cl_mod.pad_adjacency(r.arrays["adj"], nb, op=key.op)
                    for r in reqs] + [empty] * inert)
    return (adj, np.asarray([r.shape[0] for r in reqs] + [0] * inert,
                            np.int32))
  if key.kind == "mmo":
    mb, kb, nb = key.shape
    sr = sr_mod.get(key.op)
    pa, pb = sr_mod.contraction_pads(key.op)
    ident = sr.oplus_identity
    if sr.boolean:
      pa = pb = ident = False
    shapes = {"a": (mb, kb, pa), "b": (kb, nb, pb), "c": (mb, nb, ident)}
    names = ["a", "b", "c"][:2 + key.params[0]]
    valid = [r.shape[1] for r in reqs]
  else:
    qb, rb, db = key.shape
    shapes = {"queries": (qb, db, 0.0), "corpus": (rb, db, 0.0)}
    names = ["queries", "corpus"]
    valid = [r.arrays["corpus"].shape[0] for r in reqs]
  stacked = []
  for name, dt in zip(names, key.dtypes):
    rows, cols, pad = shapes[name]
    empty = np.full((rows, cols), pad, np.dtype(dt))
    stacked.append(np.stack([_pad2d(r.arrays[name], rows, cols, pad)
                             for r in reqs] + [empty] * inert))
  return tuple(stacked) + (np.asarray(valid + [0] * inert, np.int32),)


def _assert_bitwise_equal(got, want):
  assert len(got) == len(want)
  for g, w in zip(got, want):
    assert g.dtype == w.dtype and g.shape == w.shape
    assert np.ascontiguousarray(g).tobytes() == w.tobytes()


@pytest.mark.parametrize("inert", [0, 1, 3])
@pytest.mark.parametrize("sizes", [(13,), (16,), (11, 16, 9)],
                         ids=["n<nb", "n==nb", "ragged"])
@pytest.mark.parametrize("op", corpus.IDENTITY_RINGS)
def test_stack_closure_matches_pad_then_stack(op, sizes, inert):
  reqs = [closure_request(corpus.rand_adj(op, n, 1, seed=i)[0], op=op,
                          prepared=True) for i, n in enumerate(sizes)]
  key = request_bucket(reqs[0])
  assert {request_bucket(r) for r in reqs} == {key} and key.shape == (16,)
  got = batching.stack_batch(key, reqs, inert=inert)
  _assert_bitwise_equal(got, _padded_then_stacked(key, reqs, inert))
  aliased = np.shares_memory(got[0], reqs[0].arrays["adj"])
  expect = sizes == (16,) and inert == 0
  assert aliased == batching.zero_copy(key, reqs, inert) == expect


@pytest.mark.parametrize("inert", [0, 2])
@pytest.mark.parametrize("case", ["mmo", "mmo_c", "mmo_orand", "knn"])
def test_stack_mmo_knn_match_pad_then_stack(case, inert):
  rng = np.random.default_rng(5)
  if case == "knn":
    reqs = [knn_request(rng.standard_normal((q, 6)).astype(np.float32),
                        rng.standard_normal((r, 6)).astype(np.float32), k=2)
            for q, r in [(3, 9), (7, 12)]]
  else:
    op = "orand" if case == "mmo_orand" else "minplus"
    reqs = []
    for m, k, n in [(5, 9, 3), (8, 16, 6)]:
      a = rng.standard_normal((m, k)).astype(np.float32)
      b = rng.standard_normal((k, n)).astype(np.float32)
      c = (rng.standard_normal((m, n)).astype(np.float32)
           if case == "mmo_c" else None)
      if op == "orand":
        a, b = a > 0, b > 0
      reqs.append(mmo_request(a, b, c, op=op))
  key = request_bucket(reqs[-1])
  assert request_bucket(reqs[0]) == key
  got = batching.stack_batch(key, reqs, inert=inert)
  _assert_bitwise_equal(got, _padded_then_stacked(key, reqs, inert))
  assert not batching.zero_copy(key, reqs, inert)


@pytest.mark.parametrize("writeable", [True, False],
                         ids=["writeable", "read-only"])
@pytest.mark.parametrize("n", [13, 16], ids=["n<nb", "n==nb"])
def test_engine_closure_staging_leaves_input_and_matches_solver(n, writeable):
  """A batch-mode closure gives the reference solver's values and iteration
  count bit for bit; the caller's array, which a zero-copy batch hands to
  the compiled call as is, comes back unchanged, read-only or not."""
  op = "minplus"
  adj = corpus.rand_adj(op, n, 1, seed=3)[0]
  before = adj.copy()
  adj.flags.writeable = writeable
  eng = MMOEngine(backend="xla")
  fut = eng.submit(closure_request(adj, op=op, prepared=True))
  assert eng.run_until_idle() == 1
  res = fut.result()
  ref, it = cl_mod.batched_leyzorek_closure(
      cl_mod.pad_adjacency(before, 16, op=op)[None], op=op, backend="xla",
      valid_n=np.asarray([n], np.int32))
  np.testing.assert_array_equal(res.value, np.asarray(ref)[0, :n, :n])
  assert res.extras["iterations"] == int(np.asarray(it)[0])
  assert adj.tobytes() == before.tobytes()
  (stack,) = [e for e in eng.tracer.events() if e["name"] == "pad_and_stack"]
  assert stack["args"]["zero_copy"] == (n == 16)
  assert stack["args"]["host_bytes"] == (
      0 if n == 16 else stack["args"]["h2d_bytes"])
