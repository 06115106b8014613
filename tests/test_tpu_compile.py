"""Compile-only checks of the engine's Pallas kernels for a TPU v5e chip.

Nothing runs here: each test lowers a kernel at a width the engine emits and
compiles it with the TPU compiler for one chip of a described (not attached)
``v5e:2x2`` topology.  That compiler refuses what interpret mode accepts —
unaligned dynamic slices, scalar stores to VMEM, more scoped VMEM than a
kernel may use — so these tests guard the chip path on a CPU-only host.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and every test worker
must collect the same tests.  The persistent compilation cache is off around
these compiles, since an entry written for a described chip cannot be read
back without one.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.serve_mmo.arena import RequestArena
from repro.serve_mmo.scheduler import MIN_BUCKET, BucketKey

mk = importlib.import_module("repro.kernels.closure_megakernel")
ops = importlib.import_module("repro.kernels.ops")


@pytest.fixture(scope="module")
def topo():
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  was_on = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
    jax.config.update("jax_enable_compilation_cache", was_on)
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  yield topo
  jax.config.update("jax_enable_compilation_cache", was_on)
  compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
  return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh22(topo):
  from repro.core.distributed import make_mesh
  return make_mesh((2, 2), devices=topo.devices)


def _spec(shape, dtype, sharding):
  return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pow2_buckets(cap):
  n, out = MIN_BUCKET, []
  while n <= cap:
    out.append(n)
    n *= 2
  return out


@pytest.mark.parametrize("n", (128, 4096))
@pytest.mark.parametrize("op", ("mma", "addnorm", "minplus", "maxmin",
                                "orand", "maxplus", "minmul", "maxmul"))
def test_semiring_mmo_compiles(one_chip, op, n):
  """The batched serving entry (leading request axis + per-request live K)
  for one ring of each family — MXU, MXU rewrite, VPU min/max, boolean —
  and every path ring of the benchmark: the VPU kernel's wide blocks must
  fit the scoped VMEM and lower through Mosaic."""
  dtype = jnp.bool_ if op == "orand" else jnp.float32
  a = _spec((2, n, n), dtype, one_chip)
  kv = _spec((2,), jnp.int32, one_chip)
  jax.jit(lambda x, y, k: ops.semiring_mmo(x, y, op=op, interpret=False,
                                            k_valid=k)).lower(a, a,
                                                              kv).compile()


_CAP_CASES = ([("minplus", "leyzorek", n) for n in _pow2_buckets(mk.MAX_N)]
              + [("orand", "leyzorek", mk.MAX_N),
                 ("maxmin", "bellman_ford", mk.MAX_N),
                 ("mma", "leyzorek", mk.MAX_N)])


@pytest.mark.parametrize("op,algorithm,n", _CAP_CASES)
def test_megakernel_compiles_up_to_cap(one_chip, op, algorithm, n):
  """Every pow2 closure bucket the engine may route to the megakernel
  compiles, with the iterate VMEM-resident, up to the size cap."""
  dtype = jnp.bool_ if op == "orand" else jnp.float32
  adj = _spec((4, n, n), dtype, one_chip)
  valid = _spec((4,), jnp.int32, one_chip)
  jax.jit(lambda a, v: mk.megakernel_fixpoint(
      a, op=op, algorithm=algorithm, valid_n=v,
      interpret=False)).lower(adj, valid).compile()


@pytest.mark.parametrize("op", ("minplus", "orand"))
def test_arena_programs_compile(one_chip, op):
  """The arena's admit / tick / read programs at the megakernel's cap."""
  dtype = "bool" if op == "orand" else "float32"
  key = BucketKey("closure", op, (mk.MAX_N,), (dtype,), ("leyzorek",))
  arena = RequestArena(key, interpret=False)
  for make_fn, abstract in arena._program_specs.values():
    jax.jit(make_fn()).lower(
        *(_spec(a.shape, a.dtype, one_chip) for a in abstract)).compile()


@pytest.mark.parametrize("rb", (4, 8))
@pytest.mark.parametrize("op", ("orand", "minmax"))
def test_dp_closure_batch_compiles_on_the_mesh(mesh22, op, rb):
  """The dp engine's closure batch at the smallest and largest bucket the
  four-chip graph-query cell serves: one fixpoint per chip of the 2x2
  mesh, the VPU kernel inside each shard, and no collective."""
  from jax.sharding import NamedSharding, PartitionSpec as P

  from repro.serve_mmo import batching
  dtype = "bool" if op == "orand" else "float32"
  whole = NamedSharding(mesh22, P())
  for n in (64, mk.MAX_N):
    key = BucketKey("closure", op, (n,), (dtype,), ("leyzorek",))
    fn = batching.make_batch_fn(key, backend="pallas", interpret=False,
                                mesh=mesh22, schedule="dp")
    text = jax.jit(fn).lower(_spec((rb, n, n), dtype, whole),
                             _spec((rb,), jnp.int32, whole)).compile(
                             ).as_text()
    assert "tpu_custom_call" in text
    assert not any(c in text for c in ("all-reduce", "all-gather",
                                       "collective-permute", "all-to-all"))
