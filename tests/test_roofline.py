"""Roofline extraction: the HLO walker must be loop-correct and agree with
XLA on loop-free programs."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import make_mesh
    from repro.roofline.hlo_walk import module_cost

    M = 256
    def rolled(ws, x):
        def body(x, w):
            return x @ w, ()
        x, _ = jax.lax.scan(body, x, ws)
        return x
    def unrolled(ws, x):
        for i in range(16):
            x = x @ ws[i]
        return x

    sw = jax.ShapeDtypeStruct((16, M, M), jnp.float32)
    sx = jax.ShapeDtypeStruct((M, M), jnp.float32)
    c_r = module_cost(jax.jit(rolled).lower(sw, sx).compile().as_text())
    co_u = jax.jit(unrolled).lower(sw, sx).compile()
    c_u = module_cost(co_u.as_text())
    expect = 16 * 2 * M ** 3
    assert c_r.flops == expect, (c_r.flops, expect)
    assert c_u.flops == expect
    # agreement with XLA's own counter on the loop-free program
    xla_cost = co_u.cost_analysis()
    if isinstance(xla_cost, (list, tuple)):  # older jax: per-device list
        xla_cost = xla_cost[0]
    assert abs(c_u.flops - xla_cost["flops"]) < 1e-6
    print("FLOPS_OK")

    # collective accounting: K-sharded matmul → one all-reduce of (M,M) f32
    mesh = make_mesh((8,), ("model",))
    def f(a, b):
        return a @ b
    j = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "model")),
                                 NamedSharding(mesh, P("model", None))),
                out_shardings=NamedSharding(mesh, P(None, None)))
    co = j.lower(sx, sx).compile()
    c = module_cost(co.as_text())
    ring = 2 * (8 - 1) / 8 * M * M * 4
    assert abs(c.coll_bytes - ring) / ring < 0.05, (c.coll_bytes, ring)
    assert abs(c.flops - 2 * M ** 3 / 8) < 1e-6
    print("COLL_OK")
""")


@pytest.mark.slow
def test_hlo_walker():
  env = dict(os.environ, PYTHONPATH=SRC)
  r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                     text=True, env=env, timeout=600)
  assert r.returncode == 0, r.stderr[-3000:]
  assert "FLOPS_OK" in r.stdout
  assert "COLL_OK" in r.stdout


def test_roofline_terms():
  from repro.roofline.analysis import Roofline
  r = Roofline(arch="x", shape="train_4k", mesh="single", chips=256,
               hlo_flops=256 * 197e12,       # exactly 1s of compute
               hlo_bytes=256 * 819e9 * 0.5,  # 0.5s of memory
               coll_bytes=50e9 * 4 * 0.25,   # 0.25s of collective
               coll_breakdown={}, model_flops=256 * 197e12 * 0.5)
  assert abs(r.t_compute - 1.0) < 1e-9
  assert abs(r.t_memory - 0.5) < 1e-9
  assert abs(r.t_collective - 0.25) < 1e-9
  assert r.bottleneck == "compute"
  assert abs(r.mfu_bound - 0.5) < 1e-9


def test_collective_parser_shapes():
  from repro.roofline.collectives import collective_bytes
  hlo = '''
  %x = bf16[16,128]{1,0} all-gather(%a), replica_groups=[2,8]<=[16], dimensions={0}
  %y = f32[64]{0} all-reduce-start(%b), replica_groups={{0,1,2,3}}
  '''
  out = collective_bytes(hlo)
  ag = (8 - 1) / 8 * 16 * 128 * 2
  ar = 2 * (4 - 1) / 4 * 64 * 4
  assert abs(out["all-gather"] - ag) < 1e-6
  assert abs(out["all-reduce"] - ar) < 1e-6
