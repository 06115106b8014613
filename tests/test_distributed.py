"""Distributed layer tests — run in a subprocess with 8 fake host devices so
the main test process keeps seeing 1 device (per the dry-run isolation
rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import (distributed_leyzorek, make_mesh,
                                        mmo_kspan, ring_mmo, summa_mmo)
    from repro.core.mmo import mmo_reference
    from repro.core import prepare_adjacency
    from repro.models import zoo, common as cm
    from repro import configs
    from repro.train import AdamWConfig, init_opt_state, make_train_step
    from repro.data import DataConfig, SyntheticLM

    mesh = make_mesh((2, 4))
    rng = np.random.default_rng(1)

    # --- 1. all three distributed schedules == reference, every op class ---
    M, K, N = 16, 32, 24
    A = rng.standard_normal((M, K)).astype(np.float32)
    B = rng.standard_normal((K, N)).astype(np.float32)
    C = rng.standard_normal((M, N)).astype(np.float32)
    for op in ("mma", "minplus", "maxmin", "addnorm", "orand"):
        a, b, c = (A > 0, B > 0, C > 1.0) if op == "orand" else (A, B, C)
        ref = np.asarray(mmo_reference(jnp.asarray(a), jnp.asarray(b),
                                       jnp.asarray(c), op=op), np.float64)
        with mesh:
            for fn, kw in ((mmo_kspan, {}), (summa_mmo, {}), (ring_mmo, {})):
                got = np.asarray(fn(jnp.asarray(a), jnp.asarray(b),
                                    jnp.asarray(c), op=op, mesh=mesh, **kw),
                                 np.float64)
                assert np.abs(got - ref).max() < 1e-3, (op, fn.__name__)
    print("SCHEDULES_OK")

    # --- 2. distributed closure == local closure ---
    n = 32
    W = rng.uniform(1, 10, (n, n)).astype(np.float32)
    W = np.where(rng.random((n, n)) < 0.7, np.inf, W)
    adj = prepare_adjacency(jnp.asarray(W), op="minplus")
    ref = np.asarray(adj).copy()
    for k in range(n):
        ref = np.minimum(ref, ref[:, k:k+1] + ref[k:k+1, :])
    out = np.asarray(distributed_leyzorek(adj, op="minplus", mesh=mesh))
    fin = np.isfinite(ref)
    assert np.abs(out[fin] - ref[fin]).max() < 1e-4
    assert np.array_equal(np.isinf(out), ~fin)
    print("CLOSURE_OK")

    # --- 3. sharded train step == single-device train step ---
    cfg = configs.get_config("tinyllama-1.1b", smoke=True)
    par = cm.Parallelism(data_axes=("data",), tp_size=4, dp_size=2)
    params = zoo.init(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  global_batch=4, seed=2))
    batch = data.batch_at(0)
    oc = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step = make_train_step(cfg, oc)
    (_, _), m_ref = jax.jit(step)((params, opt), batch)

    specs = cm.specs_like(params, cfg, par)
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    with mesh:
        sp = jax.device_put(params, ns(specs))
        so = jax.device_put(opt, ns({"m": specs, "v": specs, "step": P()}))
        sb = jax.device_put(batch, ns({"tokens": P("data", None),
                                       "labels": P("data", None)}))
        (_, _), m_sh = jax.jit(step)((sp, so), sb)
    # tolerance covers cross-device reduction-order drift (varies by jax/XLA)
    assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3, (
        float(m_ref["loss"]), float(m_sh["loss"]))
    print("TRAIN_SHARD_OK")
""")


@pytest.mark.slow
def test_distributed_suite():
  env = dict(os.environ, PYTHONPATH=SRC)
  r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                     text=True, env=env, timeout=900)
  assert r.returncode == 0, r.stderr[-3000:]
  for marker in ("SCHEDULES_OK", "CLOSURE_OK", "TRAIN_SHARD_OK"):
    assert marker in r.stdout
