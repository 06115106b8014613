"""Pipeline parallelism: GPipe schedule ≡ sequential layer application, for
forward AND gradients (subprocess with 8 host devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.distributed import make_mesh
    from repro.models.pipeline import bubble_fraction, pipeline, split_stages

    mesh = make_mesh((4, 2), ("stage", "data"))
    S, LPS, D, M, MB = 4, 3, 16, 6, 2   # stages, layers/stage, dim, micro, mb
    L = S * LPS
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.standard_normal((L, D, D)) / np.sqrt(D), jnp.float32)
    X = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)

    def layer(x, w):
        return jnp.tanh(x @ w), None

    def stage_fn(w_stage, x):   # scan this stage's layer slice
        y, _ = jax.lax.scan(layer, x, w_stage)
        return y

    # reference: plain sequential over all layers, microbatches independent
    def ref_fwd(W, X):
        def f(x):
            y, _ = jax.lax.scan(layer, x, W)
            return y
        return jax.vmap(f)(X)

    staged = split_stages(W, S)
    with mesh:
        pl = pipeline(stage_fn, mesh, axis="stage",
                      in_spec=P("stage"), x_spec=P(None, "data"))
        got = pl(staged, X)
    ref = ref_fwd(W, X)
    err = float(jnp.abs(got - ref).max())
    assert err < 1e-5, err
    print("FWD_OK", err)

    # gradients flow through the ppermute schedule
    def loss_pl(Wst, X):
        with mesh:
            return (pipeline(stage_fn, mesh, axis="stage",
                             in_spec=P("stage"),
                             x_spec=P(None, "data"))(Wst, X) ** 2).sum()
    def loss_ref(W, X):
        return (ref_fwd(W, X) ** 2).sum()
    g_pl = jax.grad(loss_pl)(staged, X).reshape(L, D, D)
    g_ref = jax.grad(loss_ref)(W, X)
    gerr = float(jnp.abs(g_pl - g_ref).max())
    assert gerr < 1e-4, gerr
    print("GRAD_OK", gerr)

    assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
    print("BUBBLE_OK")
""")


@pytest.mark.slow
def test_pipeline_parallelism():
  env = dict(os.environ, PYTHONPATH=SRC)
  r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                     text=True, env=env, timeout=900)
  assert r.returncode == 0, r.stderr[-3000:]
  for m in ("FWD_OK", "GRAD_OK", "BUBBLE_OK"):
    assert m in r.stdout
