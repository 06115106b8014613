"""Where the entry points' persistent compilation cache lives: the directory
named by $JAX_COMPILATION_CACHE_DIR when set, else the checkout's fixed,
git-ignored .jax_cache/.  Each case runs in its own process, since JAX reads
the cache settings once."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
    path = enable_compile_cache()
    print("PATH", path)
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    print("DEFAULT", DEFAULT_DIR)
    if "compile" in __import__("sys").argv:
      jax.jit(lambda x: jnp.sin(x) * 3.0 + x).lower(jnp.ones(7)).compile()
""")


def _run(env_dir, *args):
  env = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
  env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
  if env_dir is not None:
    env["JAX_COMPILATION_CACHE_DIR"] = env_dir
  r = subprocess.run([sys.executable, "-c", _SCRIPT, *args],
                     capture_output=True, text=True, env=env, timeout=300)
  assert r.returncode == 0, r.stderr[-3000:]
  return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_cache_dir_from_environment_receives_entries(tmp_path):
  out = _run(str(tmp_path), "compile")
  assert out["PATH"] == str(tmp_path)
  assert out["CONFIG"] == str(tmp_path)
  assert any(tmp_path.iterdir()), "no cache entry written"


def test_cache_dir_defaults_to_fixed_checkout_dir():
  out = _run(None)
  root = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
  assert out["PATH"] == out["CONFIG"] == out["DEFAULT"]
  assert out["PATH"] == os.path.join(root, ".jax_cache")
  # a second process picks the same directory: nothing in it varies per run
  assert _run(None)["PATH"] == out["PATH"]
