"""repro.launch.compile_cache: the persistent compile cache lives where
JAX_COMPILATION_CACHE_DIR says, and at <checkout>/.jax_cache otherwise.
Each case runs in a fresh interpreter: enabling the cache is a process-wide
setting the test process itself must not take on."""
import os
import subprocess
import sys

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import os, sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.launch import compile_cache
path = compile_cache.enable()
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env, compile_):
    code = PROBE.format(src=os.path.join(ROOT, "src"), compile=compile_)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()[-2:]


def test_cache_goes_where_the_env_var_says(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    path, configured = _probe(env, compile_=True)
    assert path == configured == str(tmp_path)
    assert any(f.endswith("-cache") for f in os.listdir(tmp_path))


def test_cache_defaults_to_the_checkout():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    path, configured = _probe(env, compile_=False)
    assert path == configured == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.parent == compile_cache.CHECKOUT
    assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
