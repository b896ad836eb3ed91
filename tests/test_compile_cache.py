"""Where the persistent compilation cache lands: in $JAX_COMPILATION_CACHE_DIR
when that is set (and nowhere else), otherwise in the fixed, git-ignored
``.jax_cache/`` of the checkout.  Each case compiles in a CPU-only child so
the cache is never switched on in the test process itself."""
import os
import subprocess
import sys

from repro.launch.compile_cache import CHECKOUT_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T + {salt})(jnp.ones((32, 32))).block_until_ready()
"""


def _run(env_extra, salt):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    r = subprocess.run([sys.executable, "-c", CHILD.format(salt=salt)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.split()[-1]


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_env_dir_is_used_and_nothing_else(tmp_path):
    before = _entries(CHECKOUT_DIR)
    got = _run({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}, salt=1.5)
    assert got == str(tmp_path)
    assert _entries(tmp_path), "no cache entry was written"
    assert _entries(CHECKOUT_DIR) == before


def test_default_dir_is_fixed_in_the_checkout_and_ignored():
    got = _run({}, salt=2.5)
    assert got == str(CHECKOUT_DIR)
    assert os.path.dirname(got) == ROOT
    assert _entries(CHECKOUT_DIR)
    ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored
