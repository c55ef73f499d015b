"""Where the persistent compilation cache lives: ``JAX_COMPILATION_CACHE_DIR``
when it is set (and nowhere else), otherwise ``<repo>/.jax_cache``."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax, jax.numpy as jnp
import repro
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_cache_dir_from_environment(tmp_path):
    cache = tmp_path / "cc"
    assert _probe(cache) == str(cache)
    assert any(cache.iterdir()), "no cache entry written to the named dir"


def test_cache_dir_defaults_inside_checkout():
    assert _probe(None) == os.path.join(REPO, ".jax_cache")
