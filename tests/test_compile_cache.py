"""``repro.compile_cache``: the persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``.

Each case runs in a fresh interpreter, because the cache directory is
process-wide JAX state that must not leak into other tests.
"""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
placed = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.arange(3.0)).block_until_ready()
print(placed)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(action: str, cache_env: str | None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, action], env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_cache_written_where_the_environment_says(tmp_path):
    placed, configured = _probe("compile", str(tmp_path))
    assert placed == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_cache_defaults_to_the_checkout():
    placed, configured = _probe("place", None)
    assert placed == configured == str(ROOT / ".jax_cache")
