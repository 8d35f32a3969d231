"""Where the entry points put JAX's persistent compilation cache. Each case
runs in a fresh interpreter: the cache is process-wide state."""

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROG = r"""
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
# the location is under test, not the threshold: cache this small compile
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
"""


def _run(env_extra, drop=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    for k in ("JAX_COMPILATION_CACHE_DIR", *drop):
        env.pop(k, None)
    env.update(env_extra)
    out = subprocess.run(
        [sys.executable, "-c", PROG], capture_output=True, text=True,
        timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def _listing(path: Path) -> set:
    return {p.name for p in path.iterdir()} if path.is_dir() else set()


def test_cache_stays_where_the_environment_puts_it(tmp_path):
    from repro.compile_cache import DEFAULT_DIR

    before = _listing(DEFAULT_DIR)
    there = tmp_path / "cache"
    returned, configured = _run({"JAX_COMPILATION_CACHE_DIR": str(there)})
    assert returned == configured == str(there)
    assert _listing(there), "no cache entry written"
    assert _listing(DEFAULT_DIR) == before  # and nowhere else


def test_cache_defaults_to_the_checkout():
    from repro.compile_cache import DEFAULT_DIR

    returned, configured = _run({})
    assert returned == configured == str(ROOT / ".jax_cache") == str(DEFAULT_DIR)
    assert _listing(DEFAULT_DIR)
