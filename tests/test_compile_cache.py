"""Where the entry points put JAX's persistent compilation cache."""

import os
import subprocess
import sys

import jax

from repro.launch.compile_cache import DEFAULT_DIR, configure_compile_cache

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert DEFAULT_DIR.name == ".jax_cache"
    assert (DEFAULT_DIR.parent / "src" / "repro" / "launch" / "compile_cache.py").is_file()


def test_environment_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev  # nothing set in code


def test_compiles_land_in_the_environment_dir(tmp_path):
    child = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.arange(8.0)).block_until_ready()
"""
    env = dict(os.environ, PYTHONPATH=_SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    res = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
    assert any(tmp_path.iterdir()), "no cache entry written"
