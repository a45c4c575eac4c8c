"""The persistent compilation cache has one home (launch/compile_cache.py)."""
from __future__ import annotations

import os
import subprocess
import sys

import jax

from repro.launch import compile_cache as cc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_dir_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = cc.enable_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert cc.enable_compile_cache() == got     # same path every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compiles_land_in_the_env_dir(tmp_path):
    """A process that inherits JAX_COMPILATION_CACHE_DIR writes its
    executables there."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
        ".block_until_ready()\n")
    env = {**os.environ, "PYTHONPATH": "src",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert os.listdir(tmp_path / "cc")
