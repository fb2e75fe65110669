"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch.compile_cache import enable_compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENTRY = os.path.join(_REPO, "benchmarks", "eval_engine.py")


@pytest.fixture
def restore_cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_else_set(monkeypatch, tmp_path,
                                              restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(_ENTRY) == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compiles_land_in_env_dir(tmp_path):
    """A fresh process, as a CLI starts: its compiles are written to
    ``JAX_COMPILATION_CACHE_DIR``."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            f"enable_compile_cache({_ENTRY!r})\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((8, 8)))"
            ".block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
           "PYTHONPATH": os.path.join(_REPO, "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert any(tmp_path.iterdir()), "nothing was cached in the env dir"


def test_default_dir_is_fixed_inside_checkout(monkeypatch,
                                              restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    for entry in (_ENTRY, os.path.join(_REPO, "chip_smoke.py")):
        assert enable_compile_cache(entry) == want
        assert jax.config.jax_compilation_cache_dir == want


def test_entry_outside_a_checkout_raises(monkeypatch,
                                         restore_cache_config):
    """An entry script with no ``pyproject.toml`` above it has no
    checkout to hold the cache: fail rather than pick a shared path."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    with pytest.raises(RuntimeError, match="not inside a checkout"):
        enable_compile_cache(os.path.join(os.path.abspath(os.sep),
                                          "main.py"))
    assert jax.config.jax_compilation_cache_dir == before
