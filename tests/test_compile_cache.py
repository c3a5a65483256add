"""The persistent compile-cache helper the entry points call."""
import importlib
import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_jax_config():
    """The helper writes process-global config: put it back afterwards."""
    before = {k: getattr(jax.config, k) for k in _KEYS}
    yield before
    for k, v in before.items():
        jax.config.update(k, v)
    assert {k: getattr(jax.config, k) for k in _KEYS} == before


def test_env_dir_is_left_in_charge(monkeypatch, tmp_path, restore_jax_config):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper sets no other
    directory over it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_fixed_checkout_path_without_env(monkeypatch, restore_jax_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    repo = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert got == str(repo / ".jax_cache")
    assert (repo / "src" / "repro" / "compile_cache.py").exists()
    # the same path on every call and in every process: no pid, time, tmp
    assert compile_cache.enable_compile_cache() == got


def test_small_compiles_are_cached(monkeypatch, restore_jax_config):
    """The serving kernels compile in well under JAX's 1 s default."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_checkout_cache_is_git_ignored():
    repo = compile_cache.CHECKOUT_CACHE_DIR.parent
    ignored = (repo / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compiles_land_in_env_dir(tmp_path):
    """End to end, in a fresh process: with JAX_COMPILATION_CACHE_DIR set,
    a small compile is written there and the checkout path is not used."""
    code = ("import jax, jax.numpy as jnp; "
            "from repro.compile_cache import enable_compile_cache; "
            "print(enable_compile_cache()); "
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_import_changes_no_config():
    code = ("import jax; before = jax.config.jax_compilation_cache_dir; "
            "import repro.compile_cache; "
            "assert jax.config.jax_compilation_cache_dir == before")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
    assert importlib.import_module("repro.compile_cache")
