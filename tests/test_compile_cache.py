"""The persistent compilation cache sits at a fixed path (compile_cache.py).

With `JAX_COMPILATION_CACHE_DIR` set, JAX's own setting is left alone
and the cache is written there; without it the cache goes to
`<checkout>/.jax_cache`, which git ignores.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache
from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parent.parent


def test_environment_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        # the same path on every call: no temporary name, pid or time
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


_COMPILE_ONE = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro import compile_cache
if len(sys.argv) > 1:
    compile_cache.DEFAULT_CACHE_DIR = Path(sys.argv[1])
compile_cache.enable_compile_cache()
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _compile_in_child(env_dir=None, default_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [sys.executable, "-c", _COMPILE_ONE]
    if default_dir is not None:
        argv.append(str(default_dir))
    out = subprocess.run(argv, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_cache_is_written_to_the_environment_dir(tmp_path):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    _compile_in_child(env_dir=env_dir, default_dir=default_dir)
    assert any(env_dir.iterdir())
    assert not default_dir.exists()


def test_cache_is_written_to_the_default_dir(tmp_path):
    default_dir = tmp_path / "default"
    _compile_in_child(default_dir=default_dir)
    assert any(default_dir.iterdir())
    assert compile_cache.DEFAULT_CACHE_DIR == REPO / ".jax_cache"
