"""The persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR
says, and otherwise at one fixed, git-ignored path in the checkout."""
import os
import subprocess
import sys

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print("DIR", enable_compile_cache())
print(jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).sum())
"""


def test_env_dir_is_used_and_nothing_else(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run([sys.executable, "-c", CHILD], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"DIR {tmp_path}" in r.stdout
    assert os.listdir(tmp_path)


def test_default_is_fixed_ignored_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
