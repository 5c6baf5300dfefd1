"""The persistent compilation cache is placed from outside the program
(launch/compile_cache.py): JAX_COMPILATION_CACHE_DIR wins, else a fixed
git-ignored directory at the repo root."""
import subprocess
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_is_left_to_jax(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "elsewhere")
    assert compile_cache.use_compile_cache() == "elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_repo_jax_cache_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                             cwd=REPO)
    assert ignored.returncode in (0, 128)   # 128: not a git checkout
