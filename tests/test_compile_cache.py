"""The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to the fixed, git-ignored ``<repo>/.jax_cache`` — and
nowhere else (``launch/compile_cache.py``)."""
import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compiled_programs_land_in_the_env_dir(tmp_path):
    """A fresh process that sets the variable finds its programs there."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
        PYTHONPATH=str(REPO / "src"),
    )
    code = (
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "import jax\n"
        "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], env=env, check=True, timeout=120,
        capture_output=True,
    )
    assert any(tmp_path.iterdir())
