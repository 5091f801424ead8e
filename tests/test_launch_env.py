"""Where the persistent compilation cache lives (``repro.launch.env``).

``JAX_COMPILATION_CACHE_DIR`` wins whenever it is set; otherwise the cache
is the fixed, git-ignored ``<repo>/.jax_cache``.  The cache is process-wide
state, so the writes are checked in a child process pinned to the CPU.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import env as env_mod

ROOT = Path(__file__).resolve().parents[1]

_SCRIPT = """
import sys
import jax, jax.numpy as jnp
from repro.launch import env
if len(sys.argv) > 1:
    env.REPO_CACHE_DIR = sys.argv[1]     # stand-in for <repo>/.jax_cache
print(env.enable_compilation_cache())
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


def _run(tmp_path, env_dir, fallback_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(fallback_dir)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_env_var_directory_wins(tmp_path):
    env_dir, fallback = tmp_path / "from_env", tmp_path / "fallback"
    used = _run(tmp_path, env_dir, fallback)
    assert used == str(env_dir)
    assert any(env_dir.iterdir()), "no cache entry written to the env dir"
    assert not fallback.exists()


def test_fallback_directory_when_env_var_unset(tmp_path):
    fallback = tmp_path / "fallback"
    used = _run(tmp_path, None, fallback)
    assert used == str(fallback)
    assert any(fallback.iterdir()), "no cache entry written to the fallback"


def test_fallback_is_the_git_ignored_repo_directory(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert env_mod.compilation_cache_dir() == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("value", ["/some/dir", "relative/dir"])
def test_cache_dir_reads_the_env_var(monkeypatch, value):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", value)
    assert env_mod.compilation_cache_dir() == value
