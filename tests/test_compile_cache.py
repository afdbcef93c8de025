"""The entry points' persistent compilation cache lands where it should:
in ``JAX_COMPILATION_CACHE_DIR`` when that is set, and nowhere else;
otherwise in the checkout's git-ignored ``.jax_cache/``."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import cache

ROOT = Path(__file__).resolve().parents[1]

PROG = """
import sys
from pathlib import Path
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.launch import cache
cache.REPO_CACHE = Path({repo_cache!r})
print(cache.enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
"""


def _entries(d: Path) -> list:
    return [p for p in d.rglob("*") if p.is_file()] if d.exists() else []


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_directory(tmp_path, env_set):
    env_dir, repo_cache = tmp_path / "env-cache", tmp_path / "repo-cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    # cache even this millisecond CPU compile
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", PROG.format(src=str(ROOT / "src"),
                                           repo_cache=str(repo_cache))],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    used, unused = (env_dir, repo_cache) if env_set \
        else (repo_cache, env_dir)
    assert out.stdout.split()[-1] == str(used)
    assert _entries(used)
    assert not _entries(unused)


def test_default_cache_is_ignored_checkout_dir():
    assert cache.REPO_CACHE == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
