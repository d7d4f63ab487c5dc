"""Where ``repro.compile_cache.enable()`` puts the persistent cache.

Each case runs one compile in a fresh interpreter (JAX reads
``JAX_COMPILATION_CACHE_DIR`` once, at import) with ``HOME`` and ``TMPDIR``
pointed at empty directories, then checks that cache files landed in the
expected directory and nowhere else.
"""
import os
import pathlib
import subprocess
import sys

import pytest

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

_PROG = """
import sys, pathlib
import jax, jax.numpy as jnp
from repro import compile_cache
if len(sys.argv) > 1:
    compile_cache.DEFAULT_DIR = pathlib.Path(sys.argv[1])
print(compile_cache.enable())
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
"""


def _compile_once(tmp_path, env_dir=None, default_dir=None):
    home, tmp = tmp_path / "home", tmp_path / "tmp"
    home.mkdir()
    tmp.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(HOME=str(home), TMPDIR=str(tmp), JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [sys.executable, "-c", _PROG]
    if default_dir is not None:
        argv.append(str(default_dir))
    out = subprocess.run(argv, env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert not any(home.rglob("*")) and not any(tmp.rglob("*"))
    return out.stdout.split()[-1]


def test_default_dir_is_fixed_under_the_repo():
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_files_land_in_one_directory(tmp_path, from_env):
    """With the variable set, its directory holds the cache and the default
    stays empty; without it, the default directory holds the cache."""
    env_dir, default_dir = tmp_path / "env_cache", tmp_path / "default_cache"
    got = _compile_once(tmp_path, env_dir if from_env else None, default_dir)
    want, other = (env_dir, default_dir) if from_env else (default_dir, env_dir)
    assert got == str(want)
    assert any(want.iterdir())
    assert not other.exists()
