"""Where the persistent compilation cache lands (``enable_compile_cache``).

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX config. The checkout root is redirected to a temp directory so the
test never writes into the repository.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

_SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import pathlib, sys
    import repro.launch.compile_cache as cc
    cc._REPO = pathlib.Path(sys.argv[1])
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(cc.enable_compile_cache())
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(3)).block_until_ready()
""")


@pytest.mark.parametrize("env_dir", [False, True],
                         ids=["repo_default", "env_var"])
def test_cache_entries_land_in_one_directory(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=_SRC, JAX_PLATFORMS="cpu")
    repo_cache = tmp_path / ".jax_cache"
    want = repo_cache
    if env_dir:
        want = tmp_path / "from_env"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    res = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path)],
                         capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.strip() == str(want)
    assert any(want.iterdir()), "no cache entry written"
    made = {p.name for p in tmp_path.iterdir()}
    assert made == {want.name}, made  # and nowhere else under the root
