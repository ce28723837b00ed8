"""The persistent compilation cache: off on import, and where the entry
points put it when they switch it on."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHECK = """
import json, os, sys
import jax, jax.numpy as jnp
import repro.kernels.ops, repro.launch.serve, repro.launch.pareto
on_import = jax.config.jax_compilation_cache_dir
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
print(json.dumps({"on_import": on_import, "path": path,
                  "config": jax.config.jax_compilation_cache_dir,
                  "written": sorted(os.listdir(path))}))
"""


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CHECK], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_dir_is_the_only_cache(tmp_path):
    res = _run(tmp_path / "cache")
    # JAX reads the variable itself; the repo sets no other directory
    assert res["on_import"] == str(tmp_path / "cache")
    assert res["path"] == res["config"] == str(tmp_path / "cache")
    assert res["written"], "nothing was cached in $JAX_COMPILATION_CACHE_DIR"


@pytest.mark.parametrize("env_value", [None, ""])
def test_default_is_the_fixed_repo_dir(env_value):
    res = _run(env_value)
    assert not res["on_import"]           # importing repro sets no cache
    assert res["path"] == res["config"] == os.path.join(REPO, ".jax_cache")
    assert res["written"]
