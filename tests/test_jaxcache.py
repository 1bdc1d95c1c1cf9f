"""Compile-cache placement (``utils/jaxcache.py``): the directory is placed
from outside — ``JAX_COMPILATION_CACHE_DIR`` wins untouched, otherwise the
cache lives at ``<checkout>/.jax_cache``. Run in subprocesses: the helper
changes process-wide jax config."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_PROBE = (
    "import json, os, jax\n"
    "from xaynet_tpu.utils import jaxcache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "path = jaxcache.enable_compile_cache()\n"
    "import jax.numpy as jnp\n"
    "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n"
    "print(json.dumps({'before': before, 'path': path,\n"
    "    'config': jax.config.jax_compilation_cache_dir,\n"
    "    'env': os.environ.get('JAX_COMPILATION_CACHE_DIR'),\n"
    "    'report': jaxcache.compile_report()}))\n"
)


def _probe(env_extra: dict) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO), **env_extra)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, capture_output=True, text=True,
        timeout=120, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_var_set_leaves_the_directory_alone(tmp_path):
    placed = str(tmp_path / "placed")
    got = _probe({"JAX_COMPILATION_CACHE_DIR": placed})
    assert got["before"] == placed  # jax read the variable itself
    assert got["path"] == got["config"] == got["env"] == placed
    assert got["report"]["cache_dir"] == placed
    # the cache is really on: the probe's one jit landed there
    assert got["report"]["cache_writes"] >= 1
    assert got["report"]["cache_entries_now"] > got["report"]["cache_entries_start"]
    assert not (REPO / ".jax_cache" / "placed").exists()


def test_env_var_unset_uses_the_checkout_cache():
    got = _probe({})
    assert got["before"] is None and got["env"] is None
    assert got["path"] == got["config"] == str(REPO / ".jax_cache")
    assert os.path.isdir(got["path"])
    assert got["report"]["compiles"] >= 1


def test_second_process_hits_what_the_first_built(tmp_path):
    placed = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c")}
    cold = _probe(placed)["report"]
    warm = _probe(placed)["report"]
    assert cold["cache_writes"] >= 1 and cold["cache_hits"] == 0
    assert warm["cache_writes"] == 0 and warm["cache_hits"] >= 1
    assert warm["cache_entries_now"] == warm["cache_entries_start"]
