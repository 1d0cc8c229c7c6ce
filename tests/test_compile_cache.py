"""The entry points' persistent compilation cache: JAX_COMPILATION_CACHE_DIR
where set, else ``<checkout>/.jax_cache``, whether JAX was imported before
the helper ran or after. Each case runs in a child process, so no test
worker's JAX configuration changes."""

import json
import os
import subprocess
import sys

import pytest

from repro.compile_cache import CHECKOUT

_CHILD = """
import json, os, sys
if {jax_first}:
    import jax
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
import jax, jax.numpy as jnp
if {compile}:
    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(3)).block_until_ready()
print(json.dumps({{"path": path, "config": jax.config.jax_compilation_cache_dir,
                  "min_s": jax.config.jax_persistent_cache_min_compile_time_secs}}))
"""


def _child(env_dir, jax_first, compile_):
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_COMPILATION")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(CHECKOUT, "src") + os.pathsep + env.get("PYTHONPATH", "")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = _CHILD.format(jax_first=jax_first, compile=compile_)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=120, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("jax_first", [False, True])
def test_cache_goes_where_the_environment_says(tmp_path, jax_first):
    cache = tmp_path / "cc"
    got = _child(cache, jax_first, compile_=True)
    assert got["path"] == got["config"] == str(cache)
    assert got["min_s"] == 0.0
    assert any(cache.iterdir()), "no cache entry was written"


@pytest.mark.parametrize("jax_first", [False, True])
def test_cache_defaults_to_the_checkout(jax_first):
    got = _child(None, jax_first, compile_=False)
    want = os.path.join(CHECKOUT, ".jax_cache")
    assert got["path"] == got["config"] == want
    assert got["min_s"] == 0.0
