"""JAX's persistent compilation cache for the repo's entry points.

Every command that compiles (the service and measure CLIs, the benchmark
runner, ``chip_smoke.py``) calls :func:`enable_compile_cache` from its
``main`` -- never on import -- so a second process with the same program
and shapes loads executables instead of recompiling them.
"""

from __future__ import annotations

import os
import sys

#: the checkout root (``src/repro/compile_cache.py`` -> three levels up):
#: a fixed path, because the cache directory is part of every entry's key.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, wins: JAX reads it itself and
    no other directory is set. Otherwise the cache lives in
    ``<checkout>/.jax_cache``. Every compile is cached, not only those
    over JAX's one-second default: a sweep compiles in about half a
    second. Before JAX is imported the settings go through the
    environment, so a command that never touches JAX does not pay for
    importing it.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache"
    )
    settings = {
        "jax_compilation_cache_dir": path,
        "jax_persistent_cache_min_compile_time_secs": 0.0,
    }
    if "jax" in sys.modules:  # JAX read the environment when it was imported
        import jax

        for name, value in settings.items():
            jax.config.update(name, value)
    else:
        for name, value in settings.items():
            os.environ[name.upper()] = str(value)
    return path
