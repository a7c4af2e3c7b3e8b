"""Place JAX's persistent compilation cache.

Every entry point that compiles for the chip (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``, the benchmarks) calls
``enable()`` once at start-up. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
the cache lives there and nowhere else. Otherwise it lives at a fixed
path inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the
directory is part of the cache key, so it is never built from a temp
name, a pid or the time.

No jax import at module level: ``launch/dryrun.py`` must set
``XLA_FLAGS`` before jax is first imported.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns its path. Kernels compile in
    about a second, so every compile is kept."""
    import jax
    path = os.environ.get(ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
