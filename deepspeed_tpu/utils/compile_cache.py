"""Where the persistent XLA compilation cache lives: one rule for every
entry point (``benchmark/run.py``, ``chip_smoke.py``,
``RaggedInferenceEngine.warmup``).

The directory is part of the cache key, so a directory that moves never hits:
no path is built from a pid, a time or a temp name.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and no directory
    is set in code. Unset, on the chip: ``<checkout>/.jax_cache``. Unset, on
    the CPU: no cache (returns None) — cache-deserialized CPU collective
    programs can deadlock the emulated test mesh (tests/conftest.py), so a
    CPU run caches only where its caller names a directory.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    if jax.default_backend() != "tpu":
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
