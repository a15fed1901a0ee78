"""Where JAX's persistent compilation cache lives — decided in ONE place.

The cache directory is part of the cache key, so a directory that moves
(temporary, per-process, dated) never hits. The rule: the environment
places it (`JAX_COMPILATION_CACHE_DIR`, which JAX reads itself — nothing
is set in code then); otherwise it is `<checkout>/.jax_cache`, a fixed
path that `.gitignore` already lists. Used by the entry points that
compile many programs per run (`chip_smoke.py`, `benchmark/run.py`)."""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory. Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
