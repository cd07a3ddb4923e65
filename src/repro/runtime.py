"""Process-level JAX settings shared by the entry points that run on a chip."""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["use_compile_cache"]


def use_compile_cache(root: str | os.PathLike) -> Path:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache lives at ``<root>/.jax_cache`` — a
    fixed path, never a temporary, per-process or timed name, so a later
    run from the same checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    import jax

    path = Path(root).resolve() / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
