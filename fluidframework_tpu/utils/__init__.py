"""Shared small utilities."""

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache — every entry point
    calls this first. The directory comes from outside: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no code
    names another; unset, the cache lives at ``<checkout>/.jax_cache``
    (a fixed path: the path is part of the cache key). Thresholds go to
    zero so the many small AOT entries of ``parallel/aot.py`` are kept.
    Returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (shape bucketing: jit caches per shape,
    so padded dims must come from a small closed set)."""
    p = 1
    while p < n:
        p *= 2
    return p
