"""JAX's persistent compilation cache, placed so that it can hit.

Entry scripts (chip_smoke.py, bench*.py) call `enable_compile_cache()`
before their first compile; `import paddle_tpu` never does. The
directory is part of the cache's key, so it must not move between
runs: where `JAX_COMPILATION_CACHE_DIR` is set JAX already uses it and
no other directory is named here; where it is not, the cache lives at
one fixed path inside the checkout (`<repo>/.jax_cache`, ignored by
git).
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile, however short,
    and return the directory it lives in."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(repo, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
