"""Persistent XLA compilation cache for this repo's entry points.

Called by scripts (``chip_smoke.py``, ``examples/``, ``perf/``,
``__graft_entry__.py``) before their first compile — never at
``import tpusnap``: a library must not redirect its host program's
cache. The directory is part of every cache key's lookup, so it must
not move between runs: it is where ``JAX_COMPILATION_CACHE_DIR`` says,
or else one fixed directory in the checkout — never a temp dir, a pid
or a timestamp.
"""

from __future__ import annotations

import os

_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    the directory setting is left alone. Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored). Either way the size and
    compile-time thresholds are dropped, so that small programs (the
    slab-pack programs a take compiles, a kernel on its own) are
    written beside the train step."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
