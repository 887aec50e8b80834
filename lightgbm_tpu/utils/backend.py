"""Where JAX's persistent compilation cache lives.

The whole-tree grower is one large XLA program whose cold compile costs
minutes; the persistent cache turns that into a one-time cost per (shape,
params, platform).  The cache directory is part of the cache key's
lifetime — a directory that moves never hits — so the rule is fixed:

* `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and NOTHING in this
  package re-points `jax_compilation_cache_dir` (not the import hook, not
  `tpu_compile_cache_dir`).  The operator placed the cache; it stays there.
* unset: `<checkout>/.jax_cache` (git-ignored), the same path in every
  process of a checkout.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache(cache_dir: Optional[str] = None,
                             min_compile_time_secs: float = 1.0) -> str:
    """Turn on JAX's persistent compilation cache; returns the directory
    in force.

    `cache_dir` (the `tpu_compile_cache_dir` option) is honored only where
    `JAX_COMPILATION_CACHE_DIR` is unset.  min_compile_time_secs gates
    which programs get written: the package-import default keeps jax's 1s
    floor (don't litter the cache with trivial jits), while the explicit
    `tpu_compile_cache_dir` path passes 0 so EVERY program of a run
    replays warm.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if os.environ.get(CACHE_ENV):
        return str(jax.config.jax_compilation_cache_dir)
    cache_dir = cache_dir or default_cache_dir()
    prev_dir = jax.config.jax_compilation_cache_dir
    if prev_dir != cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        if prev_dir:
            # the cache singleton latches its directory at first use, so
            # re-pointing the config after any compile keeps writing to
            # the OLD dir unless the singleton resets
            import jax._src.compilation_cache as _cc

            _cc.reset_cache()
    return cache_dir
