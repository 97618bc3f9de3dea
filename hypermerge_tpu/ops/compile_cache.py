"""Where JAX's persistent compilation cache lives — decided ONCE.

A cold process pays seconds to minutes of XLA compile for the slab,
serve and live programs; with stable jit buckets a later process reloads
them from disk instead. That only works when the directory is set
before the FIRST program of the process compiles (an executable built
earlier never reaches the cache) and when the directory does not move
(the path is part of the cache key). So every module that jits goes
through `ensure()` — directly from its host entry, or via the `jit`
decorator below — and never from inside a traced function.

Placement:

- `JAX_COMPILATION_CACHE_DIR` set: JAX already reads it; this module
  sets NO directory (an operator or a driver places the cache).
- unset, accelerator backend: `<checkout>/.jax_cache`, derived from the
  package's own location (ignored by git).
- unset, CPU backend: no directory — CPU compiles are fast and the test
  suite must not fill the checkout. Decided from the platform the
  process observes, not from a flag.

The write thresholds are the same in every case: every executable is
cached, so a second process that runs the same programs has zero
persistent-cache misses to explain.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_platform: Optional[str] = None


def ensure() -> str:
    """Place the cache (first call only) and return the platform this
    process observes. Initializes the JAX backend — an accelerator
    process that cannot reach its chip fails HERE, loudly."""
    global _platform
    if _platform is not None:
        return _platform
    import jax

    platform = jax.default_backend()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if (
        not os.environ.get("JAX_COMPILATION_CACHE_DIR")
        and platform != "cpu"
    ):
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    _platform = platform
    return platform


def platform() -> Optional[str]:
    """The platform `ensure` observed, or None while this process has
    compiled nothing (a hub parent that stays off JAX reports none)."""
    return _platform


def jit(fun=None, **jit_kwargs):
    """`jax.jit` for module-level programs: every call goes through
    `ensure()` first, so the program's first compile finds the cache
    placed whichever module the process happens to enter through."""
    if fun is None:
        return functools.partial(jit, **jit_kwargs)
    import jax

    jitted = jax.jit(fun, **jit_kwargs)

    @functools.wraps(fun)
    def call(*args, **kwargs):
        ensure()
        return jitted(*args, **kwargs)

    call._cache_size = jitted._cache_size  # tests pin "no new compile"
    return call
