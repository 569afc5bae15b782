"""One persistent XLA compile cache for every entry point of the program.

A chip call starts with no compiled code, and the served path compiles a
program per shape bucket inside the first ops that meet it.  Every entry
point (`chip_smoke.py`, `scripts/load.py|chaos.py|trace.py`,
``python -m ceph_tpu.cluster.vstart``) calls :func:`enable` before its
first JAX computation so those compiles are kept.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` if the caller set
it (JAX reads the variable itself; nothing here overrides it), otherwise
``<checkout>/.jax_cache``.  The path is part of the cache key, so it is
derived from this file's location and never from a temporary name, a pid
or the time.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory the persistent compile cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(CHECKOUT, ".jax_cache")


def native_dir() -> str:
    """Where the program keeps what it builds with the host's C compiler
    (``cluster/store.py``'s walk over a spare's pieces): in the checkout,
    beside ``.jax_cache``, whatever ``JAX_COMPILATION_CACHE_DIR`` says:
    the program writes nowhere around its checkout."""
    return os.path.join(CHECKOUT, ".native_cache")


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every compile: the Pallas kernels and the layout transforms
    # compile in well under JAX's default 1 s threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
