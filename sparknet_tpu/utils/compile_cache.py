"""Where compiled programs are kept between runs.

Every entry point (the apps, ``caffe``, ``time_net``, ``tools/serve.py``,
``bench.py``, the profiling tools, ``chip_smoke.py``) calls
:func:`use_compile_cache` before it builds a model, so a cold CaffeNet
compile is paid once per checkout, not once per process.

The directory is part of a cache entry's identity for whoever must find
it again, so it is never derived from a temporary name, a pid or the
clock: it is ``JAX_COMPILATION_CACHE_DIR`` when the caller's environment
sets one (JAX reads that variable itself; nothing is set in code then),
and ``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

