"""Where compiled programs are kept between runs.

Every entry point (the apps, ``caffe``, ``time_net``, ``tools/serve.py``,
``bench.py``, the profiling tools, ``chip_smoke.py``) calls
:func:`use_compile_cache` before it builds a model, so a cold CaffeNet
compile is paid once per checkout, not once per process.

The directory is part of a cache entry's identity for whoever must find
it again, so it is never derived from a temporary name, a pid or the
clock: it is ``JAX_COMPILATION_CACHE_DIR`` when the caller's environment
sets one (JAX reads that variable itself; nothing is set in code then),
and ``<checkout>/.jax_cache`` otherwise.  Either way an entry is keyed by
its program with its debug information (see :func:`use_compile_cache`).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax

    # An entry's key leaves debug information out unless asked, and the
    # named scopes by which a device trace names layers (``L[conv1]``,
    # ``L[augment]``) are debug information: an executable cached before a
    # scope existed, or by another checkout, would be served with the
    # names it was compiled with.  With it in the key a trace never names
    # a stale scope.  The price: source locations are in the key too, so
    # an edit that moves traced lines compiles again, and so does another
    # entry point for the same program (a shared sub-computation keeps
    # the locations of whoever traced it first in the process).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

