"""Persistent XLA compilation cache setup (shared, idempotent).

The engine's shapes are deliberately bucketed (pow2 batch buckets in
the service path, pow2 string/unique-row tables in capture replay)
precisely so they repeat — but without a persistent cache every fresh
PROCESS recompiles all of them, and every daemon restart pays the
same. One call, before or after jax import, points every process at
one on-disk cache.

The location is fixed, because the path is part of the cache key: a
directory that moves between runs never hits.

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself, and this
  module sets no other directory.
* otherwise: ``<repo>/.jax_cache`` inside the checkout (listed in
  ``.gitignore``) — nothing outside the checkout is written.

Reference analog: compiled-datapath reuse across agent restarts
(``pkg/datapath/loader``'s object cache keyed by template hash); the
artifact cache in ``runtime/loader.py`` plays that role for staged
POLICY tensors, this one for XLA executables.
"""

from __future__ import annotations

import os

#: the in-checkout cache directory used when JAX_COMPILATION_CACHE_DIR
#: is unset
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_done = False


def cache_dir() -> str:
    """The directory the persistent cache lives in."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR


def enable_persistent_cache() -> None:
    """Point jax at the persistent compilation cache (see the module
    docstring for where it lives)."""
    global _done
    if _done:
        return
    _done = True
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # 0.1s, not the 1s default: the capture-staging programs (fused
    # table scan, memo gather) compile in 0.1-0.5s and sat under the
    # default bar — every fresh process recompiled all of them.
    # Sub-0.1s programs stay uncached (disk round-trip wouldn't pay).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
