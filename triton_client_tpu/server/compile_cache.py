"""Placement of JAX's persistent compilation cache.

Every process that compiles for the device — the CLI server,
``ServerHarness``, ``chip_smoke.py``'s children — calls
:func:`enable_compile_cache` before its first compile, so a restart (or the
next process on the same machine) finds the 1b decode steps, BERT's batch
buckets and the pallas kernels already built instead of paying minutes of
XLA and Mosaic again.

The directory is part of how a deployment finds its cache again, so it is
never derived from a pid, a timestamp or ``tempfile``:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself; this
  module touches nothing, so whoever placed the cache from outside wins.
* unset — one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored).

Nothing else in the tree may set a cache directory.
"""

from __future__ import annotations

import os

from .._native import _REPO_ROOT

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` does not say.
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring for which) and return that directory.  Idempotent;
    call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
