"""Persistent XLA compilation cache.

The batch-verify kernels take tens of seconds to compile cold; a node must
not pay that on every restart, and the test suite must not pay it on every
run. jax's persistent compilation cache stores serialized executables keyed
by HLO fingerprint, so every compile after the first is a disk read.

Where the cache lives:

 * ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this module
   sets nothing -- the operator (or the machine image) owns the placement.
 * otherwise: ``.jax_cache/`` at the root of the checkout (gitignored). The
   path is part of the cache key, so it is one fixed directory: never under
   ``~`` or a temp dir, never named after a pid or the time. A copy of the
   tree carries its cache with it.

Called from ops/ed25519_batch import (any process that might touch a
kernel). TM_TPU_JAX_CACHE=0 turns the in-checkout default off.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".jax_cache"))

_done = False


def enable() -> None:
    global _done
    if _done:
        return
    _done = True
    if os.environ.get("TM_TPU_JAX_CACHE", "1") == "0":
        return
    import jax

    if jax.config.jax_compilation_cache_dir:
        # JAX_COMPILATION_CACHE_DIR (jax read it at import), or set in code
        # by an embedding program: theirs, untouched
        return
    try:
        os.makedirs(CACHE_DIR, exist_ok=True)
    except OSError:
        return  # read-only checkout: run uncached
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
