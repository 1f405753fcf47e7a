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

The same call registers the start-up ring's jax listener: what the cache
does NOT save (tracing a function and lowering it, loading an executable)
is what a warm process still pays, and utils/trace.STARTUP records it.
"""

from __future__ import annotations

import os
import threading
import time

from tendermint_tpu.utils import trace

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".jax_cache"))

_done = False


# jax.monitoring duration events (names as in jax 0.9.0) -> start-up span.
# backend_compile_duration contains the cache retrieval on a hit, so a sum
# of jit_compile must not add cache_load again.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_STARTUP_SPANS = {
    _TRACE_EVENT: "startup.jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "startup.jit_trace",
    "/jax/core/compile/backend_compile_duration": "startup.jit_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "startup.cache_load",
}
# .depth: traces open on this thread; .cpu0: event -> the thread's CPU clock
# at the start of each open region of that event
_tracing = threading.local()


def _on_start(event: str, _start_time: float, **_kw) -> None:
    """jax announces the start of a timed region as a scalar event."""
    if event == _TRACE_EVENT:
        _tracing.depth = depth = getattr(_tracing, "depth", 0) + 1
        if depth > 1:
            return
    if event in _STARTUP_SPANS:
        starts = _tracing.__dict__.setdefault("cpu0", {})
        starts.setdefault(event, []).append(time.thread_time())


def _on_duration(event: str, duration: float, **kw) -> None:
    """jax reports a duration when the work ends, so record()'s default
    start (now - duration) is the right one here. Every jnp op inside a
    kernel is traced as a function of its own, thousands in one kernel's
    trace: only the outermost trace is recorded, which covers them. A
    region whose start was announced carries the thread's CPU seconds."""
    if event == _TRACE_EVENT:
        _tracing.depth = depth = getattr(_tracing, "depth", 1) - 1
        if depth > 0:
            return
    name = _STARTUP_SPANS.get(event)
    if name is not None:
        tags = {"fun": kw["fun_name"]} if "fun_name" in kw else {}
        cpu0 = getattr(_tracing, "cpu0", {}).get(event)
        trace.STARTUP.record(
            name, duration,
            cpu_s=time.thread_time() - cpu0.pop() if cpu0 else None, **tags)


def enable() -> None:
    global _done
    if _done:
        return
    _done = True
    import jax

    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if os.environ.get("TM_TPU_JAX_CACHE", "1") == "0":
        return
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
