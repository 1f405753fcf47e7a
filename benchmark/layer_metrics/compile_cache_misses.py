"""Programs this process had to compile (jax.monitoring cache events)."""


def read(run):
    return run.setup.get("compile_cache_misses")
