"""What the run ran on, what it may not run with, and the compile cache."""

from __future__ import annotations

import os

PROGRAM_ENV_PREFIXES = ("TM_TPU_", "TMTPU_")
# tests/conftest.py exports this one to every test process and its children
REHEARSE_TOLERATES = {"TM_TPU_SKIP_WARMUP"}


def program_env(rehearse: bool) -> list[str]:
    """The program's own variables that are set: a cell measures what a user
    gets by default, so any of them refuses the run."""
    found = sorted(k for k in os.environ if k.startswith(PROGRAM_ENV_PREFIXES))
    if rehearse:
        found = [k for k in found if k not in REHEARSE_TOLERATES]
    return found


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip; 0 where the backend reports none (CPU)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CacheWatch:
    """jax's persistent-compile-cache hits and misses in this process
    (jax.monitoring events; a copy of chip_smoke.CacheWatch). Create it after
    an ops module was imported (that places the cache, utils/jaxcache.py) and
    before the first compile."""

    def __init__(self) -> None:
        import jax

        self.dir = jax.config.jax_compilation_cache_dir
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"dir": self.dir, "hits": self.hits, "misses": self.misses}
