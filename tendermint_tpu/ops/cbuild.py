"""Naming of the lazily built C libraries (ops/chost, ops/chash).

Both are compiled with ``-march=native`` where the compiler accepts it, so a
binary is only valid on a CPU with the features of the one that built it: a
copy of the tree made on another machine would otherwise dlopen it by name
and die with SIGILL inside the self-test instead of returning. The file name
therefore digests everything the binary depends on -- the sources, the
compile recipe and the host CPU's feature flags -- and a library built
elsewhere is simply not found and gets rebuilt from ``csrc/*.c`` where it
runs. ``csrc/*.so`` is gitignored for the same reason.
"""

from __future__ import annotations

import functools
import hashlib
import os
import platform

CSRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "csrc"))


@functools.cache
def host_cpu_tag() -> str:
    """The machine architecture plus the CPU feature flags the kernel
    reports (x86 ``flags`` / arm ``Features`` line of /proc/cpuinfo; the
    model string where that file is absent)."""
    feats = ""
    try:
        with open("/proc/cpuinfo", "r", encoding="ascii", errors="replace") as f:
            for line in f:
                key, _, val = line.partition(":")
                if key.strip() in ("flags", "Features"):
                    feats = " ".join(sorted(val.split()))
                    break
    except OSError:
        pass
    return f"{platform.machine()}|{feats or platform.processor()}"


def lib_path(stem: str, sources: list[str], recipe: list) -> str:
    """csrc/<stem>-<digest>.so for these source files, this compile recipe
    (every candidate command line, in the order tried) and this host's CPU."""
    h = hashlib.sha256()
    for p in sources:
        with open(p, "rb") as f:
            h.update(f.read())
    h.update(repr(recipe).encode())
    h.update(host_cpu_tag().encode())
    return os.path.join(CSRC, f"{stem}-{h.hexdigest()[:12]}.so")
