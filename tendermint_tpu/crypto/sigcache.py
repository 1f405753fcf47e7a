"""Bounded verified-signature cache for the gossip vote-drain paths.

Gossip delivers the same vote from several peers: without a cache every copy
re-pays a kernel or scalar verification before the duplicate check in
VoteSet.add_vote drops it (the reference pays the same tax -- one scalar
verify per gossiped copy, types/vote_set.go:205). A verification result is a
pure function of the (pubkey, message, signature) triple, so a bounded LRU
of known-good triples lets repeat deliveries skip straight to the serial
accept-replay.

Design constraints:

 * Keys are SHA-256 digests of pubkey||msg||sig (length-framed, so no
   concatenation of a different triple can collide), 32 bytes per entry --
   the vote bytes themselves are never retained.
 * ONLY positive results are cached, and only from a RESOLVED bitmap: a
   dispatch that degrades through the circuit breaker still resolves to a
   host-verified bitmap (safe to cache), while a resolve that raises caches
   nothing -- an injected device failure (TMTPU_FAULTS) can therefore never
   poison the cache, and a tampered signature (bitmap False) is never
   remembered as valid.
 * Bounded: least-recently-used eviction at the cap.

Knobs: TM_TPU_SIGCACHE=0 disables; TM_TPU_SIGCACHE_CAP sets the entry cap
(default 65536; ~2 MiB of digests at the default). Hits/misses export as
sigcache_hits_total / sigcache_misses_total (utils/metrics.py).
"""

from __future__ import annotations

import hashlib
import os
import struct
import threading
from collections import OrderedDict

DEFAULT_CAP = 65536


def cache_key(pub: bytes, msg: bytes, sig: bytes) -> bytes:
    """SHA-256 of the length-framed triple."""
    h = hashlib.sha256(struct.pack("<II", len(pub), len(msg)))
    h.update(pub)
    h.update(msg)
    h.update(sig)
    return h.digest()


class SigCache:
    """Thread-safe LRU set of verified-signature digests."""

    def __init__(self, cap: int = DEFAULT_CAP):
        self.cap = cap
        self._od: OrderedDict[bytes, bool] = OrderedDict()
        self._mtx = threading.Lock()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._od)

    def lookup(self, key: bytes) -> bool:
        """True when `key` is a known-verified triple (LRU-refreshed).
        Counts locally only -- DrainCache batches the node-metrics mirror
        once per drain, so the hot vote path never pays a per-signature
        metrics-mutex acquisition."""
        with self._mtx:
            present = key in self._od
            if present:
                self._od.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
        return present

    def hit(self, key: bytes) -> bool:
        """lookup() plus an immediate node-metrics mirror (standalone
        callers outside a drain)."""
        present = self.lookup(key)
        _count(present)
        return present

    def add(self, key: bytes) -> None:
        """Record a POSITIVELY verified triple; evicts LRU beyond the cap."""
        with self._mtx:
            self._od[key] = True
            self._od.move_to_end(key)
            while len(self._od) > self.cap:
                self._od.popitem(last=False)

    def clear(self) -> None:
        with self._mtx:
            self._od.clear()
            self.hits = 0
            self.misses = 0


def _count(hit: bool) -> None:
    from tendermint_tpu.utils import metrics as tmmetrics

    m = tmmetrics.GLOBAL_NODE_METRICS
    if m is not None:
        (m.sigcache_hits if hit else m.sigcache_misses).add()


class DrainCache:
    """Per-flush consult-and-populate accumulator for the vote-drain call
    sites (ConsensusState._handle_vote_batch, VoteSet.add_votes). Owns THE
    cache-safety invariant in one place: only POSITIVE lanes of a RESOLVED
    bitmap ever enter the cache (``commit`` runs after resolve; a resolve
    that raises never reaches it).

    ``check(i, ...)`` either records index ``i`` as cache-verified (True)
    or records the triple's key aligned with the caller's verify queue
    (False -> caller queues item ``i``); ``commit(queued, bitmap)`` caches
    the positives, flushes the batched hit/miss metrics deltas (ONE counter
    add per drain, not one per vote), and returns the merged
    {index: verified} map."""

    __slots__ = ("_cache", "cached_ok", "_ckeys", "_hits", "_misses")

    def __init__(self):
        self._cache = get()
        self.cached_ok: dict[int, bool] = {}
        self._ckeys: list[bytes | None] = []
        self._hits = 0
        self._misses = 0

    def check(self, i: int, pub: bytes, msg: bytes, sig: bytes) -> bool:
        if self._cache is not None:
            ck = cache_key(pub, msg, sig)
            if self._cache.lookup(ck):
                self._hits += 1
                self.cached_ok[i] = True
                return True
            self._misses += 1
        else:
            ck = None
        self._ckeys.append(ck)
        return False

    def copies_queued(self) -> int:
        """Triples this flush queued more than once: copies that fell into
        the drain that holds their original, which the cache cannot answer
        because it learns a triple only when the flush resolves."""
        return len(self._ckeys) - len(set(self._ckeys))

    def commit(self, queued: list, bitmap) -> dict:
        self._flush_metrics()
        if self._cache is not None:
            for ok, ck in zip(bitmap, self._ckeys):
                if ok and ck is not None:
                    self._cache.add(ck)
        out = dict(self.cached_ok)
        out.update(zip(queued, bitmap))
        return out

    def _flush_metrics(self) -> None:
        if not (self._hits or self._misses):
            return
        from tendermint_tpu.utils import metrics as tmmetrics

        m = tmmetrics.GLOBAL_NODE_METRICS
        if m is not None:
            if self._hits:
                m.sigcache_hits.add(self._hits)
            if self._misses:
                m.sigcache_misses.add(self._misses)
        self._hits = self._misses = 0


_CACHE: SigCache | None = None
_CACHE_LOCK = threading.Lock()


def get() -> SigCache | None:
    """The process-wide cache, or None when disabled (TM_TPU_SIGCACHE=0).
    The cap (TM_TPU_SIGCACHE_CAP) is read at first use."""
    if os.environ.get("TM_TPU_SIGCACHE") == "0":
        return None
    global _CACHE
    if _CACHE is None:
        with _CACHE_LOCK:
            if _CACHE is None:
                cap = int(os.environ.get("TM_TPU_SIGCACHE_CAP", DEFAULT_CAP))
                _CACHE = SigCache(cap)
    return _CACHE


def reset() -> None:
    """Drop the process-wide cache (tests; also re-reads the cap knob)."""
    global _CACHE
    with _CACHE_LOCK:
        _CACHE = None
