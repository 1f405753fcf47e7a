"""Batched ed25519 verification: the framework's north-star TPU kernel.

Replaces the reference's serial per-signature loop (~70-100us/sig on one CPU
core; reference crypto/ed25519/ed25519.go:148, called from types/vote_set.go:205
and types/validator_set.go:685-826) with one wide SIMD verification:

    host (vectorized over the whole batch; ops/scalar25519, ops/chash):
        size checks, S < L check, batched SHA-512 h = H(R||A||msg), h mod L,
        comb-window decomposition, R byte -> limb split
    device (the FLOPs):     R' = [s]B + [h](-A)  via a comb (Lim-Lee)
        evaluation: 64 shared doublings + 64+64 table additions, then
        canonical compression and a byte-exact compare against the sig's R.

Comb method (t=4 teeth, d=64 columns): scalar bits split into 4 blocks of 64;
T[w] = sum_j w_j * [2^(64j)] P for w in 0..15; evaluation
acc <- 2*acc + T_A[wh_i] + T_B[ws_i] for i = 63..0. This quarters the
doubling count vs per-signature Straus (256 -> 64), the dominant cost. The
per-key tables T_A depend only on the pubkey, so they are built ON DEVICE
once per KEY and kept in HBM as rows of one table per key type (KeyTable:
pubkey -> row). A batch is a list of row numbers: the validators of a live
chain sign in another subset at every height, and that costs a mapping, not
a build; per call only the per-sig scalars/windows and row numbers move
host->device.

Accept/reject is byte-identical with the scalar path (crypto/ed25519.py):
 - s >= L rejected (host);
 - non-decodable / non-canonical A rejected (host, same rules as scalar ref);
 - R never decompressed: the comparison is against the canonical encoding of
   R', so non-canonical R bytes fail exactly as in the scalar path;
 - h reduced mod L before the scalar mult (both paths), so small-order A
   components behave identically.

Batches are padded to power-of-two buckets to bound XLA recompiles; results
come back as a boolean bitmap (the analogue of the reference's
libs/bits.BitArray vote bitmap).
"""

from __future__ import annotations

import functools
import os
import threading
import time as _time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.utils import faults, jaxcache
from tendermint_tpu.utils import trace as _trace

jaxcache.enable()

from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.ops import breaker as _cbreaker
from tendermint_tpu.ops import chash
from tendermint_tpu.ops import edwards25519 as ed
from tendermint_tpu.ops import scalar25519 as sc

L = ref.L
P = ref.P

MIN_BUCKET = 64

# ---------------------------------------------------------------------------
# Fixed-base comb table for B (host, exact ints)
# ---------------------------------------------------------------------------


def _b_comb_affine() -> list[tuple[int, int]]:
    """T_B[w] = sum_j w_j * [2^(64j)] B as affine points, w = 0..15."""
    base = (ref.BASE[0], ref.BASE[1])
    pj = [base]
    for _ in range(3):
        p = pj[-1]
        for _ in range(64):
            p = ed.affine_add(p, p)
        pj.append(p)
    pts = []
    for w in range(16):
        acc = (0, 1)
        for j in range(4):
            if (w >> j) & 1:
                acc = ed.affine_add(acc, pj[j])
        pts.append(acc)
    return pts


_B_COMB_AFFINE = _b_comb_affine()
# Extended-coordinate form for the jnp kernel: (16, 4, 20).
TAB_B = np.stack([ed.from_affine(x, y) for (x, y) in _B_COMB_AFFINE])


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------


def _gather_point(table, idx):
    """table (N, 16, 4, 20), idx (N,) -> (N, 4, 20)."""
    n = table.shape[0]
    flat = table.reshape(n, 16, 80)
    got = jnp.take_along_axis(flat, idx[:, None, None].astype(jnp.int32), axis=1)
    return got.reshape(n, 4, 20)


def _verify_kernel(tab, h_win, s_win, r_y, r_sign, valid):
    """The jitted batch verify (pure-jnp path: what runs off a TPU backend).

    tab:    (N, 16, 4, 20) int32  comb table of -A per signature (cached)
    h_win:  (N, 64)    int32   comb windows of h, processing order
    s_win:  (N, 64)    int32   comb windows of s, processing order
    r_y:    (N, 20)    int32   raw y limbs of sig[:32] (bit 255 stripped)
    r_sign: (N,)       int32   bit 255 of sig[:32]
    valid:  (N,)       bool    host-side precheck results
    ->      (N,)       bool
    """
    n = tab.shape[0]
    tab_b = jnp.broadcast_to(jnp.asarray(TAB_B), (n, 16, 4, 20))

    def body(j, acc):
        acc = ed.double(acc)
        wh = jax.lax.dynamic_slice_in_dim(h_win, j, 1, axis=1)[:, 0]
        ws = jax.lax.dynamic_slice_in_dim(s_win, j, 1, axis=1)[:, 0]
        acc = ed.add(acc, _gather_point(tab, wh))
        acc = ed.add(acc, _gather_point(tab_b, ws))
        return acc

    acc0 = ed.identity((n,))
    acc = jax.lax.fori_loop(0, 64, body, acc0)

    y, sign = ed.compress_canonical(acc)
    ok = jnp.all(y == r_y, axis=-1) & (sign == r_sign)
    return ok & valid


# jax.jit caches one executable per input shape (= per padded bucket size).
_jnp_kernel = jax.jit(_verify_kernel)


def _dbl64(p):
    return jax.lax.fori_loop(0, 64, lambda _, q: ed.double(q), p)


# Comb-table recurrence indices: T[w] = T[w ^ lsb(w)] + ps[log2(lsb(w))].
# Rolled into a fori_loop (one traced point-add instead of 15) because the
# unified Edwards formula is complete: T[0] = identity participates safely.
_COMB_PREV = np.array([w ^ (w & -w) for w in range(16)], dtype=np.int32)
_COMB_J = np.array(
    [max((w & -w).bit_length() - 1, 0) for w in range(16)], dtype=np.int32
)


def _build_comb_tables_impl(a_neg):
    """(K, 4, 20) extended -A points -> (K, 16, 4, 20) comb tables."""
    k = a_neg.shape[0]
    ps0 = jnp.zeros((4, k, 4, 20), jnp.int32).at[0].set(a_neg)
    ps = jax.lax.fori_loop(
        0, 3, lambda j, ps: ps.at[j + 1].set(_dbl64(ps[j])), ps0
    )
    prev = jnp.asarray(_COMB_PREV)
    jj = jnp.asarray(_COMB_J)

    def body(w, tab):
        p = jnp.take(tab, prev[w], axis=1)
        return tab.at[:, w].set(ed.add(p, ps[jj[w]]))

    tab0 = (
        jnp.zeros((k, 16, 4, 20), jnp.int32)
        .at[:, 0].set(ed.identity((k,)))
    )
    return jax.lax.fori_loop(1, 16, body, tab0)


_build_comb_tables = jax.jit(_build_comb_tables_impl)

# Fixed compile shapes: XLA compiles one executable per input shape, and a
# cold compile of these limb-heavy graphs takes from seconds to minutes
# (chip_smoke.py prints what it measured). Chunking every batch through ONE
# (tile-sized) executable makes compilation a one-time cost per process
# regardless of batch size: a KEY_TILE of keys a build, on either backend
# (the jnp program below; on a TPU ed25519_pallas._build_kernel at the same
# shape), a JNP_TILE of signatures a jnp verify.
KEY_TILE = 256
JNP_TILE = 256


def pad_identity(a_neg: np.ndarray, rows: int) -> np.ndarray:
    """(K, 4, 20) limbs, K <= rows -> (rows, 4, 20), the rows past K holding
    the identity: what a build's padding lanes compute on."""
    padded = np.broadcast_to(ed.IDENTITY_LIMBS, (rows, 4, 20)).copy()
    padded[: a_neg.shape[0]] = a_neg
    return padded


def _build_comb_tables_tiled(a_neg: np.ndarray):
    """(K, 4, 20) -> (ceil(K/KEY_TILE)*KEY_TILE, 16, 4, 20), built in
    fixed-shape chunks so _build_comb_tables compiles exactly once."""
    k = a_neg.shape[0]
    kp = max(_round_up(k, KEY_TILE), KEY_TILE)
    padded = pad_identity(a_neg, kp)
    chunks = [
        _build_comb_tables(jnp.asarray(padded[o : o + KEY_TILE]))
        for o in range(0, kp, KEY_TILE)
    ]
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@jax.jit
def _gather_transpose(tab_rows, idx):
    """(Kb, R), (nb,) -> (R, nb) lane-major per-item tables.

    Gather along the MAJOR axis then transpose: a lane-axis gather is
    pathologically slow on TPU, a row gather + transpose is fast."""
    rows = jnp.take(tab_rows, idx, axis=0)  # (nb, R)
    return rows.T


@jax.jit
def _to_niels(tab_ext):
    """(Kb, 16, 4, 20) extended comb points -> (Kb, 960) niels rows
    (y+x | y-x | 2dxy per entry, affine via batched Z inversion).

    Niels form turns the kernel's per-entry table add from a 9-mul full
    extended add into a 7-mul mixed add AND shrinks the per-iteration table
    read by 25% (60 rows/entry vs 80). One batched inversion per key set,
    amortized across every height that reuses the set."""
    from tendermint_tpu.ops import field25519 as fe

    X, Y, Z = tab_ext[:, :, 0], tab_ext[:, :, 1], tab_ext[:, :, 2]
    zinv = fe.inv(Z)
    x = fe.mul(X, zinv)
    y = fe.mul(Y, zinv)
    ypx = fe.add(y, x)
    ymx = fe.sub(y, x)
    txy = fe.mul(fe.mul(x, y), jnp.asarray(ed.TWO_D_LIMBS))
    k = tab_ext.shape[0]
    niels = jnp.stack([ypx, ymx, txy], axis=2)  # (Kb, 16, 3, 20)
    return niels.reshape(k, 960)


# ---------------------------------------------------------------------------
# Key tables: one device-resident comb table per key type, a row per key
# ---------------------------------------------------------------------------

_decomp_cache: dict[bytes, np.ndarray | None] = {}


def _decompress_neg(pub: bytes) -> np.ndarray | None:
    """Cached: pubkey bytes -> extended limbs of -A, or None if invalid."""
    hit = _decomp_cache.get(pub)
    if hit is not None or pub in _decomp_cache:
        return hit
    pt = ref._decompress(pub)
    out = None
    if pt is not None:
        x, y, z, _ = pt
        assert z == 1
        out = ed.negate_affine(x, y)
    if len(_decomp_cache) < 1_000_000:
        _decomp_cache[pub] = out
    return out


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(tab, block, row0):
    """tab with block's rows written at row0, in place (tab is donated).
    The caller keeps row0 + len(block) <= len(tab): XLA clamps a start that
    would overrun, which would shift the block onto other keys' rows."""
    return jax.lax.dynamic_update_slice_in_dim(tab, block, row0, axis=0)


@jax.jit
def _read_tile(tab, row0):
    return jax.lax.dynamic_slice_in_dim(tab, row0, KEY_TILE, axis=0)


def _grown(tab, rows: int, row_shape: tuple, device=None):
    """A zeroed (rows, *row_shape) table holding tab's rows (tab may be
    None), on `device` (None: where unplaced arrays go)."""
    new = jnp.zeros((rows,) + row_shape, jnp.int32, device=device)
    return new if tab is None else _write_rows(new, tab, 0)


class KeySet:
    """One generation of a key type's device-resident comb table: row r holds
    the tables of the r-th key this generation met, and a signer set is a
    list of row numbers (the key_idx that build_keyset returns). Rows are
    written once and never move, so an index array stays right for as long
    as its KeySet is alive; a KeyTable that overflows or is cleared starts a
    new KeySet and leaves this one to the dispatches that still hold it.

    The device arrays are (capacity, 16, 4, 20) extended points and, once the
    Pallas route has asked for them, (capacity, 960) niels rows; capacity is
    KEY_TILE times a power of two, so the gathers below compile for few
    shapes. Each further device that launches chunks (gathered_lane with a
    device: the "sharded" route, ed25519_pallas.dispatch_chunks) holds a copy
    of the niels rows, made on its first launch and written tile by tile
    from then on. A built tile is written in place (the array is donated),
    which deletes the array object that was current before: every read of
    the arrays is therefore enqueued under `_lock`, the lock the writes
    take."""

    __slots__ = ("n_rows", "valid", "_lock", "_tab_ext", "_niels",
                 "_niels_on")

    def __init__(self):
        self.n_rows = 0
        # per row: the key decoded to a curve point (a row that did not can
        # never verify: its table is the identity's, its lanes are masked)
        self.valid = np.zeros((0,), dtype=bool)
        self._lock = threading.Lock()
        self._tab_ext = None
        self._niels = None
        self._niels_on: dict = {}  # device -> that device's copy of _niels

    def append(self, a_neg: np.ndarray, valid: np.ndarray) -> str:
        """Build the tables of K new keys, a KEY_TILE at a time through the
        one tile-shaped executable, into rows n_rows.. (the KeyTable's lock
        is held: one appender at a time). -> the program that built them:
        "pallas" on a TPU backend (ed25519_pallas.build_comb_tile, a key a
        lane), "jnp" elsewhere. Waits for the last tile: a build is timed to
        its result, and the first kernel over new keys waits for it anyway."""
        k = a_neg.shape[0]
        self._reserve(self.n_rows + _round_up(k, KEY_TILE))
        if _use_pallas():
            from tendermint_tpu.ops import ed25519_pallas

            program, build = "pallas", ed25519_pallas.build_comb_tile
        else:
            program, build = "jnp", _build_comb_tables_tiled
        for o in range(0, k, KEY_TILE):
            tile = build(a_neg[o : o + KEY_TILE])
            kt = min(KEY_TILE, k - o)
            self.valid[self.n_rows : self.n_rows + kt] = valid[o : o + kt]
            with self._lock:
                self._tab_ext = _write_rows(self._tab_ext, tile, self.n_rows)
                if self._niels is not None:
                    rows = _to_niels(tile)
                    self._niels = _write_rows(self._niels, rows, self.n_rows)
                    for d, copy in list(self._niels_on.items()):
                        self._niels_on[d] = _write_rows(
                            copy, jax.device_put(rows, d), self.n_rows)
                self.n_rows += kt
        self._tab_ext.block_until_ready()
        return program

    def _reserve(self, rows: int) -> None:
        cap = self.valid.shape[0]
        if rows <= cap:
            return
        new = max(cap, KEY_TILE)
        while new < rows:
            new *= 2
        valid = np.zeros((new,), dtype=bool)
        valid[:cap] = self.valid
        with self._lock:
            self.valid = valid
            self._tab_ext = _grown(self._tab_ext, new, (16, 4, 20))
            if self._niels is not None:
                self._niels = _grown(self._niels, new, (960,))
                for d, copy in list(self._niels_on.items()):
                    self._niels_on[d] = _grown(copy, new, (960,), d)

    def take(self, idx: np.ndarray):
        """(nb,) row numbers -> (nb, 16, 4, 20) per-item extended tables."""
        with self._lock:
            return jnp.take(self._tab_ext, jnp.asarray(idx), axis=0)

    def gathered_lane(self, idx: np.ndarray, device=None):
        """(nb,) row numbers -> (960, nb) lane-major niels tables for the
        Pallas kernel, on `device` (None: where unplaced arrays go, the one
        device of a one-chip host). The first call converts the rows built
        so far, tile by tile; from then on append converts each tile it
        builds. A device's first call copies the niels rows to it once;
        append keeps the copy current a tile at a time."""
        with self._lock:
            if self._niels is None:
                niels = jnp.zeros((self.valid.shape[0], 960), jnp.int32)
                for o in range(0, self.n_rows, KEY_TILE):
                    niels = _write_rows(
                        niels, _to_niels(_read_tile(self._tab_ext, o)), o)
                self._niels = niels
            if device is None:
                return _gather_transpose(self._niels, jnp.asarray(idx))
            tab = self._niels_on.get(device)
            if tab is None:
                tab = self._niels_on[device] = jax.device_put(
                    self._niels, device)
            return _gather_transpose(tab, jax.device_put(idx, device))


class KeyTable(dict):
    """pubkey bytes -> row number of the current KeySet: what a key type's
    device tables are keyed on. Every access happens under the lock that
    build_keyset is given. `clear()` forgets every row (the next lookup
    builds its keys again), as does a lookup that would pass MAX_ROWS: that
    bounds HBM (8,960 bytes a row) against a peer that feeds a light client
    key sets without end. `overflow_clears` counts those for the table's
    lifetime (prep.keyset's `cleared` tag; on /metrics keytable_clears_total,
    beside keytable_keys_built_total and keytable_build_launches_total)."""

    MAX_ROWS = 1 << 16

    def __init__(self):
        super().__init__()
        self.keyset = KeySet()
        self.generation = 0
        self.overflow_clears = 0

    def clear(self) -> None:
        super().clear()
        self.keyset = KeySet()
        self.generation += 1

    def rows_of(self, keys: list[bytes]) -> np.ndarray | None:
        """(len(keys),) int32 row numbers, or None if a key is not resident."""
        try:
            return np.fromiter(map(self.__getitem__, keys), dtype=np.int32,
                               count=len(keys))
        except (KeyError, TypeError):  # TypeError: a bytes-like, not bytes
            return None

    def admit(self, keys: list[bytes], decode_neg, kind: str) -> int:
        """Give every key of `keys` a row, building tables only for those
        that have none. -> the number of keys built. Both halves of a build
        go to the start-up ring, tracing on or off."""
        new = [p for p in dict.fromkeys(keys) if p not in self]
        if not new:
            return 0
        if (self.keyset.n_rows
                and self.keyset.n_rows + _round_up(len(new), KEY_TILE)
                > self.MAX_ROWS):
            self.clear()
            self.overflow_clears += 1
            _count_metric("keytable_clears")
            new = list(dict.fromkeys(keys))
        t0 = _time.monotonic()
        a_neg = np.broadcast_to(ed.IDENTITY_LIMBS, (len(new), 4, 20)).copy()
        valid = np.zeros((len(new),), dtype=bool)
        for j, p in enumerate(new):
            neg = decode_neg(p)
            if neg is not None:
                a_neg[j] = neg
                valid[j] = True
        t1 = _time.monotonic()
        row0 = self.keyset.n_rows
        program = self.keyset.append(a_neg, valid)
        t2 = _time.monotonic()
        rows = _round_up(len(new), KEY_TILE)
        self.update(zip(new, range(row0, row0 + len(new))))
        _trace.STARTUP.record("startup.key_decode", t1 - t0, start=t0,
                              keys=len(new), kind=kind)
        _trace.STARTUP.record("startup.table_build", t2 - t1, start=t1,
                              keys=len(new), kind=kind, rows=rows,
                              launches=rows // KEY_TILE, program=program)
        _count_metric("keytable_keys_built", len(new))
        _count_metric("keytable_build_launches", rows // KEY_TILE)
        return len(new)


def _count_metric(metric: str, n: int = 1) -> None:
    """Add to a NodeMetrics counter where a node exposes metrics."""
    from tendermint_tpu.utils import metrics as tmmetrics

    m = tmmetrics.GLOBAL_NODE_METRICS
    if m is not None:
        getattr(m, metric).add(n)


_KS_LOCK = threading.Lock()
# Exact pubkey SEQUENCE -> (KeySet, key_idx): a memo of row-number arrays.
# Steady-state consensus verifies the same validator order every height and
# hits this without touching the items.
_KS_CACHE: OrderedDict[bytes, tuple[KeySet, np.ndarray]] = OrderedDict()
_KS_MAX = 8
# The per-key table. (Its name, like _KS_CACHE's, is pinned by
# tests/benchmark/ and benchmark/drivers/lightsync.py, which empty both
# under _KS_LOCK to start a session as a new client process would.)
_KS_UNIQ_CACHE = KeyTable()


def next_bucket(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


def _normalize_pubs(pubs: list[bytes]) -> tuple[bytes, np.ndarray]:
    """-> (joined 32-byte-normalized pubkey bytes, (N,) bool size-ok mask)."""
    n = len(pubs)
    ok = np.fromiter((len(p) == ref.PUBKEY_SIZE for p in pubs), dtype=bool, count=n)
    if ok.all():
        return b"".join(pubs), ok
    zero = b"\x00" * 32
    return b"".join(p if len(p) == 32 else zero for p in pubs), ok


def build_keyset(pubs: list[bytes], cache: OrderedDict, lock: threading.Lock,
                 decode_neg, uniq_cache: KeyTable | None = None,
                 kind: str = "ed25519",
                 ) -> tuple[KeySet, np.ndarray, np.ndarray]:
    """Shared key-table lookup for any Edwards-comb key type.

    -> (KeySet, key_idx (N,) int32 row numbers, pub_ok (N,) bool).
    `uniq_cache` is the key type's KeyTable (None: a table of this call's
    own), `cache` a memo from the exact pubkey SEQUENCE to its row numbers:
    steady-state consensus hits the memo every height; a signer set never
    seen before over resident keys -- a live chain's commit, a coalesced
    verify-service launch -- pays one pass that maps keys to rows; only a
    key the table does not hold is decoded (decode_neg: pubkey bytes ->
    extended limbs of -A or None; RFC 8032 decompression for ed25519,
    ristretto255 decode for sr25519) and has its tables built, for itself
    alone. All state lives in `cache` and `uniq_cache`. `kind` only names
    the key type on the flight-recorder spans (prep.keyset; on a build the
    start-up ring's startup.key_decode and startup.table_build)."""
    table = KeyTable() if uniq_cache is None else uniq_cache
    if _trace.ENABLED:
        tr = _trace.current()
        with tr.span("prep.keyset", keys=len(pubs), kind=kind):
            ks, key_idx, pub_ok, hit, built, cleared = _build_keyset(
                pubs, cache, lock, decode_neg, table, kind)
            tr.annotate(hit=hit, built=built, resident=ks.n_rows,
                        cleared=cleared)
        return ks, key_idx, pub_ok
    return _build_keyset(pubs, cache, lock, decode_neg, table, kind)[:3]


def _build_keyset(pubs, cache, lock, decode_neg, table, kind):
    """-> (KeySet, key_idx, pub_ok, hit, built, cleared): hit is "sequence"
    (the memo answered), "set" (every key was resident; rows mapped anew) or
    "miss" (`built` keys, at least one, were decoded and had their tables
    built); cleared is 1 where that build first emptied a table that would
    have passed MAX_ROWS."""
    joined, pub_ok = _normalize_pubs(pubs)
    with lock:
        hit = cache.get(joined)
        # a memo entry of a KeySet the table has left behind is stale
        if hit is not None and hit[0] is table.keyset:
            cache.move_to_end(joined)
            return hit[0], hit[1], pub_ok, "sequence", 0, 0
        built = 0
        clears = table.overflow_clears
        key_idx = table.rows_of(pubs) if pub_ok.all() else None
        if key_idx is None:
            keys = [joined[i : i + 32] for i in range(0, len(joined), 32)]
            built = table.admit(keys, decode_neg, kind)
            key_idx = table.rows_of(keys)
        ks = table.keyset
        cache[joined] = (ks, key_idx)
        cache.move_to_end(joined)
        while len(cache) > _KS_MAX:
            cache.popitem(last=False)
    return (ks, key_idx, pub_ok, "miss" if built else "set", built,
            table.overflow_clears - clears)


def get_keyset(pubs: list[bytes]) -> tuple[KeySet, np.ndarray, np.ndarray]:
    return build_keyset(pubs, _KS_CACHE, _KS_LOCK, _decompress_neg,
                        uniq_cache=_KS_UNIQ_CACHE)


# ---------------------------------------------------------------------------
# Host-side preparation (vectorized)
# ---------------------------------------------------------------------------

_BIT_W = (1 << np.arange(13, dtype=np.int64)).astype(np.int32)


def _r_to_limbs(r32: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(N, 32) uint8 R bytes -> ((N, 20) raw y limbs, (N,) sign bits)."""
    bits = np.unpackbits(r32, axis=1, bitorder="little")  # (N, 256)
    sign = bits[:, 255].astype(np.int32)
    y_bits = bits[:, :255].astype(np.int32)
    y_bits = np.concatenate(
        [y_bits, np.zeros((y_bits.shape[0], 5), dtype=np.int32)], axis=1
    )  # pad to 260
    limbs = y_bits.reshape(-1, 20, 13) @ _BIT_W
    return limbs.astype(np.int32), sign


def prepare_scalars(items, pub_ok: np.ndarray, windows: bool = True,
                    reduce: bool = True):
    """:func:`_prepare_scalars` inside its flight-recorder span."""
    with (_trace.current().span("prep.scalars", sigs=len(items),
                                kind="ed25519")
          if _trace.ENABLED else _trace.NULL_SPAN):
        return _prepare_scalars(items, pub_ok, windows, reduce)


def _prepare_scalars(items, pub_ok: np.ndarray, windows: bool, reduce: bool):
    """Vectorized per-signature prep: scalars, R bytes, validity.

    items: [(pub, msg, sig)]; pub_ok from get_keyset. Returns dict of numpy
    arrays sized to len(items) (unpadded). With windows=False (the Pallas
    path) the comb windows are left to the device and only raw h32/s32
    scalars are produced -- 40% less H2D payload. With reduce=False the
    mod-L reduction is ALSO left to the device: the dict carries the raw
    (N, 64) SHA-512 digests as "h64" and no "h32"."""
    n = len(items)
    sig_ok = np.fromiter(
        (len(it[2]) == ref.SIGNATURE_SIZE for it in items), dtype=bool, count=n
    )
    if sig_ok.all():
        sigs = np.frombuffer(b"".join(it[2] for it in items), dtype=np.uint8)
    else:
        zero = b"\x00" * 64
        sigs = np.frombuffer(
            b"".join(it[2] if len(it[2]) == 64 else zero for it in items),
            dtype=np.uint8,
        )
    sigs = sigs.reshape(n, 64)
    r32 = np.ascontiguousarray(sigs[:, :32])
    s32 = np.ascontiguousarray(sigs[:, 32:])

    pubs32, _ = _normalize_pubs([it[0] for it in items])
    pubs_arr = np.frombuffer(pubs32, dtype=np.uint8).reshape(n, 32)

    s_lt = sc.lt_l(s32)
    valid = sig_ok & s_lt & pub_ok
    out = dict(s32=s32, r32=r32, valid=valid)
    digests = chash.sha512_rab(r32, np.ascontiguousarray(pubs_arr),
                               [it[1] for it in items])
    if not reduce:
        out["h64"] = digests
        return out
    h32 = sc.reduce_mod_l(digests)
    out["h32"] = h32
    if windows:
        out["h_win"] = sc.comb_windows(h32)
        out["s_win"] = sc.comb_windows(s32)
    return out




def _jnp_args(s: dict, n: int, nb: int) -> dict:
    """prepare_scalars output -> padded (N-major, int32) args for the jnp
    kernel: h_win, s_win, r_y, r_sign, valid."""
    r_y, r_sign = _r_to_limbs(s["r32"])
    out = {}
    for k, v in (("h_win", s["h_win"].astype(np.int32)),
                 ("s_win", s["s_win"].astype(np.int32)),
                 ("r_y", r_y), ("r_sign", r_sign), ("valid", s["valid"])):
        pad = np.zeros((nb,) + v.shape[1:], dtype=v.dtype)
        pad[:n] = v
        out[k] = pad
    return out


def prepare(items):
    """Padded full-batch prep for the jnp kernel (the example batch of
    __graft_entry__.entry's compile check): returns (dict incl. gathered
    per-item comb tables, n)."""
    n = len(items)
    nb = next_bucket(n)
    ks, key_idx, pub_ok = get_keyset([it[0] for it in items])
    # Keys that failed decompression sit in the table as the identity point;
    # without this mask a forged (R = compress([s]B), s) pair would verify
    # under any off-curve pubkey (the scalar path rejects these).
    pub_ok = pub_ok & ks.valid[key_idx]
    s = prepare_scalars(items, pub_ok)
    idx = np.zeros((nb,), dtype=np.int32)
    idx[:n] = key_idx
    out = _jnp_args(s, n, nb)
    out["tab"] = np.asarray(ks.take(idx))
    return out, n


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"  # the Pallas kernel lowers for TPU only


# ---------------------------------------------------------------------------
# Host (CPU) crossover: below this batch size the C verifier (ops/chost,
# Pippenger RLC batch) wins because a kernel flush pays a fixed host<->device
# round trip (and a whole padded chunk) whatever its size. The adaptive value
# is measured at warmup; until calibrated, HOST_CROSSOVER_DEFAULT applies.
# Both the default and calibrate_host_crossover's assumed device rate were
# chosen on a link that is gone; chip_smoke.py prints what this machine
# measures (ROADMAP S2/S5 hold the figures for the PR that retunes them).
# ---------------------------------------------------------------------------

_HOST_CAL: dict = {"crossover": None, "floor_ms": None, "host_us": None}
_HOST_CAL_LOCK = threading.Lock()
HOST_CROSSOVER_DEFAULT = 2048


def host_crossover() -> int:
    """Current batch-size threshold below which verification runs on host.
    TM_TPU_HOST_CROSSOVER overrides (0 disables the host path)."""
    v = os.environ.get("TM_TPU_HOST_CROSSOVER")
    if v is not None:
        return int(v)
    c = _HOST_CAL["crossover"]
    return c if c is not None else HOST_CROSSOVER_DEFAULT


def shard_enabled() -> bool:
    """False when the operator opted out (TM_TPU_SHARD=0)."""
    return os.environ.get("TM_TPU_SHARD") != "0"


def should_shard(n: int) -> bool:
    """Whether a batch of n signatures is spread over the local devices: a
    TPU backend, more than one local device, sharding not opted out, and
    more than one Pallas chunk, the unit that is placed on a device (a
    batch of one chunk or less runs as on a one-chip host). Off a TPU this
    is False whatever the device count: one device's jnp kernel, as a CPU
    runs it."""
    if not (_use_pallas() and jax.local_device_count() > 1
            and shard_enabled()):
        return False
    from tendermint_tpu.ops import ed25519_pallas  # a second to import

    return n > ed25519_pallas.CHUNK


def route_batch(n: int, force_device: bool = False, scalar_min: int = 0) -> str:
    """THE routing decision of the verify path: which of four routes a batch
    of n signatures (either key type) takes. Both dispatch_batch entry
    points branch on it, the registry (crypto/batch) asks it whether the
    verify service owns the launch, and nothing else decides
    (docs/PARALLEL.md has the table). In order:

      "scalar"   not forced, n < scalar_min, C library not loaded: the
                 registry's pure-Python loop. A kernel launch never pays off
                 for a handful of signatures, and on a cold process it would
                 pay an XLA compile. scalar_min is the registry's per-kind
                 batch_min; direct callers of dispatch_batch pass 0.
      "sharded"  should_shard(n): a TPU host with several chips, from more
                 than one Pallas chunk upward. The chunks of the one-chip
                 kernel placed one a local device
                 (ed25519_pallas.dispatch_chunks).
      "host"     not forced, n < host_crossover(), C library loaded or
                 building: a kernel flush loses to the CPU there, the sync
                 floor alone exceeds the C verifier's whole runtime. While
                 the gcc build is in flight this is the scalar loop (~2
                 ms/sig, bounded by the build window): the device route on a
                 cold process means a fresh XLA compile, an order of
                 magnitude worse.
      "device"   otherwise: the one-chip kernel (Pallas on a TPU backend,
                 jnp elsewhere). The route that pays the host<->device sync
                 floor, with "sharded"; those two the verify service shares
                 between callers.

    host_crossover is looked up in this module's globals at call time:
    tests replace the module attribute."""
    from tendermint_tpu.ops import chost

    loaded = chost.available()
    if not force_device and n < scalar_min and not loaded:
        return "scalar"
    if should_shard(n):
        return "sharded"
    if (not force_device and n < host_crossover()
            and (loaded or chost.building())):
        return "host"
    return "device"


def calibrate_host_crossover(device_marginal_us: float = 2.5) -> int:
    """Measure the sync floor and the host RLC rate, set the crossover to
    floor / (host_us - device_us) clamped to [256, 16384]. One-time cost:
    ~0.5 s (64 python signs + 3 tiny device round trips). Idempotent."""
    from tendermint_tpu.ops import chost

    with _HOST_CAL_LOCK:
        if _HOST_CAL["crossover"] is not None:
            return _HOST_CAL["crossover"]
        # ensure_available: calibration runs in the warmup background
        # thread, the designated place to pay the gcc build once.
        if not chost.ensure_available():
            _HOST_CAL["crossover"] = 0
            return 0
        t_cal = _time.monotonic()

        # host RLC rate on 256 items (64 unique sigs tiled; the A-decompress
        # cache makes the tiling realistic for steady-state consensus)
        priv = ref.gen_priv_key(b"\x51" * 32)
        base = [(priv.pub_key().data, b"cal%d" % i,
                 ref.sign(priv.data, b"cal%d" % i)) for i in range(64)]
        items = base * 4
        joined, pub_ok = _normalize_pubs([it[0] for it in items])
        s = prepare_scalars(items, pub_ok, windows=False)
        pubs_arr = np.frombuffer(joined, dtype=np.uint8).reshape(-1, 32)
        args = (pubs_arr, s["h32"], s["s32"], s["r32"], s["valid"])
        out = chost.ed25519_verify(*args, mode=1)
        if not out.all():  # self-check failed: never route here
            _HOST_CAL["crossover"] = 0
            return 0
        t0 = _time.monotonic()
        chost.ed25519_verify(*args, mode=1)
        host_us = (_time.monotonic() - t0) * 1e6 / len(items)
        # sync floor of one flush round trip
        tiny = jax.jit(lambda a: a * 2)
        floor_ms = min(
            _measure_once(lambda: np.asarray(tiny(jnp.ones((1,), jnp.int32))))
            for _ in range(3))
        margin = max(host_us - device_marginal_us, 1.0)
        cross = int(min(max(floor_ms * 1e3 / margin, 256), 16384))
        _HOST_CAL.update(crossover=cross, floor_ms=floor_ms, host_us=host_us)
        _trace.STARTUP.record("startup.calibrate", _time.monotonic() - t_cal,
                              start=t_cal, crossover=cross, floor_ms=floor_ms,
                              host_us=host_us)
        return cross


def _measure_once(fn) -> float:
    t0 = _time.monotonic()
    fn()
    return (_time.monotonic() - t0) * 1e3


def _host_span(route: str, n: int, kind: str = "ed25519"):
    """prep.host_verify around a host verifier's whole answer."""
    return (_trace.current().span("prep.host_verify", route=route, sigs=n,
                                  kind=kind)
            if _trace.ENABLED else _trace.NULL_SPAN)


def _dispatch_host(items, n, route: str = "host_c"):
    """Synchronous host-path dispatch: C serial/RLC verify (ops/chost).
    Returns the (device_out=None, finish) pair of the dispatch contract."""
    from tendermint_tpu.ops import chost

    with _host_span(route, n):
        joined, pub_ok = _normalize_pubs([it[0] for it in items])
        s = prepare_scalars(items, pub_ok, windows=False)
        pubs_arr = np.frombuffer(joined, dtype=np.uint8).reshape(n, 32)
        bitmap = chost.ed25519_verify(pubs_arr, s["h32"], s["s32"], s["r32"],
                                      s["valid"])
    return None, _cbreaker.routed(lambda _unused: bitmap, route)


def _scalar_fallback_bitmap(items) -> np.ndarray:
    """Pure-Python serial re-verification: the degradation floor that needs
    neither the device nor the C library (used while the C build is in
    flight and as the last rung of the circuit-breaker fallback)."""
    return np.fromiter((ref.verify(p, m, s) for (p, m, s) in items),
                       dtype=bool, count=len(items))


def _host_fallback(items, n, route: str | None = None):
    """(device_out=None, finish) via the best available host path: the C
    verifier when loaded, else the pure-Python scalar loop. `route` names
    the answer when it is not the host's own choice (breaker_fallback)."""
    from tendermint_tpu.ops import chost

    if chost.available():
        return _dispatch_host(items, n, route or "host_c")
    route = route or "host_scalar"
    with _host_span(route, n):
        bitmap = _scalar_fallback_bitmap(items)
    return None, _cbreaker.routed(lambda _unused: bitmap, route)


def launch_span(program: str, route: str, left: int, lanes: int,
                device=None):
    """prep.launch around the host's enqueue of ONE device program: `left`
    real signatures were still to launch, `lanes` is what the call holds,
    so sigs over lanes is the share of launched lanes that did work.
    `device` is where the program was placed: a local device, None for where
    unplaced arrays go (the first)."""
    if not _trace.ENABLED:
        return _trace.NULL_SPAN
    if device is None:
        device = jax.local_devices()[0]
    return _trace.current().span(
        "prep.launch", program=program, route=route,
        sigs=max(0, min(left, lanes)), lanes=lanes,
        device=device.id)


def _dispatch_device(items, n: int, multichip: bool):
    """The accelerator route proper: comb tables + the kernel launches. On a
    TPU backend the Pallas chunks, on the one device or (`multichip`, the
    "sharded" route) placed a chunk a local device; elsewhere the jnp
    kernel. Raises on device failure (injected or real); the circuit
    breaker in dispatch_batch owns the fallback. The fault site fires in
    dispatch_batch, NOT here: the breaker probe also runs this function,
    and probe timing must never consume the deterministic consensus-path
    hit indices of ops.ed25519.device."""
    ks, key_idx, pub_ok = get_keyset([it[0] for it in items])
    # Non-decompressable keys get an identity comb table; they must be
    # rejected here, exactly as the scalar path's _decompress(pub) is None.
    pub_ok = pub_ok & ks.valid[key_idx]
    if _use_pallas():
        # Prep is done chunk-by-chunk inside the pipelined path so device
        # compute overlaps host prep of the next chunk; across chips the
        # same loop puts chunk k on local device k mod ndev.
        from tendermint_tpu.ops import ed25519_pallas

        return ed25519_pallas.dispatch_chunks(
            "ed25519", n,
            functools.partial(ed25519_pallas.dispatch_items_pipelined,
                              ks, key_idx, items, pub_ok),
            multichip)
    s = prepare_scalars(items, pub_ok, windows=True)

    # Fixed-tile chunking: every batch runs through the one JNP_TILE-shaped
    # executable, so no batch size ever triggers a fresh XLA compile.
    nb = max(_round_up(n, JNP_TILE), JNP_TILE)
    idx = np.zeros((nb,), dtype=np.int32)
    idx[:n] = key_idx
    padded = _jnp_args(s, n, nb)
    outs = []
    for off in range(0, nb, JNP_TILE):
        with launch_span("jit__verify_kernel", "jnp", n - off, JNP_TILE):
            tab = ks.take(idx[off : off + JNP_TILE])
            outs.append(_jnp_kernel(tab, **{
                k: jnp.asarray(v[off : off + JNP_TILE])
                for k, v in padded.items()
            }))
    ok = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    _start_host_copy(ok)
    return ok, _cbreaker.routed(
        lambda v: np.asarray(v)[:n].astype(bool), "jnp")


def _device_probe() -> bool:
    """Circuit-breaker probe: one real signature through the device route.
    Runs in the breaker's background thread, never on the consensus path.
    Fires its own fault site (keep a dead-device simulation dead with
    TMTPU_FAULTS="ops.ed25519.device:raise,ops.ed25519.probe:raise")."""
    faults.fire("ops.ed25519.probe")
    priv = ref.gen_priv_key(b"\x7b" * 32)
    items = [(priv.pub_key().data, b"breaker-probe",
              ref.sign(priv.data, b"breaker-probe"))]
    dev, finish = _dispatch_device(items, 1, multichip=False)
    return bool(np.all(finish(jax.device_get(dev))))


BREAKER = _cbreaker.CircuitBreaker("ed25519-device", probe=_device_probe)


def dispatch_batch(items: list[tuple[bytes, bytes, bytes]],
                   force_device: bool = False):
    """Async batched verify of [(pub, msg, sig)]: all host prep + device
    dispatches are issued, nothing is fetched. Returns (device_out, finish)
    where `finish(jax.device_get(device_out))` -> (len(items),) bool. Lets
    callers (MixedBatchVerifier) overlap the fetch latency of several
    kernels in ONE device_get: two sequential fetches cost two host<->device
    round trips, one batched fetch costs one.

    :func:`route_batch` names the route: the C host verifier below the
    measured crossover (ops/chost), else the fused Pallas kernel on TPU
    (ops/ed25519_pallas), its chunks placed over the local chips when there
    are several, or the pure-jnp CPU fallback. force_device=True skips the
    host route (kernel warmup, kernel tests).

    The device route sits behind a circuit breaker (ops/breaker): a device
    dispatch failure is re-verified on the host within the same call, the
    circuit opens, and later batches go straight to the host until a
    background probe re-closes it -- consensus keeps committing with a dead
    accelerator. While open, even force_device callers are degraded."""
    if not items:
        return None, _cbreaker.routed(
            lambda _: np.zeros((0,), dtype=bool), "host_scalar")
    n = len(items)
    route = route_batch(n, force_device)
    if route == "host":
        # No device tables are built on this path (host verification is
        # self-contained).
        return _host_fallback(items, n)

    def _device():
        faults.fire("ops.ed25519.device")
        return _dispatch_device(items, n, route == "sharded")

    return _cbreaker.guarded_dispatch(
        BREAKER, _device,
        lambda: _host_fallback(items, n, route="breaker_fallback"))


def _start_host_copy(dev) -> None:
    """Begin the D2H transfer NOW, at dispatch, so the copy rides behind the
    kernel on the active stream and the later device_get finds the bytes
    already on the host instead of starting a round trip of its own. `dev`
    is an array or the pieces of one (a piece a device, each its own copy)."""
    for piece in jax.tree_util.tree_leaves(dev):
        try:
            piece.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass


def verify_batch(items: list[tuple[bytes, bytes, bytes]],
                 force_device: bool = False) -> np.ndarray:
    """Batched verify of [(pub, msg, sig)]; returns (len(items),) bool."""
    dev, finish = dispatch_batch(items, force_device=force_device)
    return _cbreaker.guarded_fetch(
        BREAKER, dev, finish,
        lambda: _host_fallback(items, len(items), route="breaker_fallback"))
