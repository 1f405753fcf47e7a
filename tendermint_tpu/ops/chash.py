"""Batched hashing: ctypes bindings for csrc/hash_batch.c with a hashlib
fallback.

The shared library is built lazily with g++ on first use. The output filename
(ops/cbuild) digests the C sources, the compile recipe and the host CPU's
features, so neither a stale binary nor one built for another machine can be
loaded (and no binary artifact is committed — csrc/*.so is gitignored). All
entry points take/return numpy arrays so a 20k-signature commit pays ONE FFI
crossing instead of 20k hashlib calls.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from tendermint_tpu.ops import cbuild

_SRC_PATHS = [
    os.path.join(cbuild.CSRC, "hash_batch.c"),
    os.path.join(cbuild.CSRC, "sr25519_strobe.c"),
]
_HDR_PATH = os.path.join(cbuild.CSRC, "hash_consts.h")

_CC = ["g++", "-O3", "-shared", "-fPIC", "-x", "c"]
# -march=native unlocks the 4-way AVX2 SHA-512 lanes in hash_batch.c
_FLAG_SETS = [["-fopenmp", "-march=native"], ["-march=native"],
              ["-fopenmp"], []]


def _lib_path() -> str:
    return cbuild.lib_path("libhashbatch", _SRC_PATHS + [_HDR_PATH],
                           [_CC, _FLAG_SETS])

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_U8P = ctypes.POINTER(ctypes.c_uint8)
_I64P = ctypes.POINTER(ctypes.c_int64)
_I32P = ctypes.POINTER(ctypes.c_int32)


def _build(lib_path: str) -> bool:
    tmp = lib_path + ".tmp"
    for flags in _FLAG_SETS:
        cmd = _CC + [*_SRC_PATHS, "-o", tmp] + flags
        try:
            r = subprocess.run(cmd, capture_output=True, timeout=120)
            if r.returncode == 0:
                os.replace(tmp, lib_path)  # atomic vs concurrent builders
                return True
        except (OSError, subprocess.TimeoutExpired):
            return False
    return False


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("TM_TPU_DISABLE_CHASH") == "1":
            return None
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path) and not _build(lib_path):
                return None
            lib = ctypes.CDLL(lib_path)
        except OSError:
            return None
        lib.sha512_batch.argtypes = [_U8P, _I64P, _I32P, ctypes.c_int64, _U8P]
        lib.sha512_rab_batch.argtypes = [
            _U8P, ctypes.c_int64, _U8P, ctypes.c_int64,
            _U8P, _I64P, _I32P, ctypes.c_int64, _U8P,
        ]
        lib.sha256_batch.argtypes = [_U8P, _I64P, _I32P, ctypes.c_int64, _U8P]
        lib.sha256_batch_fixed.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64, _U8P]
        lib.sr25519_challenge_batch.argtypes = [
            _U8P, ctypes.c_int32, ctypes.c_int32,
            _U8P, _I64P, _I32P, _U8P, _U8P, ctypes.c_int64, _U8P,
        ]
        _lib = lib
        return _lib


def _u8(a: np.ndarray):
    return a.ctypes.data_as(_U8P)


def available() -> bool:
    return _load() is not None


def sha512_rab(r32: np.ndarray, a32: np.ndarray, msgs: list[bytes]) -> np.ndarray:
    """SHA-512(r32[i] || a32[i] || msgs[i]) for every i -> (N, 64) uint8.

    r32, a32: C-contiguous (N, 32) uint8 arrays."""
    n = len(msgs)
    out = np.empty((n, 64), dtype=np.uint8)
    lib = _load()
    if lib is None:
        rb, ab = r32.tobytes(), a32.tobytes()
        for i, m in enumerate(msgs):
            d = hashlib.sha512(rb[32 * i:32 * i + 32] + ab[32 * i:32 * i + 32] + m)
            out[i] = np.frombuffer(d.digest(), dtype=np.uint8)
        return out
    data = b"".join(msgs)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int32, count=n)
    offs = np.zeros(n, dtype=np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    buf = np.frombuffer(data, dtype=np.uint8) if data else np.zeros(1, dtype=np.uint8)
    # NOTE: this host has a single CPU core in the target environment, so
    # thread-fanning the (GIL-releasing) C call buys nothing -- measured.
    lib.sha512_rab_batch(
        _u8(r32), 32, _u8(a32), 32, _u8(buf),
        offs.ctypes.data_as(_I64P), lens.ctypes.data_as(_I32P), n, _u8(out))
    return out


def sha512_many(msgs: list[bytes]) -> np.ndarray:
    n = len(msgs)
    out = np.empty((n, 64), dtype=np.uint8)
    lib = _load()
    if lib is None:
        for i, m in enumerate(msgs):
            out[i] = np.frombuffer(hashlib.sha512(m).digest(), dtype=np.uint8)
        return out
    data = b"".join(msgs)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int32, count=n)
    offs = np.zeros(n, dtype=np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    buf = np.frombuffer(data, dtype=np.uint8) if data else np.zeros(1, dtype=np.uint8)
    lib.sha512_batch(_u8(buf), offs.ctypes.data_as(_I64P),
                     lens.ctypes.data_as(_I32P), n, _u8(out))
    return out


def sha256_many(msgs: list[bytes]) -> np.ndarray:
    n = len(msgs)
    out = np.empty((n, 32), dtype=np.uint8)
    lib = _load()
    if lib is None:
        for i, m in enumerate(msgs):
            out[i] = np.frombuffer(hashlib.sha256(m).digest(), dtype=np.uint8)
        return out
    data = b"".join(msgs)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int32, count=n)
    offs = np.zeros(n, dtype=np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    buf = np.frombuffer(data, dtype=np.uint8) if data else np.zeros(1, dtype=np.uint8)
    lib.sha256_batch(_u8(buf), offs.ctypes.data_as(_I64P),
                     lens.ctypes.data_as(_I32P), n, _u8(out))
    return out


def sr25519_challenges(prefix_state: bytes, prefix_pos: int,
                       prefix_pos_begin: int, msgs: list[bytes],
                       pubs: np.ndarray, rs: np.ndarray) -> np.ndarray | None:
    """Batched schnorrkel verify challenges -> (N, 64) uint8 pre-reduction
    transcript PRF bytes, or None when the C library is unavailable (caller
    falls back to the pure-Python transcript).

    prefix_state/pos/pos_begin: the Strobe state of the transcript prefix
    shared by every signature (SigningContext + empty context label), computed
    once in Python. pubs, rs: C-contiguous (N, 32) uint8 arrays."""
    lib = _load()
    if lib is None:
        return None
    n = len(msgs)
    out = np.empty((n, 64), dtype=np.uint8)
    data = b"".join(msgs)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int32, count=n)
    offs = np.zeros(n, dtype=np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    buf = np.frombuffer(data, dtype=np.uint8) if data else np.zeros(1, dtype=np.uint8)
    st = np.frombuffer(prefix_state, dtype=np.uint8)
    lib.sr25519_challenge_batch(
        _u8(st), prefix_pos, prefix_pos_begin, _u8(buf),
        offs.ctypes.data_as(_I64P), lens.ctypes.data_as(_I32P),
        _u8(pubs), _u8(rs), n, _u8(out))
    return out


def sha256_fixed(rows: np.ndarray) -> np.ndarray:
    """SHA-256 of every row of a C-contiguous (N, W) uint8 array -> (N, 32)."""
    n, w = rows.shape
    out = np.empty((n, 32), dtype=np.uint8)
    lib = _load()
    if lib is None:
        rb = rows.tobytes()
        for i in range(n):
            out[i] = np.frombuffer(
                hashlib.sha256(rb[w * i:w * (i + 1)]).digest(), dtype=np.uint8)
        return out
    lib.sha256_batch_fixed(_u8(rows), w, w, n, _u8(out))
    return out
