"""Host-side batched curve25519 verification: ctypes bindings for
csrc/curve25519_host.c.

This is the CPU half of the adaptive kernel/scalar crossover
(ops/ed25519_batch.host_crossover): a kernel flush pays a fixed
host<->device round trip plus a whole padded chunk, so batches below the
measured crossover are verified here — serial Straus/wNAF for a handful, a
Pippenger random-linear-combination batch check above that.  Accept/reject
is byte-identical to the scalar reference (crypto/ed25519.py verify /
crypto/sr25519.py verify; reference semantics
crypto/ed25519/ed25519.go:148, crypto/sr25519/pubkey.go:10): the RLC check
falls back to per-item serial verification whenever the batch equation
fails, so callers always observe serial decisions.

Build mirrors ops/chash.py: lazy gcc, .so named by ops/cbuild from the
source, the compile recipe and the host CPU's features (a stale binary, or
one built for another machine, can never load; csrc/*.so is gitignored).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from tendermint_tpu.ops import cbuild

_CSRC = cbuild.CSRC
_SRC = os.path.join(_CSRC, "curve25519_host.c")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False  # an attempt FINISHED (loaded or definitively failed)
_loading = False  # an attempt is IN FLIGHT (inline or background)
_build_thread: threading.Thread | None = None

_U8P = ctypes.POINTER(ctypes.c_uint8)


# gcc, not g++: the source is pure C, and linking libstdc++ into the .so
# made ITS terminate handler fire during interpreter teardown when node
# threads were mid-call ("FATAL: exception not rethrown" at exit).
_RECIPE = [["gcc", "-O3", "-shared", "-fPIC", "-pthread", "-march=native"],
           ["gcc", "-O3", "-shared", "-fPIC", "-pthread"],
           ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-x", "c"]]


def _lib_path() -> str:
    return cbuild.lib_path("libcurvehost", [_SRC], _RECIPE)


def _build(lib_path: str) -> bool:
    # Sweep temp files abandoned by builders that died mid-compile (crash-
    # injection subprocesses os._exit while the background build thread is
    # in flight). Only temps older than any plausible live build are
    # reaped, so a concurrent builder's in-flight temp is never raced.
    import time as _t

    try:
        for name in os.listdir(_CSRC):
            if ".so.tmp" not in name:
                continue
            p = os.path.join(_CSRC, name)
            try:
                if _t.time() - os.path.getmtime(p) > 900:
                    os.unlink(p)
            except OSError:
                pass
    except OSError:
        pass
    tmp = lib_path + f".tmp{os.getpid()}"
    for base in _RECIPE:
        cmd = base + [_SRC, "-o", tmp]
        try:
            r = subprocess.run(cmd, capture_output=True, timeout=180)
            if r.returncode == 0:
                os.replace(tmp, lib_path)  # atomic vs concurrent builders
                return True
        except (OSError, subprocess.TimeoutExpired):
            continue
    return False


def _load() -> ctypes.CDLL | None:
    """Blocking build+load. The lock is held for the whole attempt, so a
    concurrent ensure_available() waits for an in-flight background build
    instead of racing it; _tried flips only when the attempt FINISHES."""
    global _lib, _tried, _loading
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _loading = True
        try:
            _lib = _load_locked()
        except Exception:  # noqa: BLE001 - a failed build means "chost
            # unavailable", never a dead background build thread (available()
            # would return False forever with _tried unset)
            _lib = None
        finally:
            _loading = False
            _tried = True
        return _lib


def _load_locked() -> ctypes.CDLL | None:
    if os.environ.get("TM_TPU_DISABLE_CHOST") == "1":
        return None
    path = _lib_path()
    if not os.path.exists(path) and not _build(path):
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    lib.ed25519h_verify.argtypes = [
        ctypes.c_long, _U8P, _U8P, _U8P, _U8P, _U8P, _U8P,
        ctypes.c_int, _U8P]
    lib.ed25519h_verify.restype = None
    lib.sr25519h_verify.argtypes = lib.ed25519h_verify.argtypes
    lib.sr25519h_verify.restype = None
    lib.ed25519h_selftest.restype = ctypes.c_int
    if lib.ed25519h_selftest() != 1:
        return None
    return lib


def building() -> bool:
    """True while a build/load attempt is in flight -- background thread OR
    an ensure_available() caller building inline under the lock."""
    t = _build_thread
    return _loading or (t is not None and t.is_alive())


def available() -> bool:
    """Non-blocking: True only when the library is already loaded or loads
    without compiling (this host's .so exists). A needed gcc build is
    kicked off ONCE in a background thread and False is returned until it
    lands -- the single-signature verify path and the batch dispatch fall
    back to pure Python meanwhile (the first signature check after a source
    change must not block behind a 3x180 s build)."""
    global _build_thread
    if _lib is not None:
        return True
    if _tried or building():
        return False
    if os.environ.get("TM_TPU_DISABLE_CHOST") == "1":
        return False
    if os.path.exists(_lib_path()):
        return _load() is not None  # dlopen + selftest only: fast
    # A blocking acquire here could wait out a whole inline build started by
    # ensure_available() on another thread; never do that on this path.
    if not _lock.acquire(blocking=False):
        return False
    try:
        if _build_thread is None and not _tried and _lib is None:
            _build_thread = threading.Thread(
                target=_load, name="chost-build", daemon=True)
            _build_thread.start()
    finally:
        _lock.release()
    return False


def ensure_available() -> bool:
    """Blocking variant for callers that WANT to pay the build (warmup-time
    calibration, differential tests): builds+loads inline, or joins the
    in-flight background build."""
    return _load() is not None


def _u8(a: np.ndarray) -> "ctypes._Pointer":
    return a.ctypes.data_as(_U8P)


def _as_rows(x, n: int) -> np.ndarray:
    a = np.ascontiguousarray(x, dtype=np.uint8)
    assert a.shape == (n, 32), a.shape
    return a


def ed25519_verify(pubs: np.ndarray, h32: np.ndarray, s32: np.ndarray,
                   r32: np.ndarray, valid: np.ndarray,
                   mode: int = 2) -> np.ndarray:
    """Batched ed25519 verify on host.  pubs/h32/s32/r32: (n, 32) uint8
    (h32 = SHA-512(R||A||M) mod L, little-endian); valid: (n,) bool from the
    caller's size prechecks.  mode 0=serial, 1=RLC, 2=auto.  -> (n,) bool."""
    lib = _load()
    assert lib is not None
    n = len(valid)
    if n == 0:
        return np.zeros((0,), dtype=bool)
    pubs = _as_rows(pubs, n)
    h32 = _as_rows(h32, n)
    s32 = _as_rows(s32, n)
    r32 = _as_rows(r32, n)
    v = np.ascontiguousarray(valid, dtype=np.uint8)
    seed = np.frombuffer(os.urandom(32), dtype=np.uint8)
    out = np.zeros((n,), dtype=np.uint8)
    lib.ed25519h_verify(n, _u8(pubs), _u8(h32), _u8(s32), _u8(r32), _u8(v),
                        _u8(seed), mode, _u8(out))
    return out.astype(bool)


def ed25519_verify_one(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Single-signature fast path for PubKey.verify_signature: ~100 us vs
    the pure-Python reference's ~2 ms. Caller guarantees availability."""
    import hashlib

    if len(pub) != 32 or len(sig) != 64:
        return False
    L = 2**252 + 27742317777372353535851937790883648493
    h = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(),
                       "little") % L
    arr = np.frombuffer(pub + h.to_bytes(32, "little") + sig[32:] + sig[:32],
                        dtype=np.uint8).reshape(4, 32)
    return bool(ed25519_verify(arr[0:1], arr[1:2], arr[2:3], arr[3:4],
                               np.ones((1,), bool), mode=0)[0])


def sr25519_verify_one(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Single sr25519 fast path: C strobe challenge (ops/sr25519_batch) +
    C curve verify. Caller guarantees availability."""
    if len(pub) != 32 or len(sig) != 64:
        return False
    from tendermint_tpu.ops import sr25519_batch as srb

    pubs = np.frombuffer(pub, dtype=np.uint8).reshape(1, 32)
    r32 = np.frombuffer(sig[:32], dtype=np.uint8).reshape(1, 32)
    s32 = np.frombuffer(sig[32:], dtype=np.uint8).reshape(1, 32).copy()
    marker = bool(s32[0, 31] & 128)
    s32[0, 31] &= 127
    c32 = srb.challenges([msg], pubs, r32)
    return bool(sr25519_verify(pubs, c32, s32, r32,
                               np.array([marker]), mode=0)[0])


def sr25519_verify(pubs: np.ndarray, c32: np.ndarray, s32: np.ndarray,
                   r32: np.ndarray, valid: np.ndarray,
                   mode: int = 2) -> np.ndarray:
    """Batched sr25519 verify on host.  c32 = merlin challenge mod L
    (from ops/sr25519_batch's C strobe transcripts); s32 = sig[32:] with the
    schnorrkel marker bit already stripped; r32 = sig[:32]; valid covers
    sizes AND the sig[63]&128 marker check."""
    lib = _load()
    assert lib is not None
    n = len(valid)
    if n == 0:
        return np.zeros((0,), dtype=bool)
    pubs = _as_rows(pubs, n)
    c32 = _as_rows(c32, n)
    s32 = _as_rows(s32, n)
    r32 = _as_rows(r32, n)
    v = np.ascontiguousarray(valid, dtype=np.uint8)
    seed = np.frombuffer(os.urandom(32), dtype=np.uint8)
    out = np.zeros((n,), dtype=np.uint8)
    lib.sr25519h_verify(n, _u8(pubs), _u8(c32), _u8(s32), _u8(r32), _u8(v),
                        _u8(seed), mode, _u8(out))
    return out.astype(bool)
