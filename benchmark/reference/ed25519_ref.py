"""The benchmark's plain reference for ed25519: pure-Python RFC 8032 signing
and Go-crypto/ed25519-exact verification over Python integers.

A copy (PR 22) of the scalar path of ``tendermint_tpu/crypto/ed25519.py`` so
that no PR which changes the program can change what ``correct`` is compared
with. It imports nothing from the program, numpy or jax; data-generation
children import it. ``sign_fixed_base`` is the benchmark's own faster signer
(fixed-base table of 2^i B); tests/benchmark/test_data.py holds it byte-equal
to ``sign`` and to OpenSSL.
"""

from __future__ import annotations

import hashlib

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)  # sqrt(-1) mod p

PUBKEY_SIZE = 32
PRIVKEY_SIZE = 64
SEED_SIZE = 32
SIGNATURE_SIZE = 64

KEY_TYPE = "ed25519"


def _inv(x: int) -> int:
    return pow(x, P - 2, P)


# Extended homogeneous coordinates (X, Y, Z, T) with x=X/Z, y=Y/Z, xy=T/Z.
_IDENT = (0, 1, 1, 0)


def _add(p, q):
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    A = (Y1 - X1) * (Y2 - X2) % P
    B = (Y1 + X1) * (Y2 + X2) % P
    C = 2 * T1 * T2 * D % P
    Dd = 2 * Z1 * Z2 % P
    E, F, G, H = B - A, Dd - C, Dd + C, B + A
    return (E * F % P, G * H % P, F * G % P, E * H % P)


def _double(p):
    X1, Y1, Z1, _ = p
    A = X1 * X1 % P
    B = Y1 * Y1 % P
    C = 2 * Z1 * Z1 % P
    H = A + B
    E = H - (X1 + Y1) * (X1 + Y1) % P
    G = A - B
    F = C + G
    return (E * F % P, G * H % P, F * G % P, E * H % P)


def _scalarmult(s: int, p):
    q = _IDENT
    while s:
        if s & 1:
            q = _add(q, p)
        p = _double(p)
        s >>= 1
    return q


def _compress(p) -> bytes:
    X, Y, Z, _ = p
    zi = _inv(Z)
    x, y = X * zi % P, Y * zi % P
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


def _decompress(s: bytes):
    """RFC 8032 §5.1.3 point decoding. Returns extended point or None."""
    if len(s) != 32:
        return None
    n = int.from_bytes(s, "little")
    sign = n >> 255
    y = n & ((1 << 255) - 1)
    if y >= P:
        return None
    y2 = y * y % P
    u = (y2 - 1) % P
    v = (D * y2 + 1) % P
    # candidate root x = (u/v)^((p+3)/8) computed as u v^3 (u v^7)^((p-5)/8)
    x = u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P) % P
    vx2 = v * x * x % P
    if vx2 == u % P:
        pass
    elif vx2 == (P - u) % P:
        x = x * SQRT_M1 % P
    else:
        return None
    if x == 0 and sign:
        return None
    if x & 1 != sign:
        x = P - x
    return (x, y, 1, x * y % P)


# Base point
_By = 4 * _inv(5) % P
_Bx = 0
# recover Bx from By with even sign
_B = _decompress(_By.to_bytes(32, "little"))
assert _B is not None
BASE = _B


def _clamp(h: bytes) -> int:
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    a |= 1 << 254
    return a


def pubkey_from_seed(seed: bytes) -> bytes:
    if len(seed) != SEED_SIZE:
        raise ValueError("ed25519 seed must be 32 bytes")
    h = hashlib.sha512(seed).digest()
    a = _clamp(h)
    return _compress(_scalarmult(a, BASE))


def sign(priv: bytes, msg: bytes) -> bytes:
    """RFC 8032 Ed25519 signature; priv is the 64-byte (seed||pub) key."""
    if len(priv) != PRIVKEY_SIZE:
        raise ValueError("ed25519 private key must be 64 bytes")
    seed, pub = priv[:32], priv[32:]
    h = hashlib.sha512(seed).digest()
    a = _clamp(h)
    prefix = h[32:]
    r = int.from_bytes(hashlib.sha512(prefix + msg).digest(), "little") % L
    R = _compress(_scalarmult(r, BASE))
    k = int.from_bytes(hashlib.sha512(R + pub + msg).digest(), "little") % L
    s = (r + k * a) % L
    return R + s.to_bytes(32, "little")


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Scalar verification, bit-exact with Go crypto/ed25519 semantics."""
    if len(pub) != PUBKEY_SIZE or len(sig) != SIGNATURE_SIZE:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    A = _decompress(pub)
    if A is None:
        return False
    h = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % L
    # R' = [s]B + [h](-A); negate A by negating X and T.
    negA = (P - A[0], A[1], A[2], (P - A[3]) % P)
    Rp = _add(_scalarmult(s, BASE), _scalarmult(h, negA))
    return _compress(Rp) == sig[:32]


# --- the benchmark's own signer: same bytes, ~4x fewer point operations -----

_BASE_POW2: list = []


def base_mult(k: int):
    """[k]B from a table of 2^i B: additions only, no doublings."""
    if not _BASE_POW2:
        p = BASE
        for _ in range(256):
            _BASE_POW2.append(p)
            p = _double(p)
    q = _IDENT
    i = 0
    while k:
        if k & 1:
            q = _add(q, _BASE_POW2[i])
        k >>= 1
        i += 1
    return q


def sign_fixed_base(seed: bytes, pub: bytes, msg: bytes) -> bytes:
    """``sign(seed + pub, msg)`` byte for byte, through ``base_mult``."""
    h = hashlib.sha512(seed).digest()
    a = _clamp(h)
    r = int.from_bytes(hashlib.sha512(h[32:] + msg).digest(), "little") % L
    R = _compress(base_mult(r))
    k = int.from_bytes(hashlib.sha512(R + pub + msg).digest(), "little") % L
    return R + ((r + k * a) % L).to_bytes(32, "little")


def pubkey_fixed_base(seed: bytes) -> bytes:
    return _compress(base_mult(_clamp(hashlib.sha512(seed).digest())))
