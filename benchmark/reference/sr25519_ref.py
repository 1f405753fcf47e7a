"""The benchmark's plain reference for sr25519 (schnorrkel): keccak-f[1600],
STROBE-128, merlin transcripts, ristretto255 and the Schnorr-sig protocol in
pure Python.

A copy (PR 22) of the scalar path of ``tendermint_tpu/crypto/sr25519.py`` so
that no PR which changes the program can change what ``correct`` is compared
with. It imports only the benchmark's ed25519 reference. ``sign_fast`` is the
benchmark's own signer for data generation (known public key, fixed-base
table, cloned transcript prefix); tests/benchmark/test_data.py holds it
byte-equal to ``sign`` with the same ``rng_seed``.
"""

from __future__ import annotations

import hashlib
import os

from benchmark.reference import ed25519_ref as ed

KEY_TYPE = "sr25519"
PUBKEY_SIZE = 32
PRIVKEY_SIZE = 32
SIGNATURE_SIZE = 64

P = ed.P
L = ed.L
D = ed.D

# --- keccak-f[1600] ---------------------------------------------------------

_KECCAK_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
_KECCAK_ROT = [
    [0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56], [27, 20, 39, 8, 14],
]
_M64 = (1 << 64) - 1


def _rotl(x: int, n: int) -> int:
    return ((x << n) | (x >> (64 - n))) & _M64


def keccak_f1600(state: bytearray) -> None:
    """In-place permutation of a 200-byte state."""
    a = [[int.from_bytes(state[8 * (x + 5 * y): 8 * (x + 5 * y) + 8], "little")
          for y in range(5)] for x in range(5)]
    for rc in _KECCAK_RC:
        # theta
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rotl(c[(x + 1) % 5], 1) for x in range(5)]
        for x in range(5):
            for y in range(5):
                a[x][y] ^= d[x]
        # rho + pi
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rotl(a[x][y], _KECCAK_ROT[x][y])
        # chi
        for x in range(5):
            for y in range(5):
                a[x][y] = b[x][y] ^ ((~b[(x + 1) % 5][y] & _M64) & b[(x + 2) % 5][y])
        # iota
        a[0][0] ^= rc
    for x in range(5):
        for y in range(5):
            state[8 * (x + 5 * y): 8 * (x + 5 * y) + 8] = a[x][y].to_bytes(8, "little")


# --- STROBE-128 (v1.0.2, merlin subset: meta-AD / AD / PRF / KEY) -----------

_STROBE_R = 166
_FLAG_I, _FLAG_A, _FLAG_C, _FLAG_M, _FLAG_K = 1, 2, 4, 16, 32


class Strobe128:
    def __init__(self, protocol_label: bytes):
        st = bytearray(200)
        st[:6] = bytes([1, _STROBE_R + 2, 1, 0, 1, 96])
        st[6:18] = b"STROBEv1.0.2"
        keccak_f1600(st)
        self.state = st
        self.pos = 0
        self.pos_begin = 0
        self.cur_flags = 0
        self.meta_ad(protocol_label, False)

    def clone(self) -> "Strobe128":
        c = Strobe128.__new__(Strobe128)
        c.state = bytearray(self.state)
        c.pos, c.pos_begin, c.cur_flags = self.pos, self.pos_begin, self.cur_flags
        return c

    def _run_f(self) -> None:
        self.state[self.pos] ^= self.pos_begin
        self.state[self.pos + 1] ^= 0x04
        self.state[_STROBE_R + 1] ^= 0x80
        keccak_f1600(self.state)
        self.pos = 0
        self.pos_begin = 0

    def _absorb(self, data: bytes) -> None:
        for b in data:
            self.state[self.pos] ^= b
            self.pos += 1
            if self.pos == _STROBE_R:
                self._run_f()

    def _overwrite(self, data: bytes) -> None:
        for b in data:
            self.state[self.pos] = b
            self.pos += 1
            if self.pos == _STROBE_R:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        for _ in range(n):
            out.append(self.state[self.pos])
            self.state[self.pos] = 0
            self.pos += 1
            if self.pos == _STROBE_R:
                self._run_f()
        return bytes(out)

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            if flags != self.cur_flags:
                raise ValueError("flag mismatch on continued operation")
            return
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb(bytes([old_begin, flags]))
        if flags & (_FLAG_C | _FLAG_K) and self.pos != 0:
            self._run_f()

    def meta_ad(self, data: bytes, more: bool = False) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool = False) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int, more: bool = False) -> bytes:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, more)
        return self._squeeze(n)

    def key(self, data: bytes, more: bool = False) -> None:
        self._begin_op(_FLAG_A | _FLAG_C, more)
        self._overwrite(data)


# --- merlin transcript ------------------------------------------------------


def _le32(n: int) -> bytes:
    return n.to_bytes(4, "little")


class Transcript:
    def __init__(self, label: bytes):
        self.strobe = Strobe128(b"Merlin v1.0")
        self.append_message(b"dom-sep", label)

    def clone(self) -> "Transcript":
        t = Transcript.__new__(Transcript)
        t.strobe = self.strobe.clone()
        return t

    def append_message(self, label: bytes, message: bytes) -> None:
        self.strobe.meta_ad(label + _le32(len(message)))
        self.strobe.ad(message)

    def challenge_bytes(self, label: bytes, n: int) -> bytes:
        self.strobe.meta_ad(label + _le32(n))
        return self.strobe.prf(n)

    def challenge_scalar(self, label: bytes) -> int:
        return int.from_bytes(self.challenge_bytes(label, 64), "little") % L

    def witness_scalar(self, label: bytes, witness: bytes,
                       rng_seed: bytes | None = None) -> int:
        """merlin TranscriptRng: clone, rekey with the witness, key with
        (normally OS) randomness, squeeze a wide scalar."""
        s = self.strobe.clone()
        s.meta_ad(label + _le32(len(witness)))
        s.key(witness)
        seed = rng_seed if rng_seed is not None else os.urandom(32)
        s.meta_ad(b"rng" + _le32(len(seed)))
        s.key(seed)
        s.meta_ad(b"" + _le32(64))
        return int.from_bytes(s.prf(64), "little") % L


# --- ristretto255 (RFC 9496) ------------------------------------------------

SQRT_M1 = pow(2, (P - 1) // 4, P)
_A_MINUS_D = (-1 - D) % P


def _is_neg(x: int) -> bool:
    return (x % P) & 1 == 1


def _ct_abs(x: int) -> int:
    x %= P
    return P - x if _is_neg(x) else x


def _sqrt_ratio_m1(u: int, v: int) -> tuple[bool, int]:
    """RFC 9496 4.2 SQRT_RATIO_M1."""
    v3 = v * v % P * v % P
    v7 = v3 * v3 % P * v % P
    r = u * v3 % P * pow(u * v7 % P, (P - 5) // 8, P) % P
    check = v * r % P * r % P
    correct = check == u % P
    flipped = check == (-u) % P
    flipped_i = check == (-u) % P * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    return (correct or flipped), _ct_abs(r)


_ok, INVSQRT_A_MINUS_D = _sqrt_ratio_m1(1, _A_MINUS_D)
assert _ok


def ristretto_decode(data: bytes):
    """32 bytes -> extended point (x, y, z=1, t) or None."""
    if len(data) != 32:
        return None
    s = int.from_bytes(data, "little")
    if s >= P or _is_neg(s):
        return None
    ss = s * s % P
    u1 = (1 - ss) % P
    u2 = (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(D * u1 % P * u1) - u2_sqr) % P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2_sqr % P)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x % P * v % P
    x = _ct_abs(2 * s % P * den_x % P)
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or _is_neg(t) or y == 0:
        return None
    return (x, y, 1, t)


def ristretto_encode(pt) -> bytes:
    """Extended (X, Y, Z, T) -> canonical 32 bytes (RFC 9496 4.3.2)."""
    x0, y0, z0, t0 = pt
    u1 = (z0 + y0) % P * ((z0 - y0) % P) % P
    u2 = x0 * y0 % P
    _, invsqrt = _sqrt_ratio_m1(1, u1 * u2 % P * u2 % P)
    den1 = invsqrt * u1 % P
    den2 = invsqrt * u2 % P
    z_inv = den1 * den2 % P * t0 % P
    ix = x0 * SQRT_M1 % P
    iy = y0 * SQRT_M1 % P
    enchanted = den1 * INVSQRT_A_MINUS_D % P
    rotate = _is_neg(t0 * z_inv % P)
    if rotate:
        x, y, den_inv = iy, ix, enchanted
    else:
        x, y, den_inv = x0, y0, den2
    if _is_neg(x * z_inv % P):
        y = (-y) % P
    s = _ct_abs(den_inv * ((z0 - y) % P) % P)
    return s.to_bytes(32, "little")


def ristretto_eq(p, q) -> bool:
    x1, y1, _, _ = p
    x2, y2, _, _ = q
    return (x1 * y2 - y1 * x2) % P == 0 or (y1 * y2 - x1 * x2) % P == 0


def _pt_scalarmult(k: int, pt):
    return ed._scalarmult(k, pt)


def _pt_add(p, q):
    return ed._add(p, q)


# --- schnorrkel protocol ----------------------------------------------------


def _signing_context(msg: bytes) -> Transcript:
    """reference privkey.go:34: NewSigningContext([]byte{}, msg)."""
    t = Transcript(b"SigningContext")
    t.append_message(b"", b"")
    t.append_message(b"sign-bytes", msg)
    return t


def _expand_ed25519(mini: bytes) -> tuple[int, bytes]:
    """MiniSecretKey.ExpandEd25519: (key scalar = clamped/8, 32-byte nonce)."""
    h = hashlib.sha512(mini).digest()
    key = bytearray(h[:32])
    key[0] &= 248
    key[31] &= 63
    key[31] |= 64
    scalar = int.from_bytes(bytes(key), "little") >> 3  # divide by cofactor
    return scalar, h[32:]


def pubkey_from_mini(mini: bytes) -> bytes:
    scalar, _ = _expand_ed25519(mini)
    return ristretto_encode(_pt_scalarmult(scalar, ed.BASE))


def sign(mini: bytes, msg: bytes, rng_seed: bytes | None = None) -> bytes:
    scalar, nonce = _expand_ed25519(mini)
    pub = ristretto_encode(_pt_scalarmult(scalar, ed.BASE))
    t = _signing_context(msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub)
    r = t.witness_scalar(b"signing", nonce, rng_seed)
    R = _pt_scalarmult(r, ed.BASE)
    r_bytes = ristretto_encode(R)
    t.append_message(b"sign:R", r_bytes)
    k = t.challenge_scalar(b"sign:c")
    s = (k * scalar + r) % L
    sig = bytearray(r_bytes + s.to_bytes(32, "little"))
    sig[63] |= 128  # schnorrkel v1 marker bit
    return bytes(sig)


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != SIGNATURE_SIZE or len(pub) != PUBKEY_SIZE:
        return False
    if sig[63] & 128 == 0:
        return False  # not schnorrkel-marked (reference Signature.Decode)
    a_pt = ristretto_decode(pub)
    r_pt = ristretto_decode(sig[:32])
    if a_pt is None or r_pt is None:
        return False
    s_bytes = bytearray(sig[32:])
    s_bytes[31] &= 127
    s = int.from_bytes(bytes(s_bytes), "little")
    if s >= L:
        return False  # non-canonical scalar
    t = _signing_context(msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub)
    t.append_message(b"sign:R", sig[:32])
    k = t.challenge_scalar(b"sign:c")
    # s*B == R + k*A
    lhs = _pt_scalarmult(s, ed.BASE)
    rhs = _pt_add(r_pt, _pt_scalarmult(k, a_pt))
    return ristretto_eq(lhs, rhs)


# --- the benchmark's own signer: same bytes as sign(), less work -------------


_CTX_PREFIX: list = []  # the constant head of every signing transcript


def sign_fast(mini: bytes, pub: bytes, msg: bytes, rng_seed: bytes) -> bytes:
    """``sign(mini, msg, rng_seed)`` byte for byte, for a key whose public
    key is known: one fixed-base multiplication instead of two generic."""
    scalar, nonce = _expand_ed25519(mini)
    if not _CTX_PREFIX:
        t = Transcript(b"SigningContext")
        t.append_message(b"", b"")
        _CTX_PREFIX.append(t)
    t = _CTX_PREFIX[0].clone()
    t.append_message(b"sign-bytes", msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub)
    r = t.witness_scalar(b"signing", nonce, rng_seed)
    r_bytes = ristretto_encode(ed.base_mult(r))
    t.append_message(b"sign:R", r_bytes)
    k = t.challenge_scalar(b"sign:c")
    sig = bytearray(r_bytes + ((k * scalar + r) % L).to_bytes(32, "little"))
    sig[63] |= 128
    return bytes(sig)


def pubkey_fast(mini: bytes) -> bytes:
    scalar, _ = _expand_ed25519(mini)
    return ristretto_encode(ed.base_mult(scalar))
