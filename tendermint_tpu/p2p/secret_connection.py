"""SecretConnection: authenticated encryption for the peer wire
(reference: p2p/conn/secret_connection.go:63,92,139-143).

STS-shaped construction, v0.33-style key schedule — NOT wire-interoperable
with reference v0.34 nodes (which derive the auth challenge from a Merlin
transcript, secret_connection.go:92-143); framework peers interoperate with
each other:
 1. exchange ephemeral X25519 pubkeys (32 bytes, length-delimited);
 2. DH -> shared secret; HKDF-SHA256 expand to 96 bytes: send/recv keys
    (ordering by lexicographic comparison of the ephemeral pubkeys) plus a
    32-byte challenge (okm[64:96], in place of the reference's Merlin
    transcript challenge);
 3. all further traffic in ChaCha20-Poly1305 sealed frames: 4-byte LE length
    + payload, padded to 1024 bytes; 12-byte nonce with a LE u64 counter in
    bytes [4:12) per direction (same layout as secret_connection.go:455-463);
 4. exchange (node ed25519 pubkey, sig over challenge) inside the encrypted
    channel and verify.
"""

from __future__ import annotations

import hashlib
import hmac
import os
import socket
import struct
import threading

try:
    from cryptography.hazmat.primitives.asymmetric.x25519 import (
        X25519PrivateKey,
        X25519PublicKey,
    )
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305
except ModuleNotFoundError:  # image without `cryptography`: RFC-exact fallback
    from tendermint_tpu.crypto.purecrypto import (
        ChaCha20Poly1305,
        X25519PrivateKey,
        X25519PublicKey,
    )

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.encoding import proto

DATA_MAX_SIZE = 1024
FRAME_SIZE = 4 + DATA_MAX_SIZE
SEALED_FRAME_SIZE = FRAME_SIZE + 16  # AEAD tag


class SecretConnectionError(Exception):
    pass


def _hkdf_sha256(secret: bytes, info: bytes, length: int) -> bytes:
    """RFC 5869 HKDF with empty salt (reference uses the same)."""
    prk = hmac.new(b"\x00" * 32, secret, hashlib.sha256).digest()
    out = b""
    block = b""
    i = 1
    while len(out) < length:
        block = hmac.new(prk, block + info + bytes([i]), hashlib.sha256).digest()
        out += block
        i += 1
    return out[:length]


def _read_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise SecretConnectionError("connection closed during read")
        buf += chunk
    return buf


class SecretConnection:
    """Wraps a connected socket. Thread-safe for one reader + one writer."""

    def __init__(self, sock: socket.socket, priv_key: ed25519.PrivKey):
        self._sock = sock
        self._send_lock = threading.Lock()
        self._recv_lock = threading.Lock()
        self._recv_buf = b""
        self._send_nonce = 0
        self._recv_nonce = 0
        # sealed frames and their bytes, per direction (the handshake's
        # included): plain integers, each written under its own lock
        self.frames_sent = self.frames_recv = 0
        self.sealed_bytes_sent = self.sealed_bytes_recv = 0

        # 1. ephemeral key exchange
        eph_priv = X25519PrivateKey.generate()
        eph_pub = eph_priv.public_key().public_bytes_raw()
        sock.sendall(proto.delimited(proto.Writer().bytes(1, eph_pub).out()))
        hdr = _read_exact(sock, 1)
        # delimited BytesValue: varint len (<=127 here) + msg
        (ln,) = hdr
        msg = _read_exact(sock, ln)
        fields = proto.fields(msg)
        remote_eph = fields.get(1, [b""])[-1]
        if len(remote_eph) != 32:
            raise SecretConnectionError("bad ephemeral key")

        # 2. DH + HKDF key schedule
        shared = eph_priv.exchange(X25519PublicKey.from_public_bytes(remote_eph))
        lo, hi = sorted([eph_pub, remote_eph])
        we_are_lo = eph_pub == lo
        okm = _hkdf_sha256(shared, b"TENDERMINT_SECRET_CONNECTION_KEY_AND_CHALLENGE_GEN", 96)
        if we_are_lo:
            recv_key, send_key = okm[0:32], okm[32:64]
        else:
            send_key, recv_key = okm[0:32], okm[32:64]
        challenge = okm[64:96]
        self._send_aead = ChaCha20Poly1305(send_key)
        self._recv_aead = ChaCha20Poly1305(recv_key)

        # 3. authenticate: exchange (pubkey, sig(challenge)) encrypted
        sig = priv_key.sign(challenge)
        auth = (
            proto.Writer()
            .message(1, proto.Writer().bytes(1, priv_key.pub_key().bytes()).out(), always=True)
            .bytes(2, sig)
            .out()
        )
        self.write(auth)
        remote_auth = self.read_msg()
        f = proto.fields(remote_auth)
        pk_fields = proto.fields(f.get(1, [b""])[-1])
        remote_pub_bytes = pk_fields.get(1, [b""])[-1]
        remote_sig = f.get(2, [b""])[-1]
        remote_pub = ed25519.PubKey(remote_pub_bytes)
        if not remote_pub.verify_signature(challenge, remote_sig):
            raise SecretConnectionError("challenge verification failed")
        self.remote_pub_key = remote_pub

    # --- framed encrypted IO ----------------------------------------------

    def write(self, data: bytes) -> None:
        """Writes data as one message (split into sealed frames)."""
        with self._send_lock:
            pos = 0
            first = True
            while pos < len(data) or first:
                first = False
                chunk = data[pos : pos + DATA_MAX_SIZE]
                pos += len(chunk)
                frame = struct.pack("<I", len(chunk)) + chunk
                frame += b"\x00" * (FRAME_SIZE - len(frame))
                nonce = b"\x00" * 4 + struct.pack("<Q", self._send_nonce)
                self._send_nonce += 1
                sealed = self._send_aead.encrypt(nonce, frame, None)
                # _send_lock exists to serialize exactly this write (nonce
                # order must match wire order); it guards nothing else
                self._sock.sendall(sealed)  # tmlint: disable=lock-held-call
                self.frames_sent += 1
                self.sealed_bytes_sent += len(sealed)

    def _read_frame(self) -> bytes:
        sealed = _read_exact(self._sock, SEALED_FRAME_SIZE)
        self.frames_recv += 1
        self.sealed_bytes_recv += len(sealed)
        nonce = b"\x00" * 4 + struct.pack("<Q", self._recv_nonce)
        self._recv_nonce += 1
        try:
            frame = self._recv_aead.decrypt(nonce, sealed, None)
        except Exception as e:  # noqa: BLE001
            raise SecretConnectionError(f"frame decryption failed: {e}") from e
        (ln,) = struct.unpack_from("<I", frame)
        if ln > DATA_MAX_SIZE:
            raise SecretConnectionError("frame length too big")
        return frame[4 : 4 + ln]

    def read(self, max_bytes: int = DATA_MAX_SIZE) -> bytes:
        """Stream-style read of up to max_bytes."""
        with self._recv_lock:
            if not self._recv_buf:
                self._recv_buf = self._read_frame()
            out = self._recv_buf[:max_bytes]
            self._recv_buf = self._recv_buf[max_bytes:]
            return out

    def read_msg(self) -> bytes:
        """Reads one frame's payload (used during handshake)."""
        with self._recv_lock:
            return self._read_frame()

    def close(self) -> None:
        # shutdown first: close() alone neither wakes a thread blocked in
        # recv() on this socket nor sends the FIN while that thread holds
        # it, so the reader would outlive the connection and the far end
        # would keep a dead peer until its pong timeout
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
