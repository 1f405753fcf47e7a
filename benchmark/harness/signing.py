"""Signing for data generation: keys and signatures as a function of the seed.

ed25519 goes through OpenSSL (the ``cryptography`` package) when it imports,
else through the benchmark's own pure-Python signer; RFC 8032 signing is
deterministic, so the bytes are the same either way
(tests/benchmark/test_data.py). sr25519 is the benchmark's pure-Python
schnorrkel signer with the witness randomness taken from the seed.

This module and everything it imports stay off jax and numpy: the chip
belongs to the one process that runs the cell, and ``SignerPool`` fans the
signing out over spawned children that only ever import this.
"""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import os

from benchmark.reference import ed25519_ref, sr25519_ref

ED25519, SR25519 = "ed25519", "sr25519"


def have_openssl() -> bool:
    try:
        from cryptography.hazmat.primitives.asymmetric import ed25519  # noqa: F401
    except ImportError:
        return False
    return True


def public_keys(kind: str, openssl: bool, secrets: list[bytes]) -> list[bytes]:
    if kind == SR25519:
        return [sr25519_ref.pubkey_fast(s) for s in secrets]
    if openssl:
        from cryptography.hazmat.primitives import serialization as ser
        from cryptography.hazmat.primitives.asymmetric import ed25519

        return [ed25519.Ed25519PrivateKey.from_private_bytes(s).public_key()
                .public_bytes(ser.Encoding.Raw, ser.PublicFormat.Raw)
                for s in secrets]
    return [ed25519_ref.pubkey_fixed_base(s) for s in secrets]


def sign_jobs(kind: str, openssl: bool, jobs: list[tuple]) -> list[bytes]:
    """jobs: (secret, public key, message, rng seed) -> signatures, in order."""
    if kind == SR25519:
        return [sr25519_ref.sign_fast(sk, pk, msg, rng)
                for sk, pk, msg, rng in jobs]
    if openssl:
        from cryptography.hazmat.primitives.asymmetric import ed25519

        return [ed25519.Ed25519PrivateKey.from_private_bytes(sk).sign(msg)
                for sk, _pk, msg, _rng in jobs]
    return [ed25519_ref.sign_fixed_base(sk, pk, msg)
            for sk, pk, msg, _rng in jobs]


class SignerPool:
    """``workers`` spawned children (0 = sign in this process). A context
    manager: leaving it waits until every child has ended."""

    def __init__(self, workers: int | None = None, openssl: bool | None = None):
        if workers is None:
            workers = min(12, max(1, (os.cpu_count() or 2) - 1))
        self.workers = workers
        self.openssl = have_openssl() if openssl is None else openssl
        self._pool = None

    def __enter__(self) -> "SignerPool":
        if self.workers > 0:
            self._pool = concurrent.futures.ProcessPoolExecutor(
                self.workers, mp_context=multiprocessing.get_context("spawn"))
        return self

    def __exit__(self, *exc) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool = None

    def _fan_out(self, fn, kind: str, rows: list, min_chunk: int) -> list:
        if self._pool is None or len(rows) < 2 * min_chunk:
            return fn(kind, self.openssl, rows)
        step = max(min_chunk, -(-len(rows) // (self.workers * 2)))
        futs = [self._pool.submit(fn, kind, self.openssl, rows[i:i + step])
                for i in range(0, len(rows), step)]
        return [out for f in futs for out in f.result()]

    def public_keys(self, kind: str, secrets: list[bytes]) -> list[bytes]:
        return self._fan_out(public_keys, kind, secrets, 64)

    def sign(self, kind: str, jobs: list[tuple]) -> list[bytes]:
        # OpenSSL signs in ~50 us: a chunk must outweigh its pickling
        return self._fan_out(sign_jobs, kind, jobs,
                             2048 if kind == ED25519 and self.openssl else 16)
