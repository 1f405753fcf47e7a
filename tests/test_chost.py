"""Differential tests: C host verifier (ops/chost) vs the pure-Python
scalar references (crypto/ed25519.verify, crypto/sr25519.verify).

The C path is the CPU half of the adaptive kernel/scalar crossover; its
contract is byte-identical accept/reject with the scalar reference
(reference semantics: crypto/ed25519/ed25519.go:148,
crypto/sr25519/pubkey.go:10).  Every case runs through BOTH C modes:
serial (mode 0) and RLC-batch (mode 1, Pippenger with serial fallback),
so a batch-equation bug can never hide behind the fallback."""

from __future__ import annotations

import hashlib
import random

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.crypto import sr25519 as srref
from tendermint_tpu.ops import chost

# ensure_available: build inline -- the non-blocking available() would
# background the build and wrongly skip this whole module on a fresh tree.
pytestmark = pytest.mark.skipif(
    not chost.ensure_available(), reason="C host verifier unavailable (no g++?)")

rng = random.Random(0xC405)


def _keypair(i):
    priv = ref.gen_priv_key(bytes([i + 1]) * 32)
    return priv, priv.pub_key()


def _prep_ed(items):
    n = len(items)
    pubs = np.zeros((n, 32), np.uint8)
    r32 = np.zeros((n, 32), np.uint8)
    s32 = np.zeros((n, 32), np.uint8)
    h32 = np.zeros((n, 32), np.uint8)
    valid = np.zeros((n,), bool)
    for i, (pub, msg, sig) in enumerate(items):
        if len(pub) != 32 or len(sig) != 64:
            continue  # valid stays False, like prepare_scalars' size mask
        valid[i] = True
        pubs[i] = np.frombuffer(pub, np.uint8)
        r32[i] = np.frombuffer(sig[:32], np.uint8)
        s32[i] = np.frombuffer(sig[32:], np.uint8)
        h = int.from_bytes(
            hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % ref.L
        h32[i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
    return pubs, h32, s32, r32, valid


def _check_ed(items):
    expect = np.array([ref.verify(p, m, s) for (p, m, s) in items])
    args = _prep_ed(items)
    for mode in (0, 1, 2):
        got = chost.ed25519_verify(*args, mode=mode)
        assert (got == expect).all(), (
            f"mode={mode} C={got.tolist()} python={expect.tolist()}")


def test_valid_signatures():
    items = []
    for i in range(20):
        priv, pub = _keypair(i)
        msg = b"msg-%d" % i
        items.append((pub.data, msg, ref.sign(priv.data, msg)))
    _check_ed(items)


def test_mixed_corruptions():
    items = []
    for i in range(24):
        priv, pub = _keypair(i % 6)
        msg = b"payload-%d" % i
        sig = bytearray(ref.sign(priv.data, msg))
        if i % 4 == 1:
            sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
        elif i % 4 == 2:
            msg = msg + b"?"
        elif i % 4 == 3:
            sig = bytearray(rng.randbytes(64))
        items.append((pub.data, bytes(msg), bytes(sig)))
    _check_ed(items)


def test_adversarial_encodings():
    """Same vector set as test_ed25519_batch.test_adversarial_encodings."""
    priv, pub = _keypair(7)
    msg = b"edge"
    sig = ref.sign(priv.data, msg)
    s_int = int.from_bytes(sig[32:], "little")
    items = [
        (pub.data, msg, sig[:32] + (s_int + ref.L).to_bytes(32, "little")),
        (pub.data, msg, sig[:32] + ref.L.to_bytes(32, "little")),
        (ref.P.to_bytes(32, "little"), msg, sig),
        ((1).to_bytes(32, "little"), msg, sig),
        ((5).to_bytes(32, "little"), msg, sig),
        ((1 | (1 << 255)).to_bytes(32, "little"), msg, sig),
        (pub.data, msg, ref.P.to_bytes(32, "little") + sig[32:]),
        (pub.data, msg, bytes([sig[0], *sig[1:31], sig[31] ^ 0x80]) + sig[32:]),
        (pub.data[:-1], msg, sig),
        (pub.data, msg, sig[:-1]),
        (b"\x00" * 32, b"", b"\x00" * 64),
        (pub.data, msg, sig),
    ]
    _check_ed(items)


def test_small_order_pubkey_signatures():
    small = (ref.P - 1).to_bytes(32, "little")
    items = []
    for i in range(8):
        r = rng.randbytes(32)
        s = rng.randrange(ref.L).to_bytes(32, "little")
        items.append((small, b"m%d" % i, r + s))
    items.append((small, b"x", (1).to_bytes(32, "little") + b"\x00" * 32))
    _check_ed(items)


def test_forged_sig_under_invalid_pubkey():
    bad_pubs = [
        (5).to_bytes(32, "little"),
        ref.P.to_bytes(32, "little"),
        (1 | (1 << 255)).to_bytes(32, "little"),
    ]
    items = []
    for i, bad in enumerate(bad_pubs):
        s = (i + 2) * 12345 % ref.L
        r_bytes = ref._compress(ref._scalarmult(s, ref.BASE))
        forged = r_bytes + s.to_bytes(32, "little")
        items.append((bad, b"any %d" % i, forged))
    expect = np.array([ref.verify(p, m, s) for (p, m, s) in items])
    assert not expect.any()
    _check_ed(items)


def test_single_bad_item_in_large_batch_attributed():
    """RLC must fail then fall back to serial, attributing exactly the one
    corrupt item (reference per-vote error attribution, types/vote_set.go:205)."""
    items = []
    for i in range(40):
        priv, pub = _keypair(i % 5)
        msg = b"n%d" % i
        sig = ref.sign(priv.data, msg)
        if i == 23:
            sig = sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]
        items.append((pub.data, msg, sig))
    expect = np.array([i != 23 for i in range(40)])
    args = _prep_ed(items)
    for mode in (0, 1):
        got = chost.ed25519_verify(*args, mode=mode)
        assert (got == expect).all()


def test_torsion_component_batch_consistency():
    """Keys/R with torsion components: the mod-8L reduction in the batch
    equation must keep batch-accept == serial-accept (the reason scalars on
    A are reduced mod 8L, not mod L)."""
    # build a mixed-order pubkey: A = [a]B + T where T has order 2
    a = 987654321 % ref.L
    t_pt = ref._decompress((ref.P - 1).to_bytes(32, "little"))
    assert t_pt is not None
    mixed = ref._add(ref._scalarmult(a, ref.BASE), t_pt)
    pub = ref._compress(mixed)
    items = []
    for i in range(12):
        # craft sigs that the serial path accepts: R' = [s]B - [h]A computed
        # with the actual mixed-order A
        s = (a * (i + 3) + 77) % ref.L
        r_guess = ref._compress(ref._scalarmult(s, ref.BASE))
        sig0 = r_guess + s.to_bytes(32, "little")
        msg = b"tors%d" % i
        h = int.from_bytes(
            hashlib.sha512(sig0[:32] + pub + msg).digest(), "little") % ref.L
        negA = (ref.P - mixed[0], mixed[1], mixed[2], (ref.P - mixed[3]) % ref.P)
        rp = ref._add(ref._scalarmult(s, ref.BASE), ref._scalarmult(h, negA))
        # R must be guessed before h; instead use the real construction:
        # pick random r scalar, R = [r]B + torsion sometimes
        items.append((pub, msg, sig0))
        items.append((pub, msg, ref._compress(rp) + s.to_bytes(32, "little")))
    _check_ed(items)


# --- sr25519 -----------------------------------------------------------------


def _prep_sr(items):
    from tendermint_tpu.ops import sr25519_batch as srb

    n = len(items)
    pubs = np.zeros((n, 32), np.uint8)
    r32 = np.zeros((n, 32), np.uint8)
    s32 = np.zeros((n, 32), np.uint8)
    valid = np.zeros((n,), bool)
    for i, (pub, msg, sig) in enumerate(items):
        if len(pub) != 32 or len(sig) != 64:
            continue
        pubs[i] = np.frombuffer(pub, np.uint8)
        r32[i] = np.frombuffer(sig[:32], np.uint8)
        s32[i] = np.frombuffer(sig[32:], np.uint8)
        # schnorrkel v1 marker bit (crypto/sr25519.py verify:358)
        valid[i] = bool(s32[i, 31] & 128)
        s32[i, 31] &= 127
    c32 = srb.challenges([it[1] for it in items], pubs, r32)
    return pubs, c32, s32, r32, valid


def _check_sr(items):
    expect = np.array([srref.verify(p, m, s) for (p, m, s) in items])
    args = _prep_sr(items)
    for mode in (0, 1, 2):
        got = chost.sr25519_verify(*args, mode=mode)
        assert (got == expect).all(), (
            f"mode={mode} C={got.tolist()} python={expect.tolist()}")


def test_sr25519_differential():
    privs = [srref.gen_priv_key(bytes([i + 1])) for i in range(10)]
    items = []
    for i, p in enumerate(privs):
        msg = b"sr-%d" % i
        items.append((p.pub_key().data, msg, p.sign(msg)))
    # corruptions: sig byte, msg, stripped marker bit, bad pub, bad sizes
    items[2] = (items[2][0], items[2][1],
                items[2][2][:40] + b"\x00" + items[2][2][41:])
    items[4] = (items[4][0], items[4][1] + b"!", items[4][2])
    stripped = bytearray(items[6][2])
    stripped[63] &= 127
    items[6] = (items[6][0], items[6][1], bytes(stripped))
    items.append((b"\x01" * 32, b"m", items[0][2]))
    items.append((items[0][0][:-1], b"m", items[0][2]))
    items.append((items[0][0], b"m", items[0][2][:-1]))
    # non-canonical s (>= L with marker bit)
    sbad = bytearray(items[1][2])
    sbad[32:64] = (ref.L + 7).to_bytes(32, "little")
    sbad[63] |= 128
    items.append((items[1][0], b"sr-1", bytes(sbad)))
    _check_sr(items)


def test_routing_host_below_crossover(monkeypatch):
    """ops dispatch routes sub-crossover batches to the host verifier (no
    device work: device_out is None) with bitmaps identical to the kernel."""
    from tendermint_tpu.ops import ed25519_batch as edb

    monkeypatch.setenv("TM_TPU_HOST_CROSSOVER", "512")
    items = []
    for i in range(20):
        priv, pub = _keypair(i % 4)
        msg = b"route-%d" % i
        sig = ref.sign(priv.data, msg)
        if i == 13:
            sig = sig[:5] + bytes([sig[5] ^ 1]) + sig[6:]
        items.append((pub.data, msg, sig))
    dev, finish = edb.dispatch_batch(items)
    assert dev is None, "sub-crossover batch must not touch the device"
    got = finish(None)
    expect = np.array([ref.verify(p, m, s) for (p, m, s) in items])
    assert (np.asarray(got) == expect).all()
    # force_device bypasses the host route (kernel warmup / kernel tests)
    got_dev = edb.verify_batch(items, force_device=True)
    assert (np.asarray(got_dev) == expect).all()


def test_verify_signature_fast_path_matches_reference():
    priv, pub = _keypair(3)
    msg = b"single"
    sig = ref.sign(priv.data, msg)
    assert pub.verify_signature(msg, sig)
    assert not pub.verify_signature(msg + b"x", sig)
    assert not pub.verify_signature(msg, sig[:32] + bytes(32))
    sp = srref.gen_priv_key(b"\x11")
    ssig = sp.sign(b"m")
    assert sp.pub_key().verify_signature(b"m", ssig)
    assert not sp.pub_key().verify_signature(b"n", ssig)


def test_sr25519_bad_item_attribution():
    privs = [srref.gen_priv_key(bytes([i + 40])) for i in range(12)]
    items = []
    for i, p in enumerate(privs):
        msg = b"batch-%d" % i
        sig = p.sign(msg)
        if i == 5:
            sig = sig[:12] + bytes([sig[12] ^ 2]) + sig[13:]
        items.append((p.pub_key().data, msg, sig))
    _check_sr(items)


def test_library_built_for_another_cpu_is_not_loaded(monkeypatch):
    """Both C libraries compile with -march=native, so their file names
    digest the host CPU's features and the compile recipe beside the
    source: a copy of the tree made on another machine finds no library
    under its own name and rebuilds from csrc/*.c instead of dying on an
    illegal instruction in someone else's binary."""
    import os

    from tendermint_tpu.ops import cbuild, chash

    here = {mod: mod._lib_path() for mod in (chost, chash)}
    assert os.path.exists(here[chost])  # the one this module loaded
    assert "flags" in cbuild.host_cpu_tag() or "|" in cbuild.host_cpu_tag()

    monkeypatch.setattr(cbuild, "host_cpu_tag",
                        lambda: "x86_64|avx2 but no avx512f")
    for mod, path in here.items():
        other = mod._lib_path()
        assert other != path and not os.path.exists(other)
        assert os.path.dirname(other) == os.path.dirname(path)
    monkeypatch.undo()

    # ... and so does a changed compile line
    monkeypatch.setattr(chost, "_RECIPE", [["gcc", "-O2", "-shared"]])
    assert chost._lib_path() != here[chost]
