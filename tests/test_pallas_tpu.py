"""On-chip differential test for the fused Pallas verifier. SKIPPED on CPU
backends (the suite forces CPU; run it on the chip through the chip tool:
`TM_TPU_TEST_BACKEND=tpu python -m pytest tests/test_pallas_tpu.py`)."""

import numpy as np
import pytest

import jax

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="pallas TPU kernel requires a TPU backend",
)


def test_pallas_differential_vs_scalar():
    from tendermint_tpu.crypto import ed25519 as ref
    from tendermint_tpu.ops import ed25519_batch as edb

    assert edb._use_pallas()
    rng = np.random.default_rng(5)
    privs = [ref.gen_priv_key(bytes([i % 250 + 1]) * 32) for i in range(200)]
    items = []
    expect = []
    for i in range(4500):
        p = privs[i % 200]
        msg = b"pl%d" % i + rng.bytes(30)
        sig = ref.sign(p.data, msg)
        bad = i % 11 == 0
        if bad:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append((p.pub_key().data, msg, sig))
        expect.append(not bad)
    # adversarial: S >= L, truncated sig, off-curve pubkey
    items.append((privs[0].pub_key().data, b"x", b"\xff" * 64)); expect.append(False)
    items.append((privs[0].pub_key().data, b"x", b"\x00" * 63)); expect.append(False)
    items.append((b"\x01" * 32, b"x", ref.sign(privs[0].data, b"x"))); expect.append(False)

    out = edb.verify_batch(items)
    assert (out == np.array(expect)).all()
    # scalar differential on a sample
    sample = list(range(0, len(items), 131))
    scal = np.array([ref.verify(*items[i]) for i in sample])
    assert (out[sample] == scal).all()


def test_sr_pallas_differential_vs_host_and_scalar():
    """Two chunks of sr25519 through the Pallas route (_sr_verify_chunk):
    the bitmap equals the C host verifier's on every lane and the scalar
    reference's on every corrupted lane and a sample."""
    from tendermint_tpu.crypto import sr25519 as sr
    from tendermint_tpu.ops import chost
    from tendermint_tpu.ops import ed25519_batch as edb
    from tendermint_tpu.ops import sr25519_batch as srb

    assert edb._use_pallas()
    rng = np.random.default_rng(9)
    privs = [sr.gen_priv_key(bytes([i + 1]) * 4) for i in range(8)]
    base = []
    for i in range(64):
        p = privs[i % 8]
        msg = b"v%d|" % i + rng.bytes(int(rng.integers(0, 100)))
        base.append((p.pub_key().data, msg,
                     sr.sign(p.data, msg, rng_seed=bytes([i + 1]) * 32)))
    items = (base * 80)[:4500]
    bad = list(range(0, len(items), 37))
    for j in bad:
        pub, msg, sig = items[j]
        items[j] = [
            (pub, msg + b"!", sig),                                    # message
            (pub, msg, sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]),    # s
            (pub, msg, bytes([sig[0] ^ 2]) + sig[1:]),                 # R
            (pub, msg, (sr.P - 1).to_bytes(32, "little") + sig[32:]),  # y = 0
            (b"\x02" + bytes(31), msg, sig),                           # key
            (pub, msg, sig[:63] + bytes([sig[63] & 0x7F])),            # marker
        ][j // 37 % 6]
    dev, finish = srb.dispatch_batch(items)
    out = finish(jax.device_get(dev))
    assert finish.route == "pallas" and srb.BREAKER.failures == 0
    assert not out[bad].any() and out.sum() == len(items) - len(bad)
    if chost.ensure_available():
        _, host = srb._host_fallback(items, len(items))
        assert (out == host(None)).all()
    sample = sorted(set(bad[:40] + list(range(1, len(items), 301))))
    assert (out[sample] == np.array([sr.verify(*items[i]) for i in sample])).all()


@pytest.mark.skipif(jax.default_backend() != "tpu"
                    or jax.local_device_count() < 2,
                    reason="placing chunks needs two TPU devices")
def test_a_commit_of_9999_on_every_local_chip_vs_the_serial_reference():
    """The "sharded" route (ops/ed25519_pallas.dispatch_chunks, multichip):
    a 9,999-signature commit is three Pallas chunks on three chips, then a
    batch one signature past ndev - 1 chunks puts a chunk on every chip;
    both bitmaps equal the serial reference's on every corrupted lane (one
    sort a chunk) and a sample, piece for piece in chunk order."""
    from tendermint_tpu.crypto import ed25519 as ref
    from tendermint_tpu.ops import ed25519_batch as edb
    from tendermint_tpu.ops import ed25519_pallas as edp

    ndev = jax.local_device_count()
    rng = np.random.default_rng(33)
    privs = [ref.gen_priv_key(bytes([i % 250 + 1]) * 32) for i in range(200)]
    items = []
    for i in range(9999):
        p = privs[i % 200]
        msg = b"c%d" % i + rng.bytes(20)
        items.append((p.pub_key().data, msg, ref.sign(p.data, msg)))
    pub, msg, sig = items[0]
    s = int.from_bytes(sig[32:], "little")
    wrong = {
        17: (pub, msg, sig[:9] + bytes([sig[9] ^ 4]) + sig[10:]),       # bit
        edp.CHUNK + 5: (pub, msg, sig[:32]
                        + (s + ref.L).to_bytes(32, "little")),          # S >= L
        2 * edp.CHUNK + 7: (pub, msg, sig[:63]),                       # short
        9998: (b"\x02" + b"\x00" * 31, msg, sig),                      # no point
    }
    for lane, item in wrong.items():
        items[lane] = item

    def check(batch, chunks):
        assert edb.route_batch(len(batch)) == "sharded"
        dev, finish = edb.dispatch_batch(batch)
        assert len(dev) == chunks and finish.route == "sharded"
        local = jax.local_devices()
        assert [next(iter(p.devices())) for p in dev] == [
            local[k % ndev] for k in range(chunks)]
        out = finish(jax.device_get(dev))
        bad = sorted(j for j in range(len(batch)) if j % 9999 in wrong)
        assert sorted(np.flatnonzero(~out)) == bad
        sample = sorted(set(bad + list(range(3, len(batch), 257))))
        assert (out[sample]
                == np.array([ref.verify(*batch[j]) for j in sample])).all()
        return out

    failures = edb.BREAKER.failures
    out = check(items, 3)
    one_chip = edb._dispatch_device(items, len(items), False)
    assert (one_chip[1](jax.device_get(one_chip[0])) == out).all()
    check((items * ndev)[: (ndev - 1) * edp.CHUNK + 1], ndev)
    assert edb.BREAKER.failures == failures


def test_the_table_build_kernel_vs_the_jnp_loop_and_one_commit_over_both(
        monkeypatch):
    """4,096 seeded keys (i * B: the signer's scalar is i) built by the
    Pallas kernel and by the jnp program, a tile a launch, hold
    the same points and the same niels rows mod p, and one commit over all
    of them (a signature a key, corrupted lanes among them) gets the same
    bitmap over either table, lane for lane, and the scalar verifier's."""
    import hashlib

    import jax.numpy as jnp

    from tendermint_tpu.crypto import ed25519 as ref
    from tendermint_tpu.ops import ed25519_batch as edb
    from tendermint_tpu.ops import ed25519_pallas as edp
    from tendermint_tpu.ops import edwards25519 as ed
    from tendermint_tpu.utils import trace

    n = edp.CHUNK
    base = (ref.BASE[0], ref.BASE[1])

    def enc(pt):
        return (pt[1] | ((pt[0] & 1) << 255)).to_bytes(32, "little")

    # key i is (i + 1) B, nonce point i is (r0 + i) B: affine adds, no
    # scalar multiplication a signature
    r0 = 0x5EED_0049
    a_pt, r_pt = base, ref._scalarmult(r0, ref.BASE)
    zi = pow(r_pt[2], -1, ref.P)
    r_pt = (r_pt[0] * zi % ref.P, r_pt[1] * zi % ref.P)
    items = []
    for i in range(n):
        pub, r_enc, msg = enc(a_pt), enc(r_pt), b"table build vote %d" % i
        h = int.from_bytes(hashlib.sha512(r_enc + pub + msg).digest(),
                           "little") % ref.L
        s = (r0 + i + h * (i + 1)) % ref.L
        items.append((pub, msg, r_enc + s.to_bytes(32, "little")))
        a_pt, r_pt = ed.affine_add(a_pt, base), ed.affine_add(r_pt, base)
    bad = [0, 255, 256, 1000, n - 1]
    for j in bad:
        pub, msg, sig = items[j]
        items[j] = (pub, msg + b"!", sig)

    a_neg = np.stack([edb._decompress_neg(it[0]) for it in items])
    by_kernel = jnp.concatenate(
        [edp.build_comb_tile(a_neg[o : o + edp.TILE])
         for o in range(0, n, edp.TILE)], axis=0)
    by_loop = edb._build_comb_tables_tiled(a_neg)
    weights = np.array([1 << (13 * k) for k in range(20)], dtype=object)

    def ints(limbs):
        return (np.asarray(limbs).astype(object) * weights).sum(-1) % ref.P

    k_pts, l_pts = ints(by_kernel), ints(by_loop)      # (n, 16, 4)
    for c in (0, 1):                                   # X1 Z2 = X2 Z1, Y too
        assert ((k_pts[..., c] * l_pts[..., 2]
                 - l_pts[..., c] * k_pts[..., 2]) % ref.P == 0).all()
    assert (k_pts[..., 2] != 0).all()
    assert ((k_pts[..., 0] * k_pts[..., 1]
             - k_pts[..., 2] * k_pts[..., 3]) % ref.P == 0).all()
    assert (ints(np.asarray(edb._to_niels(by_kernel)).reshape(n, 48, 20))
            == ints(np.asarray(edb._to_niels(by_loop)).reshape(n, 48, 20))
            ).all()

    def commit(build):
        monkeypatch.setattr(edb, "_KS_CACHE", type(edb._KS_CACHE)())
        monkeypatch.setattr(edb, "_KS_UNIQ_CACHE", edb.KeyTable())
        monkeypatch.setattr(edp, "build_comb_tile", build)
        dev, finish = edb.dispatch_batch(items, force_device=True)
        out = finish(jax.device_get(dev))
        assert finish.route == "pallas" and edb._KS_UNIQ_CACHE.keyset.n_rows == n
        return out

    failures = edb.BREAKER.failures
    over_kernel = commit(edp.build_comb_tile)
    tags = [s.tags for s in trace.STARTUP.dump()
            if s.name == "startup.table_build"][-1]
    assert tags == {"keys": n, "kind": "ed25519", "program": "pallas",
                    "launches": n // edp.TILE, "rows": n}
    over_loop = commit(edb._build_comb_tables_tiled)
    assert edb.BREAKER.failures == failures
    assert (over_kernel == over_loop).all()
    assert sorted(np.flatnonzero(~over_kernel)) == bad
    sample = sorted(set(bad + list(range(7, n, 211))))
    assert (over_kernel[sample]
            == np.array([ref.verify(*items[j]) for j in sample])).all()
