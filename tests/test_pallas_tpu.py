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
