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
