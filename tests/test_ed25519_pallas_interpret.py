"""The ed25519 Pallas kernel on the CPU, in interpret mode.

ops/ed25519_pallas._kernel shares its comb loop (_comb) with the sr25519
kernel; its tail, the program around it and its answers must stay what they
were. One seeded tile of 256 lanes with corrupted lanes among them goes
through ed25519_batch._dispatch_device as on a TPU backend (the backend test
patched, the chunk cut to a tile, _pallas_verify interpreted), and the bitmap
is held against the jnp `_verify_kernel` (the route this CPU takes
unpatched) and the scalar verifier, lane for lane. tests/test_pallas_tpu.py
is the same comparison on the chip.

The unrolled inversion makes the interpreted kernel a 150 s compile on the
CPU; the module fails, rather than hangs, past LIMIT_S."""

import concurrent.futures
import functools

import jax
import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import ed25519_pallas as edp

LIMIT_S = 1200
N_VALID = 40


def _build():
    rng = np.random.default_rng(41)
    privs = [ref.gen_priv_key(bytes([i + 1]) * 32) for i in range(5)]
    items = []
    for i in range(N_VALID):
        p = privs[i % 5]
        msg = b"commit-%d|" % i + rng.bytes(int(rng.integers(0, 90)))
        items.append((p.pub_key().data, msg, ref.sign(p.data, msg)))
    pub, msg, sig = items[0]
    s = int.from_bytes(sig[32:], "little")
    bad = {
        "flipped_message_bit": (pub, bytes([msg[0] ^ 1]) + msg[1:], sig),
        "flipped_s_bit": (pub, msg, sig[:32] + (s ^ 2).to_bytes(32, "little")),
        "flipped_R_bit": (pub, msg, bytes([sig[0] ^ 1]) + sig[1:]),
        "flipped_R_sign": (pub, msg, sig[:31] + bytes([sig[31] ^ 0x80]) + sig[32:]),
        "s_not_below_L": (pub, msg, sig[:32] + (s + ref.L).to_bytes(32, "little")),
        "truncated": (pub, msg, sig[:63]),
        "off_curve_key": (b"\x01" * 32, msg, sig),
        "another_keys_signature": (items[1][0], msg, sig),
    }
    lanes = {}
    for name, item in bad.items():
        lanes[name] = len(items)
        items.append(item)
    return items, lanes


def _through_dispatch(items):
    dev, finish = edb._dispatch_device(items, len(items), False)
    return np.asarray(finish(jax.device_get(dev))), finish.route


def _answers():
    items, lanes = _build()
    assert len(items) <= edp.TILE
    jnp_bits, jnp_route = _through_dispatch(items)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(edb, "_use_pallas", lambda: True)
        mp.setattr(edp, "CHUNK", edp.TILE)
        mp.setattr(edp, "_pallas_verify",
                   functools.partial(edp._pallas_verify, interpret=True))
        # the chunk program as written, traced anew so that it finds the
        # interpreted kernel
        mp.setattr(edp, "_verify_chunk", jax.jit(edp._verify_chunk.__wrapped__))
        pallas_bits, pallas_route = _through_dispatch(items)
    scalar = np.array([ref.verify(*it) for it in items])
    return dict(lanes=lanes, pallas=pallas_bits, jnp=jnp_bits, scalar=scalar,
                routes=(pallas_route, jnp_route))


@pytest.fixture(scope="module")
def answers():
    pool = concurrent.futures.ThreadPoolExecutor(1)
    try:
        return pool.submit(_answers).result(timeout=LIMIT_S)
    except concurrent.futures.TimeoutError:
        pytest.fail(f"the ed25519 kernels took more than {LIMIT_S} s on the CPU")
    finally:
        pool.shutdown(wait=False)


def test_each_branch_ran(answers):
    assert answers["routes"] == ("pallas", "jnp")


def test_valid_signatures_verify(answers):
    for bits in (answers["pallas"], answers["jnp"], answers["scalar"]):
        assert bits[:N_VALID].all()


@pytest.mark.parametrize("case", [
    "flipped_message_bit", "flipped_s_bit", "flipped_R_bit", "flipped_R_sign",
    "s_not_below_L", "truncated", "off_curve_key", "another_keys_signature"])
def test_a_wrong_lane_is_rejected_by_all_three(answers, case):
    lane = answers["lanes"][case]
    assert not answers["scalar"][lane]
    assert not answers["pallas"][lane] and not answers["jnp"][lane]


def test_bitmaps_equal_lane_for_lane(answers):
    assert (answers["pallas"] == answers["jnp"]).all()
    assert (answers["pallas"] == answers["scalar"]).all()
