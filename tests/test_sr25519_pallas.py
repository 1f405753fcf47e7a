"""Differential test of the sr25519 Pallas chunk, on the CPU.

On a TPU backend an sr25519 batch on the `device` route runs
ops/ed25519_pallas._sr_verify_chunk: the comb loop of the ed25519 kernel and
a ristretto tail (decode of R, projective coset comparison). Here the same
kernel body runs in Pallas's interpret mode on ONE tile of 256 lanes,
reached the way a chip reaches it (sr25519_batch._dispatch_device with the
backend test patched and the chunk cut to a tile), and every lane's answer
is held against the jnp kernel `_sr_verify_kernel` (the route this CPU takes
unpatched) and against the scalar reference crypto/sr25519.verify. The
ristretto decode is also compared with the reference value for value, since
a dropped validity flag would hide behind a failing equation.

One cold compile of each kernel (about 40 s Pallas, 90 s jnp) is most of the
time; the module fails, rather than hangs, past LIMIT_S."""

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu.crypto import sr25519 as sr
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import ed25519_pallas as edp
from tendermint_tpu.ops import field25519 as fe
from tendermint_tpu.ops import sr25519_batch as srb

LIMIT_S = 900
P, L = sr.P, sr.L


def _classify(s: int) -> str:
    """What the reference's ristretto_decode makes of the even s < p."""
    ss = s * s % P
    u1, u2 = (1 - ss) % P, (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(sr.D * u1 % P * u1) - u2_sqr) % P
    was_square, invsqrt = sr._sqrt_ratio_m1(1, v * u2_sqr % P)
    den_x = invsqrt * u2 % P
    x = sr._ct_abs(2 * s % P * den_x % P)
    y = u1 * (invsqrt * den_x % P * v % P) % P
    if not was_square:
        return "non_square"
    if sr._is_neg(x * y % P):
        return "negative_xy"
    return "y_zero" if y == 0 else "ok"


def _first_encoding(kind: str) -> bytes:
    """The smallest even s >= 2 the reference classifies as `kind`."""
    s = 2
    while _classify(s) != kind:
        s += 2
    return s.to_bytes(32, "little")


def _with_s(sig: bytes, s: int) -> bytes:
    return sig[:32] + (s | 1 << 255).to_bytes(32, "little")


def _build():
    """-> (items, {case: lane}): 16 valid signatures of 4 keys, then one lane
    per way a signature can be wrong."""
    rng = np.random.default_rng(31)
    privs = [sr.gen_priv_key(bytes([i + 1]) * 4) for i in range(4)]
    items = []
    for i in range(16):
        p = privs[i % 4]
        msg = b"vote-%d|" % i + rng.bytes(int(rng.integers(0, 120)))
        items.append((p.pub_key().data, msg,
                      sr.sign(p.data, msg, rng_seed=bytes([i + 1]) * 32)))
    pub, msg, sig = items[0]
    s = int.from_bytes(sig[32:], "little") & ((1 << 255) - 1)
    non_square = _first_encoding("non_square")
    bad = {
        "flipped_message_bit": (pub, bytes([msg[0] ^ 1]) + msg[1:], sig),
        "flipped_s_bit": (pub, msg, _with_s(sig, s ^ 2)),
        "s_not_below_L": (pub, msg, _with_s(sig, s + L)),
        "R_not_below_p": (pub, msg, (P + 1).to_bytes(32, "little") + sig[32:]),
        "odd_R": (pub, msg, bytes([sig[0] | 1]) + sig[1:]),
        "non_square_R": (pub, msg, non_square + sig[32:]),
        "negative_xy_R": (pub, msg, _first_encoding("negative_xy") + sig[32:]),
        "y_zero_R": (pub, msg, (P - 1).to_bytes(32, "little") + sig[32:]),
        "off_curve_key": (non_square, msg, sig),
        "wrong_marker": (pub, msg, sig[:63] + bytes([sig[63] & 0x7F])),
        "another_keys_signature": (items[1][0], msg, sig),
    }
    assert s + L < 1 << 255 and _classify(P - 1) == "y_zero"
    lanes = {}
    for name, item in bad.items():
        lanes[name] = len(items)
        items.append(item)
    return items, lanes


def _through_dispatch(items):
    dev, finish = srb._dispatch_device(items, len(items))
    return np.asarray(finish(jax.device_get(dev))), finish.route


def _answers():
    items, lanes = _build()
    n = len(items)
    assert n <= edp.TILE
    jnp_bits, jnp_route = _through_dispatch(items)
    with pytest.MonkeyPatch.context() as mp:
        # what a TPU backend does, one tile wide, the body interpreted
        mp.setattr(edb, "_use_pallas", lambda: True)
        mp.setattr(edp, "CHUNK", edp.TILE)
        mp.setattr(edp, "_sr_verify_chunk",
                   functools.partial(edp._sr_verify_chunk, interpret=True))
        pallas_bits, pallas_route = _through_dispatch(items)
    scalar = np.array([sr.verify(*it) for it in items])
    return dict(lanes=lanes, n=n, pallas=pallas_bits, jnp=jnp_bits,
                scalar=scalar, routes=(pallas_route, jnp_route))


@pytest.fixture(scope="module")
def answers():
    pool = concurrent.futures.ThreadPoolExecutor(1)
    try:
        return pool.submit(_answers).result(timeout=LIMIT_S)
    except concurrent.futures.TimeoutError:
        pytest.fail(f"the sr25519 kernels took more than {LIMIT_S} s on the CPU")
    finally:
        pool.shutdown(wait=False)


def test_each_branch_ran(answers):
    assert answers["routes"] == ("pallas", "jnp")
    assert answers["pallas"].shape == (answers["n"],)


def test_valid_signatures_verify(answers):
    for bits in (answers["pallas"], answers["jnp"], answers["scalar"]):
        assert bits[:16].all()


@pytest.mark.parametrize("case", [
    "flipped_message_bit", "flipped_s_bit", "s_not_below_L", "R_not_below_p",
    "odd_R", "non_square_R", "negative_xy_R", "y_zero_R", "off_curve_key",
    "wrong_marker", "another_keys_signature"])
def test_a_wrong_lane_is_rejected_by_all_three(answers, case):
    lane = answers["lanes"][case]
    assert not answers["scalar"][lane]
    assert not answers["pallas"][lane] and not answers["jnp"][lane]


def test_bitmaps_equal_lane_for_lane(answers):
    assert (answers["pallas"] == answers["jnp"]).all()
    assert (answers["pallas"] == answers["scalar"]).all()


# --- the decode alone, value for value --------------------------------------


@pytest.fixture(scope="module")
def decoded():
    """The kernel's decode as plain XLA ops over encodings of every class
    -> [(s, x, y, ok)] with x, y as canonical ints."""
    rng = np.random.default_rng(32)
    enc = [int.from_bytes(_first_encoding(k), "little")
           for k in ("ok", "non_square", "negative_xy")] + [P - 1, 0]
    enc += [int(v) * 2 for v in rng.integers(1, 1 << 62, size=27)]
    enc += [int.from_bytes(sr.gen_priv_key(bytes([i + 9]) * 4).pub_key().data,
                           "little") for i in range(8)]
    limbs = jnp.asarray(np.stack([fe.from_int(s) for s in enc]).T)
    consts = jnp.asarray(edp.SR_CONSTS)

    @jax.jit
    def run(limbs):
        edp._bind_consts(consts)
        x, y, ok = edp._ristretto_decode(limbs, consts)
        return edp._to_canonical(x), edp._to_canonical(y), ok

    x, y, ok = (np.asarray(a) for a in run(limbs))
    return [(s, fe.to_int(x[:, i]), fe.to_int(y[:, i]), bool(ok[0, i]))
            for i, s in enumerate(enc)]


def test_decode_flags_are_the_references(decoded):
    kinds = {_classify(s) for s, *_ in decoded}
    assert kinds == {"ok", "non_square", "negative_xy", "y_zero"}
    for s, _x, _y, ok in decoded:
        assert ok == (sr.ristretto_decode(s.to_bytes(32, "little")) is not None)


def test_decoded_points_are_the_references(decoded):
    seen = 0
    for s, x, y, ok in decoded:
        pt = sr.ristretto_decode(s.to_bytes(32, "little"))
        if pt is not None:
            assert ok and (x, y) == pt[:2]
            seen += 1
    assert seen >= 12
