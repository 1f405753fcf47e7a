"""The "sharded" route: Pallas chunks placed one a local device
(ops/ed25519_pallas.launch_chunks under dispatch_chunks), a TPU host's chips
played by the CPU's forced devices.

The chunk programs are stood in for by the jnp kernels over the niels rows
the chunk was GIVEN (so a device's copy of the key table decides the answer),
with the chunk's own device-side preparation (mod-L reduction, comb windows,
R's limbs) as written; a chunk is cut to one 256-lane tile, as the interpret
tests cut it. The stand-in notes where its inputs live, computes on the first
device (one compile of each jnp kernel, not one a device) and puts its answer
back where the chunk was placed. tests/test_pallas_tpu.py runs the real
programs on every chip."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.crypto import sr25519 as sr
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import ed25519_pallas as edp
from tendermint_tpu.ops import field25519 as fe
from tendermint_tpu.ops import sr25519_batch as srb

LANES = edp.TILE
NDEV = 3          # of the eight: four chunks wrap around to the first
_INV2 = fe.from_int((ref.P + 1) // 2)


@jax.jit
def _ext_from_niels(tab):
    """(960, N) lane-major niels rows (y+x | y-x | 2dxy) -> (N, 16, 4, 20)
    extended points, what the jnp kernels gather from."""
    n = tab.shape[1]
    niels = tab.T.reshape(n, 16, 3, 20)
    ypx, ymx = niels[:, :, 0], niels[:, :, 1]
    inv2 = jnp.asarray(_INV2)
    x = fe.mul(fe.sub(ypx, ymx), inv2)
    y = fe.mul(fe.add(ypx, ymx), inv2)
    one = jnp.zeros_like(x).at[..., 0].set(1)
    return jnp.stack([x, y, one, fe.mul(x, y)], axis=2)


@jax.jit
def _ed_prep(h64, s32, r32, valid):
    r_y, sign = edp._r_limbs_device(r32)
    return (edp._windows_from_limbs12(edp._reduce_mod_l_device(h64)).T,
            edp._windows_device(s32).T, r_y.T, sign[0], valid[0] != 0)


@jax.jit
def _sr_prep(k32, s32, r32, valid):
    return (edp._windows_device(k32).T, edp._windows_device(s32).T,
            edp._r_limbs_device(r32)[0].T, valid[0] != 0)


def _ed_chunk(tab, h64, s32, r32, valid):
    return edb._jnp_kernel(_ext_from_niels(tab), *_ed_prep(h64, s32, r32, valid))


def _sr_chunk(tab, k32, s32, r32, valid):
    return srb._kernel(_ext_from_niels(tab), *_sr_prep(k32, s32, r32, valid))


Kind = collections.namedtuple("Kind", "name mod body attr sign decode")


def _ed_sign(i, msg):
    priv = ref.gen_priv_key(bytes([i + 1]) * 32)
    return priv.pub_key().data, ref.sign(priv.data, msg)


def _sr_sign(i, msg):
    priv = sr.gen_priv_key(bytes([i + 1]) * 4)
    return priv.pub_key().data, sr.sign(priv.data, msg,
                                        rng_seed=bytes([i + 1]) * 32)


KINDS = [
    Kind("ed25519", edb, _ed_chunk, "_verify_chunk", _ed_sign,
         edb._decompress_neg),
    Kind("sr25519", srb, _sr_chunk, "_sr_verify_chunk", _sr_sign,
         srb._decode_neg),
]


@pytest.fixture(params=KINDS, ids=lambda k: k.name)
def kind(request):
    return request.param


@pytest.fixture
def placed(monkeypatch, fake_tpu_host):
    """A TPU host of NDEV chips, as far as the host code can tell: the
    backend test says Pallas, the chunk is a tile, both chunk programs are
    the stand-ins. -> the device of every chunk launched, in order."""
    devices = fake_tpu_host(NDEV, LANES)
    seen = []

    def stand_in(body):
        def chunk(tab, *cols):
            where = {d for a in (tab,) + cols for d in a.devices()}
            assert len(where) == 1, where   # a chunk's inputs are on ONE device
            at = where.pop()
            seen.append(at)
            ok = body(*(jax.device_put(a, devices[0]) for a in (tab,) + cols))
            return jax.device_put(ok[None, :].astype(jnp.int32), at)
        return chunk

    for k in KINDS:
        monkeypatch.setattr(edp, k.attr, stand_in(k.body))
    cbatch.forget_keys()
    yield seen
    cbatch.forget_keys()


def _items(kind, n):
    """n valid (pub, msg, sig): sixteen signed (sr25519 signs in pure
    Python), tiled."""
    base = []
    for i in range(16):
        msg = b"placed-%d" % i
        pub, sig = kind.sign(i % 5, msg)
        base.append((pub, msg, sig))
    return (base * -(-n // 16))[:n]


def _scalar(kind, items):
    verify = ref.verify if kind.name == "ed25519" else sr.verify
    return np.array([verify(*it) for it in items])


def _run(kind, items, multichip):
    dev, finish = kind.mod._dispatch_device(items, len(items), multichip)
    return dev, np.asarray(finish(cbatch._device_get(dev))), finish.route


def test_chunks_land_round_robin_and_answers_come_back_in_chunk_order(
        kind, placed):
    """Four chunks on three devices: chunk k on device k mod 3, a packed
    piece a chunk on the device that computed it, and a bitmap that equals
    the one-device loop's and the serial reference's with one wrong
    signature of another sort in each chunk."""
    devices = jax.local_devices()
    n = 3 * LANES + 40
    items = _items(kind, n)
    pub, msg, sig = items[0]
    s = int.from_bytes(sig[32:], "little")
    top = s >> 255 << 255   # sr25519's marker bit stays where it is
    wrong = {
        7: (pub, msg, sig[:5] + bytes([sig[5] ^ 1]) + sig[6:]),  # flipped bit
        LANES + 9: (pub, msg, sig[:32] + (
            (s - top + ref.L) | top).to_bytes(32, "little")),    # S >= L
        2 * LANES + 11: (pub, msg, sig[:63]),                    # truncated
        3 * LANES + 13: (b"\x02" + b"\x00" * 31, msg, sig),      # off curve
    }
    assert (s - top) + ref.L < 1 << 255
    assert kind.decode(wrong[3 * LANES + 13][0]) is None
    for lane, item in wrong.items():
        items[lane] = item

    assert edb.route_batch(n) == "sharded"
    dev, bits, route = _run(kind, items, multichip=True)
    assert route == "sharded"
    want = [devices[k % NDEV] for k in range(4)]
    assert placed == want
    assert [next(iter(p.devices())) for p in dev] == want
    assert all(p.shape == (LANES // 32,) for p in dev)

    del placed[:]
    one_dev, one, one_route = _run(kind, items, multichip=False)
    assert one_route == "pallas" and len(one_dev) == 1
    assert placed == [devices[0]] * 4
    assert bits.shape == (n,) and (bits == one).all()
    assert (bits == _scalar(kind, items)).all()
    assert sorted(np.flatnonzero(~bits)) == sorted(wrong)


def test_a_key_appended_later_verifies_on_another_device(kind, placed):
    """The per-device copies of the key table are written a tile at a time
    by append, and grown with it: keys admitted after the copies exist, more
    than a tile of them, verify in the chunk placed on the second device."""
    devices = jax.local_devices()
    table = kind.mod._KS_UNIQ_CACHE
    items = _items(kind, 2 * LANES)
    _dev, bits, _route = _run(kind, items, multichip=True)
    assert bits.all()
    ks = table.keyset
    assert set(ks._niels_on) == {devices[1]}
    cap = ks.valid.shape[0]

    # more than a tile of joiners; the first and the last of them sign
    signed = [kind.sign(100 + j, b"late-%d" % j) for j in range(2)]
    late = [(pub, b"late-%d" % j, sig) for j, (pub, sig) in enumerate(signed)]
    joiners = ([signed[0][0]]
               + [_joiner(kind, i) for i in range(edb.KEY_TILE + 1)]
               + [signed[1][0]])
    with kind.mod._KS_LOCK:
        assert table.admit(joiners, kind.decode, kind.name) == len(joiners)
    assert table.keyset is ks and ks.valid.shape[0] > cap
    assert set(ks._niels_on) == {devices[1]}
    assert ks._niels_on[devices[1]].shape == ks._niels.shape

    items[LANES + 5], items[2 * LANES - 1] = late
    pub, msg, sig = late[1]
    items[LANES + 6] = (pub, msg + b"!", sig)
    del placed[:]
    _dev, bits, _route = _run(kind, items, multichip=True)
    assert placed == [devices[0], devices[1]]
    assert sorted(np.flatnonzero(~bits)) == [LANES + 6]
    assert (np.asarray(ks._niels_on[devices[1]]) == np.asarray(ks._niels)).all()


def _joiner(kind, i: int) -> bytes:
    """A key that decodes, cheaply: a small multiple of the base point."""
    if kind.name == "ed25519":
        return ref.gen_priv_key(b"join" + i.to_bytes(4, "big") * 7).pub_key().data
    return sr.gen_priv_key(b"join" + i.to_bytes(4, "big")).pub_key().data


@pytest.mark.parametrize("devices", [(), ("only",)], ids=["none", "one"])
def test_one_device_makes_no_placement_call(devices, monkeypatch):
    """Without devices to place on, or with one, the loop enqueues what a
    one-chip host always did: jnp.asarray, the unplaced table, one
    concatenate, one pack -- and no device_put."""
    def no_put(*a, **k):
        raise AssertionError("a placement call on one device")

    gathered = []

    class Table:
        def gathered_lane(self, idx, device=None):
            gathered.append(device)
            return None

    monkeypatch.setattr(edp, "CHUNK", LANES)
    monkeypatch.setattr(jax, "device_put", no_put)
    packs = []
    real_pack = edp.pack_bitmap
    monkeypatch.setattr(edp, "pack_bitmap",
                        lambda ok: packs.append(ok.shape) or real_pack(ok))
    n = 2 * LANES + 3
    cols = np.ones((n, 32), np.uint8), np.ones((n,), np.uint8)
    pieces = edp.launch_chunks(
        "stand_in", lambda tab, a, valid: valid.astype(jnp.int32), Table(),
        np.zeros((n,), np.int32), n, lambda sl: (cols[0][sl], cols[1][sl]),
        devices)
    assert gathered == [None] * 3 and packs == [(1, 3 * LANES)]
    assert len(pieces) == 1 and edp.unpack_pieces(pieces, n).all()


# --- the routing table on a TPU host -----------------------------------------


@pytest.mark.parametrize("n, want", [
    (100, "host"), (edb.JNP_TILE * 4, "device"), (edp.CHUNK, "device"),
    (edp.CHUNK + 1, "sharded"), (9999, "sharded")])
def test_route_table_on_a_tpu_host_with_four_chips(n, want, monkeypatch,
                                                   fake_tpu_host):
    """One chunk or less takes the rows a one-chip host takes; from one
    signature more the batch is spread."""
    from tendermint_tpu.ops import chost

    monkeypatch.setattr(chost, "available", lambda: True)
    monkeypatch.setattr(edb, "host_crossover", lambda: 256)
    fake_tpu_host(4, edp.CHUNK)
    assert edb.should_shard(n) == (n > edp.CHUNK)
    assert edb.route_batch(n) == want
    monkeypatch.setattr(jax, "local_device_count", lambda: 1)
    assert edb.route_batch(n) == ("device" if want == "sharded" else want)
    # TM_TPU_SHARD=0 is one device
    monkeypatch.setattr(jax, "local_device_count", lambda: 4)
    monkeypatch.setenv("TM_TPU_SHARD", "0")
    assert edb.route_batch(n) == ("device" if want == "sharded" else want)


@pytest.mark.parametrize("n", [64, 2048, 20480])
def test_off_a_tpu_nothing_is_sharded(kind, n, monkeypatch):
    """A CPU-only node, or these tests' eight forced devices: whatever the
    device count and the size, a batch takes the `device` route and the jnp
    tile loop on one device (kernels stood in for by `valid`)."""
    monkeypatch.delenv("TM_TPU_SHARD", raising=False)
    assert not edb._use_pallas() and jax.local_device_count() > 1
    tiles = []

    def tile(tab, *arrays, **kw):
        valid = kw["valid"] if kw else arrays[-1]
        tiles.append((valid.shape, valid.devices()))
        return valid

    monkeypatch.setattr(edb, "_jnp_kernel", tile)
    monkeypatch.setattr(srb, "_kernel", tile)
    assert not edb.should_shard(n)
    assert edb.route_batch(n, force_device=True) == "device"
    dev, finish = kind.mod.dispatch_batch(_items(kind, n), force_device=True)
    assert finish(cbatch._device_get(dev)).all() and finish.route == "jnp"
    assert tiles == [((edb.JNP_TILE,), {jax.local_devices()[0]})] * (
        -(-n // edb.JNP_TILE))


# --- the warm-up ---------------------------------------------------------------


def test_warm_mesh_launches_both_chunk_programs_on_every_device(
        placed, monkeypatch):
    """What a node compiles at start on a TPU host of several chips: a chunk
    of each key type on every local device, so that no commit compiles."""
    devices = jax.local_devices()
    launched = {k.attr: [] for k in KINDS}
    for k in KINDS:
        def chunk(tab, a, s32, r32, valid, _to=launched[k.attr]):
            assert tab.devices() == valid.devices()
            _to.append(next(iter(valid.devices())))
            return valid.astype(jnp.int32)
        monkeypatch.setattr(edp, k.attr, chunk)
    monkeypatch.delenv("TM_TPU_SKIP_WARMUP", raising=False)
    monkeypatch.setattr(cbatch, "WARMUP", cbatch.WarmupStatus())
    monkeypatch.setattr(edb, "calibrate_host_crossover", lambda: 256)
    monkeypatch.setattr(edb, "host_crossover", lambda: 256)
    cbatch.warmup(background=False)
    assert cbatch.WARMUP.state == "done", cbatch.WARMUP.error
    # the one-chunk warm batch first, then a chunk a device
    assert launched["_verify_chunk"] == [devices[0]] + devices
    assert launched["_sr_verify_chunk"] == devices
