"""The key table's Pallas build on the CPU, in interpret mode.

On a TPU backend ops/ed25519_batch.KeySet.append builds the comb tables of
the keys a request is missing with ops/ed25519_pallas._build_kernel, a key a
lane, where every other backend runs the jnp program
_build_comb_tables_impl; both a 256-key tile a launch. The two must hold the same
sixteen points a key, and a KeySet must not be able to tell which of them
filled it: rows, validity, capacity and the clear at MAX_ROWS are reckoned
in KEY_TILEs either way.

First the kernel alone, one TILE of lanes against the jnp program and
against big-integer scalar multiplication (crypto/ed25519), entry by entry
as affine points mod p; then KeySet.append with the backend test patched
as tests/test_ed25519_pallas_interpret.py patches it. tests/test_pallas_tpu.py
holds the same comparison on the chip.

Every interpreted launch is computed once, in a fixture that fails, rather
than hangs, past LIMIT_S (a tile is seconds on the CPU)."""

import concurrent.futures
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.crypto import sr25519 as srref
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import ed25519_pallas as edp
from tendermint_tpu.ops import edwards25519 as ed
from tendermint_tpu.ops import sr25519_batch as srb
from tendermint_tpu.utils import metrics as tmmetrics
from tendermint_tpu.utils import trace

LIMIT_S = 900
P = ref.P
_WEIGHTS = np.array([1 << (13 * i) for i in range(20)], dtype=object)
# scalar of comb entry w: sum_j w_j 2^(64 j)
_ENTRY = [sum(((w >> j) & 1) << (64 * j) for j in range(4)) for w in range(16)]


def _within(fn):
    pool = concurrent.futures.ThreadPoolExecutor(1)
    try:
        return pool.submit(fn).result(timeout=LIMIT_S)
    except concurrent.futures.TimeoutError:
        pytest.fail(f"the table build took more than {LIMIT_S} s on the CPU")
    finally:
        pool.shutdown(wait=False)


def _ints(limbs) -> np.ndarray:
    """(..., 20) limbs -> (...) Python integers mod p."""
    return (np.asarray(limbs).astype(object) * _WEIGHTS).sum(axis=-1) % P


def _same_points(a, b) -> np.ndarray:
    """(..., 4, 20) extended points -> (...) bool: equal as affine points,
    and each on the extended form X Y = Z T."""
    xa, ya, za, ta = (_ints(a[..., c, :]) for c in range(4))
    xb, yb, zb, tb = (_ints(b[..., c, :]) for c in range(4))
    return ((xa * zb - xb * za) % P == 0) & ((ya * zb - yb * za) % P == 0) \
        & ((xa * ya - za * ta) % P == 0) & ((xb * yb - zb * tb) % P == 0) \
        & (za != 0) & (zb != 0)


# --- the kernel, one tile ----------------------------------------------------------

# a point of order 4 (y = 0) and one of order 8, as RFC 8032 encodes them
_ORDER_4 = bytes(32)
_ORDER_8 = bytes.fromhex(
    "c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac037a")


def _tile_lanes() -> dict:
    """lane -> (kind, extended limbs of -A); every other lane of the tile
    holds the identity, as the padding of a build does."""
    lanes = {}
    for i, lane in enumerate((0, 1, 2, 127, 128, edp.TILE - 1)):
        pub = ref.gen_priv_key(b"table-build-%02d" % i + bytes(18)).pub_key().data
        kind = "last_lane" if lane == edp.TILE - 1 else "ed25519"
        lanes[lane] = (kind, edb._decompress_neg(pub))
    lanes[3] = ("small_order", edb._decompress_neg(_ORDER_4))
    lanes[4] = ("small_order", edb._decompress_neg(_ORDER_8))
    spub = srref.gen_priv_key(b"table-build-sr").pub_key().data
    lanes[5] = ("sr25519", srb._decode_neg(spub))
    lanes[6] = ("identity_padding", ed.IDENTITY_LIMBS)
    lanes[200] = ("identity_padding", ed.IDENTITY_LIMBS)
    return lanes


@pytest.fixture(scope="module")
def tile():
    lanes = _tile_lanes()
    a_neg = np.broadcast_to(ed.IDENTITY_LIMBS, (edp.TILE, 4, 20)).copy()
    for lane, (_kind, limbs) in lanes.items():
        assert limbs is not None
        a_neg[lane] = limbs

    def run():
        return (np.asarray(edp._build_comb_lanes(jnp.asarray(a_neg),
                                                 interpret=True)),
                np.asarray(edb._build_comb_tables(jnp.asarray(a_neg))))

    pallas, jnp_tables = _within(run)
    return dict(lanes=lanes, a_neg=a_neg, pallas=pallas, jnp=jnp_tables)


KINDS = ["ed25519", "small_order", "sr25519", "identity_padding", "last_lane"]


def _lanes_of(tile, kind):
    got = [lane for lane, (k, _) in tile["lanes"].items() if k == kind]
    assert got
    return got


def test_the_small_order_keys_are_what_they_are_called():
    for enc, order in ((_ORDER_4, 4), (_ORDER_8, 8)):
        pt = ref._decompress(enc)
        assert pt is not None
        assert ref._compress(ref._scalarmult(order, pt)) == ref._compress(ref._IDENT)
        assert ref._compress(ref._scalarmult(order // 2, pt)) != ref._compress(ref._IDENT)


@pytest.mark.parametrize("kind", KINDS)
def test_entries_equal_the_jnp_builds_as_points(tile, kind):
    for lane in _lanes_of(tile, kind):
        same = _same_points(tile["pallas"][lane], tile["jnp"][lane])
        assert same.all(), (lane, np.nonzero(~same)[0])


@pytest.mark.parametrize("kind", KINDS)
def test_entries_equal_the_big_integer_reference(tile, kind):
    """Entry w is [sum_j w_j 2^(64j)](-A), by double-and-add over Python
    integers from the lane's own input."""
    for lane in _lanes_of(tile, kind):
        x, y, z, _t = (int(v) for v in _ints(tile["a_neg"][lane]))
        assert z == 1
        neg_a = (x, y, 1, x * y % P)
        for w in range(16):
            want = ed.from_affine(*_affine(ref._scalarmult(_ENTRY[w], neg_a)))
            assert _same_points(tile["pallas"][lane, w], want), (lane, w)


def _affine(pt):
    x, y, z, _ = pt
    zi = pow(z, -1, P)
    return x * zi % P, y * zi % P


def test_every_lane_of_the_tile_equals_the_jnp_build(tile):
    same = _same_points(tile["pallas"], tile["jnp"])
    assert same.shape == (edp.TILE, 16) and same.all(), np.nonzero(~same)


def test_the_limbs_keep_the_kernels_bound(tile):
    """Every stored limb is what a field multiplication leaves (<= 8799,
    ops/ed25519_pallas._carry_n): what _to_niels and the verify kernel's
    adds are sized for."""
    assert tile["pallas"].min() >= 0 and tile["pallas"].max() <= 8799
    assert tile["pallas"].dtype == np.int32


@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described and not attached: the TPU's compiler is
    installed where the tests run, the chip is not."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_for_the_chip(one_chip):
    """What interpret mode cannot show: Mosaic takes the kernel's traced row
    offsets and its VMEM (a (320, TILE) scratch, a (1280, TILE) output
    block), and the launch is the kernel between two transposes. A compile
    is not a run: tests/test_pallas_tpu.py runs it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # a described chip's executable cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        a_neg = jax.ShapeDtypeStruct((edp.TILE, 4, 20), jnp.int32,
                                     sharding=one_chip)
        compiled = _within(
            lambda: edp._build_comb_lanes.lower(a_neg).compile())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == edp.TILE * 16 * 4 * 20 * 4
    assert mem.temp_size_in_bytes == 0


def test_the_build_launches_the_key_tile():
    """One compiled shape: a launch is a KEY_TILE of lanes whatever the
    count of keys, the lanes past them holding the identity."""
    assert edp.TILE == edb.KEY_TILE
    with pytest.MonkeyPatch.context() as mp:
        seen = []
        mp.setattr(edp, "_build_comb_lanes",
                   lambda a: seen.append(np.asarray(a)))
        edp.build_comb_tile(np.zeros((3, 4, 20), np.int32))
    (a,) = seen
    assert a.shape == (edp.TILE, 4, 20) and not a[:3].any()
    assert (a[3:] == ed.IDENTITY_LIMBS).all()


# --- KeySet.append, as on a TPU backend --------------------------------------------


def _points(n: int):
    """n distinct curve points as fake keys: B, 2B, 3B, ... (affine adds
    over Python integers; a real key each would be n scalar
    multiplications). Every 50th key decodes to nothing."""
    base = (ref.BASE[0], ref.BASE[1])
    pt = base
    pubs, limbs = [], {}
    for i in range(n):
        pub = b"fake-key-%023d" % i
        pubs.append(pub)
        limbs[pub] = None if i % 50 == 17 else ed.negate_affine(*pt)
        pt = ed.affine_add(pt, base)
    return pubs, limbs.get


_POINTS = functools.lru_cache(maxsize=None)(_points)


def _admit(n_keys: int, build, before: int = 0):
    """A table of its own that admits n_keys (after `before` of them, with
    the niels rows asked for in between) -> what a KeySet shows of it.
    `build` stands for ed25519_pallas._build_comb_lanes behind a TPU
    backend (KERNEL: the kernel itself, interpreted); None is this
    backend's jnp loop."""
    pubs, decode = _POINTS(n_keys)
    with pytest.MonkeyPatch.context() as mp:
        if build is not None:
            mp.setattr(edb, "_use_pallas", lambda: True)
            mp.setattr(edp, "_build_comb_lanes", build)
        table = edb.KeyTable()
        mark = len(_builds())
        if before:
            assert table.admit(pubs[:before], decode, "ed25519") == before
            table.keyset.gathered_lane(np.zeros((8,), np.int32))
        assert table.admit(pubs, decode, "ed25519") == n_keys - before
        ks = table.keyset
        rows = table.rows_of(pubs)
        return dict(
            n_rows=ks.n_rows, valid=ks.valid.copy(),
            capacity=(ks.valid.shape[0], ks._tab_ext.shape[0]),
            rows=rows, niels=np.asarray(ks.gathered_lane(rows)).T,
            ext=np.asarray(ks.take(rows)),
            builds=[s.tags for s in _builds()[mark:]])


def _builds():
    return [s for s in trace.STARTUP.dump() if s.name == "startup.table_build"]


KERNEL = functools.partial(edp._build_comb_lanes, interpret=True)
STUB = edb._build_comb_tables  # the jnp program at the launch's shape

# keys -> the kernel as the test runs it: the kernel itself, interpreted,
# for one launch and for two; the other counts have the jnp build standing
# in, so the file stays a minute or two
CASES = {1: KERNEL, 255: STUB, 256: STUB, 257: KERNEL, 4097: STUB}


@pytest.fixture(scope="module")
def appended():
    def run():
        return {k: (_admit(k, CASES[k]), _admit(k, None)) for k in CASES}

    return _within(run)


@pytest.mark.parametrize("keys", sorted(CASES))
def test_a_keyset_cannot_tell_which_program_filled_it(appended, keys):
    got, want = appended[keys]
    assert got["n_rows"] == want["n_rows"] == keys
    assert (got["valid"] == want["valid"]).all()
    assert got["valid"][:keys].sum() == keys - len(range(17, keys, 50))
    assert got["capacity"] == want["capacity"]
    assert got["capacity"][0] == max(
        edb.KEY_TILE, 1 << (keys - 1).bit_length())  # no launch's padding
    assert list(got["rows"]) == list(want["rows"]) == list(range(keys))
    assert (_ints(got["niels"].reshape(keys, 48, 20))
            == _ints(want["niels"].reshape(keys, 48, 20))).all()
    assert _same_points(got["ext"], want["ext"]).all()
    if CASES[keys] is STUB:  # the same program: the same limbs
        assert (got["niels"] == want["niels"]).all()


@pytest.mark.parametrize("keys", sorted(CASES))
def test_a_build_says_which_program_and_how_many_launches(appended, keys):
    tiles = -(-keys // edb.KEY_TILE)
    for side, program in ((0, "pallas"), (1, "jnp")):
        (got,) = appended[keys][side]["builds"]
        assert got == {"keys": keys, "kind": "ed25519", "program": program,
                       "launches": tiles, "rows": tiles * edb.KEY_TILE}


def test_a_tile_built_after_the_niels_rows_exist_is_converted_too():
    """Once the Pallas route has asked for niels rows, append converts each
    tile it builds and writes it beside its extended points, whatever row
    the table had reached."""
    got, want = _within(lambda: (_admit(300, STUB, before=5),
                                 _admit(300, None, before=5)))
    assert got["n_rows"] == want["n_rows"] == 300
    assert got["capacity"] == want["capacity"] == (1024, 1024)
    assert (got["valid"] == want["valid"]).all()
    assert (got["niels"] == want["niels"]).all()
    assert (got["ext"] == want["ext"]).all()
    assert [b["launches"] for b in got["builds"]] == [1, 2]
    assert [b["rows"] for b in got["builds"]] == [256, 512]


def test_a_build_that_would_pass_max_rows_clears_once(monkeypatch):
    """admit reckons a build in KEY_TILEs behind a TPU backend as off it:
    300 keys take 512 rows, and 100 more would pass a limit of 512 and
    start the table anew, once."""
    monkeypatch.setattr(edb, "_use_pallas", lambda: True)
    monkeypatch.setattr(edp, "_build_comb_lanes", STUB)
    monkeypatch.setattr(edb.KeyTable, "MAX_ROWS", 2 * edb.KEY_TILE)
    m = tmmetrics.NodeMetrics()
    monkeypatch.setattr(tmmetrics, "GLOBAL_NODE_METRICS", m)
    pubs, decode = _POINTS(400)

    def counted(name):
        (line,) = [ln for ln in m.registry.expose().splitlines()
                   if ln.split(" ")[0].endswith("crypto_" + name)]
        return float(line.rsplit(" ", 1)[1])

    table = edb.KeyTable()
    assert _within(lambda: table.admit(pubs[:300], decode, "ed25519")) == 300
    first = table.keyset
    assert (first.n_rows, first.valid.shape[0]) == (300, 512)
    assert counted("keytable_build_launches_total") == 2
    # resident keys and new ones that fit: no clear
    assert table.admit(pubs[250:300], decode, "ed25519") == 0
    # 100 new keys: 300 + 256 rows > 512
    assert _within(
        lambda: table.admit(pubs[290:400], decode, "ed25519")) == 110
    assert table.overflow_clears == 1 and table.generation == 1
    assert table.keyset is not first
    assert (table.keyset.n_rows, table.keyset.valid.shape[0]) == (110, 256)
    assert list(table.rows_of(pubs[290:400])) == list(range(110))
    assert counted("keytable_build_launches_total") == 3
    assert counted("keytable_keys_built_total") == 410
    assert counted("keytable_clears_total") == 1
