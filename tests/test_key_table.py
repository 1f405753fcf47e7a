"""The per-key device table (ops/ed25519_batch.KeyTable / KeySet): what the
comb tables of both Edwards key types are keyed on.

One table per key type whose rows are validator keys; a signer set is a list
of row numbers. A set never seen before over resident keys must build
nothing, a key never seen before must build one tile for itself alone, and
whichever tile and neighbours a key was built with, its rows are the same
integers and its signatures get the scalar path's answers. Every case runs
for ed25519 and for sr25519, which shares the code through build_keyset."""

import sys
import threading

import jax
import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as edref
from tendermint_tpu.crypto import sr25519 as srref
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import sr25519_batch as srb
from tendermint_tpu.utils import trace


class _Ed:
    name = "ed25519"
    mod = edb
    decode = staticmethod(edb._decompress_neg)
    verify = staticmethod(edref.verify)
    bad_pub = (2).to_bytes(32, "little")  # y = 2 is on no curve point

    @staticmethod
    def priv(i):
        return edref.gen_priv_key(b"key-table-%04d" % i + bytes(18))

    @staticmethod
    def sign(priv, msg):
        return edref.sign(priv.data, msg)

    @staticmethod
    def forged(s):
        """(R = compress([s]B), s): verifies against an identity table."""
        return (edref._compress(edref._scalarmult(s, edref.BASE))
                + s.to_bytes(32, "little"))


class _Sr:
    name = "sr25519"
    mod = srb
    decode = staticmethod(srb._decode_neg)
    verify = staticmethod(srref.verify)
    bad_pub = b"\xff" * 32  # no canonical field element

    @staticmethod
    def priv(i):
        return srref.gen_priv_key(b"key-table-%04d" % i)

    @staticmethod
    def sign(priv, msg):
        return srref.sign(priv.data, msg, rng_seed=b"\x27" * 32)

    @staticmethod
    def forged(s):
        sb = bytearray(s.to_bytes(32, "little"))
        sb[31] |= 0x80  # schnorrkel's marker bit
        return (srref.ristretto_encode(edref._scalarmult(s, edref.BASE))
                + bytes(sb))


_KINDS = {"ed25519": _Ed, "sr25519": _Sr}
_PUBS: dict = {}
_ITEMS: dict = {}


def _pubs(kind, n):
    have = _PUBS.setdefault(kind.name, [])
    while len(have) < n:
        have.append(kind.priv(len(have)).pub_key().data)
    return have[:n]


def _items(kind, n):
    """n signed (pub, msg, sig), one key each; made once per key type."""
    have = _ITEMS.setdefault(kind.name, [])
    while len(have) < n:
        i = len(have)
        priv = kind.priv(i)
        msg = b"key table vote %d" % i
        have.append((priv.pub_key().data, msg, kind.sign(priv, msg)))
    return have[:n]


def _corrupt(item):
    pub, msg, sig = item
    return pub, msg, bytes([sig[0] ^ 0x04]) + sig[1:]


@pytest.fixture(params=sorted(_KINDS))
def kind(request, monkeypatch):
    """A key type with a memo and a table of the test's own."""
    k = _KINDS[request.param]
    monkeypatch.setattr(k.mod, "_KS_CACHE", type(k.mod._KS_CACHE)())
    monkeypatch.setattr(k.mod, "_KS_UNIQ_CACHE", type(k.mod._KS_UNIQ_CACHE)())
    return k


@pytest.fixture
def builds(monkeypatch):
    """Calls of the tile-shaped table-build executable."""
    calls = []
    real = edb._build_comb_tables

    def counting(a_neg):
        calls.append(a_neg.shape[0])
        return real(a_neg)

    monkeypatch.setattr(edb, "_build_comb_tables", counting)
    return calls


def _device_bitmap(kind, items):
    dev, finish = kind.mod.dispatch_batch(items, force_device=True)
    return np.asarray(finish(jax.device_get(dev)), dtype=bool)


def _check(kind, items):
    want = np.array([kind.verify(p, m, s) for p, m, s in items])
    got = _device_bitmap(kind, items)
    assert (got == want).all(), np.nonzero(got != want)[0]
    return want


def _lookups(tracer):
    return [s.tags for s in tracer.dump() if s.name == "prep.keyset"]


@pytest.fixture
def tracer():
    t = trace.Tracer("key-table", cap=256, enabled=True)
    with t.activate():
        yield t
    t.disable()


# --- (a) a key's rows do not depend on how it came to the table -----------------


def test_rows_equal_the_whole_set_build_whatever_tile_they_came_in(kind):
    n = edb.KEY_TILE + 44
    pubs = _pubs(kind, n)
    a_neg = np.stack([kind.decode(p) for p in pubs])
    ext_ref = edb._build_comb_tables_tiled(a_neg)
    niels_ref = np.asarray(edb._to_niels(ext_ref))[:n]
    ext_ref = np.asarray(ext_ref)[:n]

    table = kind.mod._KS_UNIQ_CACHE
    assert table.admit(pubs[:1], kind.decode, kind.name) == 1      # alone
    ks = table.keyset
    assert ks.valid.shape == (edb.KEY_TILE,)
    assert table.admit(pubs[:10], kind.decode, kind.name) == 9     # with others
    assert ks.valid.shape == (2 * edb.KEY_TILE,)                   # doubled
    # the Pallas route's first request converts what is there; from then on
    # every appended tile is converted as it is built
    first = np.asarray(ks.gathered_lane(np.arange(10, dtype=np.int32)))
    assert (first.T == niels_ref[:10]).all()
    assert table.admit(pubs, kind.decode, kind.name) == n - 10     # two tiles
    assert table.keyset is ks and ks.n_rows == n
    assert ks.valid.shape == (4 * edb.KEY_TILE,) and ks.valid[:n].all()
    assert not ks.valid[n:].any()

    rows = table.rows_of(pubs)
    assert list(rows) == list(range(n))
    assert (np.asarray(ks.take(rows)) == ext_ref).all()
    assert (np.asarray(ks.gathered_lane(rows)).T == niels_ref).all()
    # any order, any repetition: a set is row numbers
    some = np.array([n - 1, 0, 7, 7, edb.KEY_TILE, 3], dtype=np.int32)
    assert (np.asarray(ks.take(some)) == ext_ref[some]).all()


# --- (b) resident keys, new signer sets -----------------------------------------


def test_a_subset_or_reordering_of_resident_keys_builds_nothing(kind, builds,
                                                                tracer):
    items = _items(kind, 6)
    assert _check(kind, items).all()
    assert builds == [edb.KEY_TILE]
    live = [items[4], _corrupt(items[1]), items[0], _corrupt(items[4]),
            items[0], items[5]]
    want = _check(kind, live)
    assert list(want) == [True, False, True, False, True, True]
    assert _check(kind, items[::-1]).all()
    assert _check(kind, items).all()
    assert builds == [edb.KEY_TILE], "a set over resident keys built tables"
    tags = _lookups(tracer)
    assert [t["hit"] for t in tags] == ["miss", "set", "set", "sequence"]
    assert [t["built"] for t in tags] == [6, 0, 0, 0]
    assert {t["resident"] for t in tags} == {6}


# --- (c) one key the table has not met -------------------------------------------


def test_one_new_key_among_resident_ones_builds_one_tile(kind, builds, tracer):
    items = _items(kind, 8)
    _check(kind, items[:7])
    ks = kind.mod._KS_UNIQ_CACHE.keyset
    del builds[:]
    assert _check(kind, items[3:8]).all()
    assert builds == [edb.KEY_TILE]
    tags = _lookups(tracer)[-1]
    assert (tags["hit"], tags["built"], tags["resident"]) == ("miss", 1, 8)
    assert kind.mod._KS_UNIQ_CACHE.keyset is ks
    assert kind.mod._KS_UNIQ_CACHE[items[7][0]] == 7
    ring = [s for s in trace.STARTUP.dump() if s.name == "startup.table_build"]
    assert ring[-1].tags == {"keys": 1, "kind": kind.name,
                             "rows": edb.KEY_TILE, "launches": 1,
                             "program": "jnp"}


def test_forget_keys_makes_the_next_verify_build_its_keys_again(kind, builds,
                                                                tracer):
    """crypto.batch.forget_keys is the public way to start a session as a
    process that has just started does: both key types' tables and
    sequence memos are emptied under their locks, a dispatch that still
    holds the old KeySet keeps it, and the next verify of the same keys
    builds them again and answers as before."""
    from tendermint_tpu.crypto import batch as crypto_batch

    items = _items(kind, 5)
    _check(kind, items)
    table = kind.mod._KS_UNIQ_CACHE
    ks = table.keyset
    del builds[:]
    _check(kind, items)
    assert builds == [] and _lookups(tracer)[-1]["hit"] == "sequence"
    crypto_batch.forget_keys()
    for mod in (edb, srb):
        assert len(mod._KS_UNIQ_CACHE) == 0 and not mod._KS_CACHE
    assert table.keyset is not ks and ks.n_rows == 5
    assert _check(kind, items + [_corrupt(items[0])]).sum() == 5
    assert builds == [edb.KEY_TILE]
    tags = _lookups(tracer)[-1]
    assert (tags["hit"], tags["built"], tags["resident"]) == ("miss", 5, 5)


# --- (d) a key that is no curve point ----------------------------------------------


@pytest.mark.parametrize("company", ["alone", "among_valid_keys"])
def test_an_undecodable_key_never_verifies(kind, company):
    """Its row holds the identity's tables, under which a forged
    (R = encode([s]B), s) would verify: the row's `valid` flag masks it."""
    forged = [(kind.bad_pub, b"any message", kind.forged(12345 + i))
              for i in range(2)]
    items = forged if company == "alone" else (
        _items(kind, 3)[:2] + forged[:1] + _items(kind, 3)[2:] + forged[1:])
    want = _check(kind, items)
    assert want.sum() == (0 if company == "alone" else 3)
    table = kind.mod._KS_UNIQ_CACHE
    assert not table.keyset.valid[table[kind.bad_pub]]
    # resident now: the answer does not change on the hit paths
    assert (_device_bitmap(kind, items) == want).all()
    assert (_device_bitmap(kind, items[::-1]) == want[::-1]).all()


# --- (e) the row limit ---------------------------------------------------------------


def test_overflow_resets_the_table_and_answers_stay_right(kind, builds,
                                                          monkeypatch):
    monkeypatch.setattr(edb.KeyTable, "MAX_ROWS", edb.KEY_TILE)
    table = kind.mod._KS_UNIQ_CACHE
    items = _items(kind, 6)
    first = [items[0], _corrupt(items[1]), items[2], items[3]]
    before, finish = kind.mod.dispatch_batch(first, force_device=True)
    old = table.keyset
    assert (table.generation, old.n_rows) == (0, 4)
    # two resident keys and two new ones: a tile more would pass the limit,
    # so every row goes and all four are built in a table that starts anew
    second = [items[2], items[5], _corrupt(items[4]), items[3]]
    assert list(_check(kind, second)) == [True, True, False, True]
    assert table.generation == 1 and table.keyset is not old
    assert sorted(table) == sorted(it[0] for it in second)
    assert table.keyset.n_rows == 4 and builds == [edb.KEY_TILE] * 2
    # the dispatch issued before the reset reads the rows it was given
    assert list(finish(jax.device_get(before))) == [True, False, True, True]
    # its sequence is memoised with row numbers of the old table: not served
    assert list(_check(kind, first)) == [True, False, True, True]
    assert table.generation == 2 and len(builds) == 3


# --- (f) clear(), as benchmark/drivers/lightsync.py starts a session --------------------


def test_clear_forgets_the_rows_and_the_next_lookup_builds_again(kind, builds,
                                                                 tracer):
    items = _items(kind, 5)
    table = kind.mod._KS_UNIQ_CACHE
    _check(kind, items)
    old = table.keyset
    with kind.mod._KS_LOCK:
        table.clear()
    assert not table and table.keyset.n_rows == 0 and table.keyset is not old
    assert _check(kind, items).all()    # same sequence: the memo is stale
    assert builds == [edb.KEY_TILE] * 2
    assert [(t["hit"], t["built"]) for t in _lookups(tracer)] == [
        ("miss", 5), ("miss", 5)]
    assert list(table.values()) == list(range(5))


# --- (g) two threads -------------------------------------------------------------------


def test_threads_looking_up_overlapping_sets_get_the_same_rows(kind):
    pubs = _pubs(kind, 24)
    got: list = []
    errors: list = []

    def worker(w):
        try:
            for r in range(12):
                lo = (5 * w + 3 * r) % 16
                batch = pubs[lo:lo + 8][::-1 if (w + r) % 2 else 1]
                ks, idx, ok = kind.mod.get_keyset(batch)
                assert ok.all() and ks.valid[idx].all()
                got.append((ks, batch, idx))
                # a read of the table while another thread appends to it
                assert np.asarray(ks.take(idx)).shape == (8, 16, 4, 20)
        except Exception as e:  # noqa: BLE001 - reported by the main thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(w,)) for w in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    table = kind.mod._KS_UNIQ_CACHE
    assert len(got) == 72 and sorted(table.values()) == list(range(len(table)))
    assert table.keyset.n_rows == len(table)
    for ks, batch, idx in got:
        assert ks is table.keyset
        assert [table[p] for p in batch] == list(idx)
    # and each row holds its own key's tables
    want = np.asarray(edb._build_comb_tables_tiled(
        np.stack([kind.decode(p) for p in table])))[:len(table)]
    assert (np.asarray(table.keyset.take(np.arange(len(table)))) == want).all()
