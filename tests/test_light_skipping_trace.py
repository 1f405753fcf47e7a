"""PR 45: what skipping mode writes to the flight recorder and counts.

``verify_commit_light_trusting`` is traced like the other two commit checks
(one ``light.skip.trusting`` span over a ``commit.assemble`` decision), a
bisection of known shape writes its attempts, refusals and pivot fetches, in
the reference's order (light/client.go verifySkipping: a block cache, retries
from the target, pivots 9/16 of the way), and a key table that would pass
``MAX_ROWS`` clears once, counts it, and still answers as the host verifier
does."""

import jax
import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.light import (SKIPPING, Client, DBStore, MockProvider,
                                  TrustOptions)
from tendermint_tpu.light import verifier as lv
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.store.db import MemDB
from tendermint_tpu.types.block import Commit, CommitSig, Header
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.light_block import LightBlock, SignedHeader
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import (ErrDoubleVote,
                                                ErrNotEnoughVotingPowerSigned,
                                                ErrWrongSignature, ValidatorSet)
from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT
from tendermint_tpu.utils import metrics as tmmetrics
from tendermint_tpu.utils import trace

CHAIN_ID = "skip-trace-chain"
T0 = 1_700_000_000
N, HEIGHTS = 6, 12        # set(h) = keys h-1 .. h+4: one key rotates a height


def _priv(k):
    return ed25519.gen_priv_key(b"skip-trace-%04d" % k + bytes(17))


PRIVS = [_priv(k) for k in range(N + HEIGHTS)]


def _set(h):
    """-> (ValidatorSet of height h, its private keys in set order)."""
    privs = PRIVS[h - 1:h - 1 + N]
    vals = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    return vals, [by_addr[v.address] for v in vals.validators]


def _light_block(h, last_bid):
    vals, privs = _set(h)
    header = Header(chain_id=CHAIN_ID, height=h, time=Time(T0 + h, 0),
                    last_block_id=last_bid, validators_hash=vals.hash(),
                    next_validators_hash=_set(h + 1)[0].hash(),
                    proposer_address=vals.validators[0].address)
    bid = BlockID(hash=header.hash(),
                  part_set_header=PartSetHeader(total=1, hash=b"\xcd" * 32))
    commit = Commit(height=h, round=0, block_id=bid, signatures=[
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, Time(T0 + h, 7 * i), b"")
        for i, v in enumerate(vals.validators)])
    for i, priv in enumerate(privs):
        commit.signatures[i].signature = priv.sign(
            commit.vote_sign_bytes(CHAIN_ID, i))
    return LightBlock(SignedHeader(header, commit), vals), bid


@pytest.fixture(scope="module")
def chain():
    out, bid = {}, BlockID()
    for h in range(1, HEIGHTS + 1):
        out[h], bid = _light_block(h, bid)
    return out


@pytest.fixture
def tracer():
    t = trace.Tracer("skip-trace", cap=1024, enabled=True)
    with t.activate():
        yield t
    t.disable()


@pytest.fixture
def node_metrics(monkeypatch):
    m = tmmetrics.NodeMetrics()
    monkeypatch.setattr(tmmetrics, "GLOBAL_NODE_METRICS", m)

    def read(name):
        lines = [ln for ln in m.registry.expose().splitlines()
                 if ln.split(" ")[0].split("{")[0].endswith(name)]
        assert len(lines) == 1, (name, lines)
        return float(lines[0].rsplit(" ", 1)[1])
    return read


def _spans(tracer, name):
    return [s for s in tracer.dump() if s.name == name]


# --- the trusting check, traced like the other two --------------------------------


def test_the_trusting_check_is_one_span_over_one_decision(chain, tracer):
    commit = chain[4].signed_header.commit
    chain[1].validator_set.verify_commit_light_trusting(CHAIN_ID, commit, (1, 3))
    (span,) = _spans(tracer, "light.skip.trusting")
    (root,) = _spans(tracer, "commit.assemble")
    # three of set(1)'s six signed height 4: all three are needed for > 1/3
    assert span.tags["n"] == 3 and "refused" not in span.tags
    assert span.tags["decision"] == root.span_id == root.tags["decision"]
    assert root.tags["mode"] == "trusting" and root.tags["sigs"] == 3
    assert root.parent_id == span.span_id
    for name in ("commit.wait", "commit.tally"):
        (child,) = _spans(tracer, name)
        assert child.tags["decision"] == root.span_id


def test_a_refusal_is_tagged_and_raised_as_it_was_untraced(chain, tracer):
    commit = chain[7].signed_header.commit          # set(1) has left: no signer
    with pytest.raises(ErrNotEnoughVotingPowerSigned) as traced:
        chain[1].validator_set.verify_commit_light_trusting(CHAIN_ID, commit, (1, 3))
    (span,) = _spans(tracer, "light.skip.trusting")
    assert span.tags["n"] == 0
    assert span.tags["refused"] == "ErrNotEnoughVotingPowerSigned"
    tracer.disable()
    with pytest.raises(ErrNotEnoughVotingPowerSigned) as plain:
        chain[1].validator_set.verify_commit_light_trusting(CHAIN_ID, commit, (1, 3))
    assert str(plain.value) == str(traced.value)


def _edited(commit, idx, **changes):
    sigs = list(commit.signatures)
    cs = sigs[idx]
    sigs[idx] = CommitSig(cs.block_id_flag,
                          changes.get("address", cs.validator_address),
                          cs.timestamp, changes.get("sig", cs.signature))
    return Commit(height=commit.height, round=commit.round,
                  block_id=commit.block_id, signatures=sigs)


def test_the_serial_order_of_errors_is_the_references(chain):
    """A double vote is reported only once every signature before it has
    verified (types/validator_set.go:772-830 checks both in one loop)."""
    trusted = chain[3].validator_set                  # five of six still sign 4
    commit = chain[4].signed_header.commit
    known = [i for i, cs in enumerate(commit.signatures)
             if trusted.has_address(cs.validator_address)]
    a, b = known[0], known[1]
    twice = _edited(commit, b, address=commit.signatures[a].validator_address)
    with pytest.raises(ErrDoubleVote) as e:
        trusted.verify_commit_light_trusting(CHAIN_ID, twice, (1, 3))
    assert (e.value.first, e.value.index) == (a, b)
    bad = commit.signatures[a].signature
    both = _edited(twice, a, sig=bad[:-1] + bytes([bad[-1] ^ 1]))
    with pytest.raises(ErrWrongSignature) as e:
        trusted.verify_commit_light_trusting(CHAIN_ID, both, (1, 3))
    assert e.value.index == a
    # and the handle's twin defers the same verdict to resolve()
    pending = trusted.verify_commit_light_trusting_async(CHAIN_ID, both, (1, 3))
    assert pending.sigs == 1
    with pytest.raises(ErrWrongSignature):
        pending.resolve()


# --- a bisection of known shape -------------------------------------------------------

# set(a) and set(b) share 6 - (b - a) keys and a hop needs three of them:
# a hop of up to three heights verifies, a longer one is refused
ATTEMPTS = [(1, 12, False), (1, 7, False), (1, 4, True), (4, 12, False),
            (4, 7, True), (7, 12, False), (7, 9, True), (9, 12, True)]


def _client(chain):
    primary, witness = MockProvider(CHAIN_ID, chain), MockProvider(CHAIN_ID, chain)
    store = DBStore(MemDB())
    client = Client(CHAIN_ID, TrustOptions(period_s=3600.0, height=1,
                                           hash=chain[1].hash()),
                    primary, [witness], store, verification_mode=SKIPPING)
    return client, store


def test_a_bisection_writes_its_attempts_fetches_and_counters(chain, tracer,
                                                              node_metrics):
    client, store = _client(chain)
    tracer.clear()
    client.verify_light_block_at_height(HEIGHTS, Time(T0 + HEIGHTS + 5, 0))
    assert client.last_bisection == ATTEMPTS
    hops = _spans(tracer, "light.skip.hop")
    assert [(s.tags["from"], s.tags["to"], bool(s.tags["accepted"]))
            for s in hops] == ATTEMPTS
    assert [s.tags["depth"] for s in hops] == [0, 1, 2, 0, 1, 0, 1, 0]
    # a pivot is fetched once, 9/16 of the way, and tried again from the
    # block the next hop verified before another is asked for
    assert [s.tags["height"] for s in _spans(tracer, "light.skip.fetch")] == [7, 4, 9]
    # every attempt runs the trusting check; the light check follows where
    # it passed, one decision after the other
    trusting = _spans(tracer, "light.skip.trusting")
    assert len(trusting) == len(ATTEMPTS)
    assert [("refused" in s.tags) for s in trusting] == [
        not ok for _f, _t, ok in ATTEMPTS]
    assert len(_spans(tracer, "light.skip.light")) == 4
    modes = [s.tags["mode"] for s in _spans(tracer, "commit.assemble")]
    assert modes.count("trusting") == 8 and modes.count("light") == 4
    (sync,) = _spans(tracer, "light.sync")
    assert sync.tags["mode"] == SKIPPING
    assert all(s.parent_id for s in hops)
    assert node_metrics("light_skip_hops_total") == 4
    assert node_metrics("light_skip_refused_total") == 4
    assert node_metrics("light_skip_depth_max") == 2
    assert [h for h in range(1, HEIGHTS + 1)
            if store.light_block(h) is not None] == [1, 4, 7, 9, 12]


def test_untraced_the_same_attempts_and_no_span(chain):
    client, _store = _client(chain)
    client.verify_light_block_at_height(HEIGHTS, Time(T0 + HEIGHTS + 5, 0))
    assert client.last_bisection == ATTEMPTS
    assert not trace.ENABLED


def test_an_attempt_that_raises_is_the_last_of_the_list(chain):
    broken = dict(chain)
    commit = chain[4].signed_header.commit
    sig = commit.signatures[0].signature
    broken[4] = LightBlock(SignedHeader(
        chain[4].signed_header.header,
        _edited(commit, 0, sig=sig[:-1] + bytes([sig[-1] ^ 1]))),
        chain[4].validator_set)
    client, store = _client(broken)
    with pytest.raises((ErrWrongSignature, lv.ErrInvalidHeader)):
        client.verify_light_block_at_height(HEIGHTS, Time(T0 + HEIGHTS + 5, 0))
    assert client.last_bisection == ATTEMPTS[:2] + [(1, 4, False)]
    assert store.light_block(4) is None


# --- the key table's row limit, counted ------------------------------------------------


def test_a_table_past_max_rows_clears_once_counts_it_and_answers_as_the_host(
        monkeypatch, tracer, node_metrics):
    monkeypatch.setattr(edb, "_KS_CACHE", type(edb._KS_CACHE)())
    monkeypatch.setattr(edb, "_KS_UNIQ_CACHE", edb.KeyTable())
    monkeypatch.setattr(edb.KeyTable, "MAX_ROWS", edb.KEY_TILE)
    table = edb._KS_UNIQ_CACHE
    items = []
    for k in range(6):
        msg = b"skip key table vote %d" % k
        items.append((PRIVS[k].pub_key().data, msg, PRIVS[k].sign(msg)))
    pub, msg, sig = items[1]
    items[1] = (pub, msg, bytes([sig[0] ^ 0x04]) + sig[1:])

    def both(batch):
        _none, host = edb._dispatch_host(batch, len(batch))
        dev, finish = edb.dispatch_batch(batch, force_device=True)
        got = np.asarray(finish(jax.device_get(dev)), dtype=bool)
        assert got.tobytes() == np.asarray(host(None), dtype=bool).tobytes()
        return list(got)

    assert both(items[:4]) == [True, False, True, True]
    assert (node_metrics("keytable_keys_built_total"),
            table.overflow_clears) == (4, 0)
    # two resident keys and two new ones: a tile more would pass the limit
    assert both([items[2], items[5], items[4], items[3]]) == [True] * 4
    assert (node_metrics("keytable_keys_built_total"), table.overflow_clears,
            table.generation) == (8, 1, 1)
    assert both(items[2:6]) == [True] * 4           # resident again: no build
    assert (node_metrics("keytable_keys_built_total"),
            table.overflow_clears) == (8, 1)
    tags = [s.tags for s in tracer.dump() if s.name == "prep.keyset"]
    assert [(t["hit"], t["built"], t["cleared"]) for t in tags] == [
        ("miss", 4, 0), ("miss", 4, 1), ("set", 0, 0)]
    assert node_metrics("keytable_clears_total") == 1
    # forget_keys is no overflow: it empties the table and counts nothing
    table.clear()
    assert (table.overflow_clears, table.generation) == (1, 2)
