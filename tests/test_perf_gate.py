"""Perf regression gate for the consensus-path verify flushes (VERDICT r3
weak #4/#8): verify_commit and verify_commit_light at 256 and 1024
validators must stay BATCHED — exactly one kernel dispatch per call, the
scalar fallback never taken — and complete within a generous wall-clock
ceiling so a silent fall-back to serial verification (the reference's
per-signature loop, types/validator_set.go:719) cannot land unnoticed.

Flush counting is the hard gate; the wall-clock ceilings are sanity bounds
chosen loose enough for the noisy 1-core CI host."""

import time

import pytest

from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.types.block import Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, PRECOMMIT_TYPE, Vote

CHAIN_ID = "perf-gate-chain"
WALL_CEILING_S = {256: 20.0, 1024: 40.0}


def _signed_commit(vals, privs, height, round_, bid, ts):
    """One precommit per validator over the canonical sign bytes — the
    single commit builder every gate in this module uses."""
    sigs = []
    for i, (p, v) in enumerate(zip(privs, vals.validators)):
        vote = Vote(type=PRECOMMIT_TYPE, height=height, round=round_,
                    block_id=bid, timestamp=ts, validator_address=v.address,
                    validator_index=i)
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                              p.sign(vote.sign_bytes(CHAIN_ID))))
    return Commit(height=height, round=round_, block_id=bid, signatures=sigs)


def _mk_vals(n):
    privs = [ed25519.gen_priv_key((i + 1).to_bytes(2, "big") * 16)
             for i in range(n)]
    vals = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    return [by_addr[v.address] for v in vals.validators], vals


def _commit(n):
    privs, vals = _mk_vals(n)
    bid = BlockID(hash=b"\x42" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x43" * 32))
    return vals, _signed_commit(vals, privs, 3, 0, bid, Time(1_700_000_500, 0))


class _FlushCounter:
    """Counts kernel dispatches vs scalar fallbacks through the verifier."""

    def __init__(self, monkeypatch):
        self.kernel = 0
        self.scalar = 0
        orig = cbatch._KernelBatchVerifier.dispatch
        counter = self

        def counted(vself, force_device=False):
            small = len(vself._items) < cbatch.batch_min(
                vself._batch_min_default)
            if small and not force_device:
                counter.scalar += 1
            else:
                counter.kernel += 1
            return orig(vself, force_device=force_device)

        monkeypatch.setattr(cbatch._KernelBatchVerifier, "dispatch", counted)


@pytest.mark.parametrize("n_vals", [256, 1024])
def test_verify_commit_stays_batched(n_vals, monkeypatch):
    vals, commit = _commit(n_vals)
    # warm BOTH call shapes outside the gate (first-ever XLA compile of a
    # new padded shape is O(minutes) and must not count against the ceiling)
    vals.verify_commit(CHAIN_ID, commit.block_id, 3, commit)
    vals.verify_commit_light(CHAIN_ID, commit.block_id, 3, commit)

    fc = _FlushCounter(monkeypatch)
    t0 = time.monotonic()
    vals.verify_commit(CHAIN_ID, commit.block_id, 3, commit)
    full_s = time.monotonic() - t0
    assert fc.kernel == 1, f"verify_commit used {fc.kernel} kernel flushes"
    assert fc.scalar == 0, "verify_commit fell back to the scalar loop"

    t0 = time.monotonic()
    vals.verify_commit_light(CHAIN_ID, commit.block_id, 3, commit)
    light_s = time.monotonic() - t0
    assert fc.kernel == 2, "verify_commit_light did not flush exactly once"
    assert fc.scalar == 0

    ceiling = WALL_CEILING_S[n_vals]
    assert full_s < ceiling, f"verify_commit {full_s:.1f}s > {ceiling}s"
    assert light_s < ceiling, f"verify_commit_light {light_s:.1f}s > {ceiling}s"


@pytest.mark.quick
def test_verify_ahead_batches_blocking_fetches(monkeypatch):
    """The verify-ahead pipeline gate (no wall clock, no kernels): over the
    same chain, a depth-4 pipeline must issue NO MORE blocking device
    fetches than depth 1 — the whole point of verify-ahead is amortizing
    the per-fetch sync floor across in-flight decisions. Kernel dispatch is
    stubbed with a sentinel "device" output (the scalar result computed
    eagerly), and the fetch-spy counts crypto_batch._device_get calls, the
    one choke point every blocking readback passes through."""
    from tendermint_tpu.blockchain.replay import ReplayCtx, make_chain
    from tendermint_tpu.blockchain import pipeline as bpipe

    n_blocks = 8
    privs, vals = _mk_vals(4)
    blocks = make_chain(CHAIN_ID, n_blocks + 1, vals, privs)

    def fake_dispatch(self, force_device=False):
        items, self._items = self._items, []
        out = [ed25519.verify(p, m, s) for (p, m, s) in items]
        return cbatch.PendingVerify(
            [object()], lambda _f, _r=(all(out), out): _r)

    fetches = {"n": 0}

    def counting_get(tree):
        fetches["n"] += 1
        return tree  # sentinel "device" outputs need no real transfer

    monkeypatch.setattr(cbatch._KernelBatchVerifier, "dispatch", fake_dispatch)
    monkeypatch.setattr(cbatch, "_device_get", counting_get)

    def run_depth(depth):
        monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", str(depth))
        ctx = ReplayCtx(vals, CHAIN_ID)
        for b in blocks:
            ctx.pool.add_block("p", b)
        pipe = bpipe.VerifyAheadPipeline()
        fetches["n"] = 0
        applied = 0
        while pipe.process_next(ctx):
            applied += 1
        assert applied == n_blocks
        return fetches["n"]

    depth1 = run_depth(1)
    depth4 = run_depth(4)
    assert depth1 == n_blocks, f"depth-1 issued {depth1} fetches, expected one per block"
    # strictly fewer (which also satisfies the <= acceptance bound)
    assert depth4 < depth1, (
        f"depth-4 pipeline did not batch readbacks: {depth4} fetches vs "
        f"depth-1's {depth1}")


@pytest.mark.quick
def test_registry_bitmap_off_a_tpu_is_one_devices_whatever_the_count(
        monkeypatch):
    """Quick tier: off a TPU the REGISTRY-level dispatch
    (crypto/batch.create_batch_verifier -- the exact object
    verify_commit_async, fast-sync, the vote drain, and range_verify
    construct) spreads nothing over the CPU's several devices: every batch
    takes the `device` route and the jnp kernels answer with the scalar
    ground truth, valid + tampered lanes, for ed25519, sr25519, and the
    mixed router.

    Small tiles keep the one-time XLA compiles bounded on the CI host: the
    jnp route dispatches in fixed JNP_TILE chunks, so shrinking JNP_TILE
    shrinks the compiled chunk without changing the routing."""
    import jax

    if jax.local_device_count() < 2:
        pytest.skip("needs the CPU's forced devices")
    from tendermint_tpu.crypto import sr25519
    from tendermint_tpu.ops import ed25519_batch as edb

    monkeypatch.setattr(edb, "JNP_TILE", 16)
    monkeypatch.setenv("TM_TPU_BATCH_MIN", "1")
    monkeypatch.delenv("TM_TPU_SHARD", raising=False)

    def ed_item(i, tamper=False):
        p = ed25519.gen_priv_key(bytes([i % 61 + 1]) * 32)
        m = b"gate-ed-%d" % i
        s = p.sign(m)
        if tamper:
            s = s[:-1] + bytes([s[-1] ^ 1])
        return (p.pub_key(), m, s)

    def sr_item(i, tamper=False):
        p = sr25519.gen_priv_key(bytes([i % 13 + 1]) * 32)
        m = b"gate-sr-%d" % i
        s = p.sign(m)
        if tamper:
            s = s[:-2] + bytes([s[-2] ^ 1]) + s[-1:]
        return (p.pub_key(), m, s)

    ed_items = [ed_item(i, tamper=i in (3, 20)) for i in range(44)]
    sr_items = [sr_item(i, tamper=i == 5) for i in range(20)]
    # Mixed: interleave so the router's order restoration is exercised.
    mixed, want_mixed = [], []
    for i in range(36):
        if i % 2 == 0:
            mixed.append(ed_item(i, tamper=i == 8))
            want_mixed.append(i != 8)
        else:
            mixed.append(sr_item(i, tamper=i == 11))
            want_mixed.append(i != 11)

    def registry(key_type, items):
        v = cbatch.create_batch_verifier(key_type)
        for pk, m, s in items:
            v.add(pk, m, s)
        return v.dispatch().resolve()

    cases = [("ed25519", ed_items, [i not in (3, 20) for i in range(44)]),
             ("sr25519", sr_items, [i != 5 for i in range(20)]),
             (None, mixed, want_mixed)]
    for key_type, items, want in cases:
        assert edb.route_batch(len(items)) == "device"
        all_ok, bitmap = registry(key_type, items)
        assert bitmap == want, f"{key_type}: bitmap != scalar ground truth"
        assert all_ok == all(want)


def test_range_verify_one_flush_and_no_scalar_header_hashing(monkeypatch):
    """BASELINE config 3's shape must not silently regress: the whole range
    verifies in EXACTLY one kernel flush, and header hashing goes through
    the batched merkle forest (precompute fills every cache; the scalar
    fallback inside Header.hash must not run for range members)."""
    from tendermint_tpu.light.range_verify import verify_header_range
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    n_headers = 65
    privs, vals = _mk_vals(1)
    chain = []
    last_bid = BlockID()
    for h in range(1, n_headers + 1):
        header = Header(chain_id=CHAIN_ID, height=h, time=Time(1_700_000_000 + 10 * h, 0),
                        last_block_id=last_bid, validators_hash=vals.hash(),
                        next_validators_hash=vals.hash(),
                        proposer_address=vals.validators[0].address)
        bid = BlockID(hash=header.hash(),
                      part_set_header=PartSetHeader(total=1, hash=b"\x44" * 32))
        commit = _signed_commit(vals, privs, h, 1, bid,
                                Time(header.time.seconds, 0))
        chain.append(LightBlock(signed_header=SignedHeader(header, commit),
                                validator_set=vals.copy()))
        last_bid = bid

    trusted, rest = chain[0], chain[1:]
    now = Time(1_700_000_000 + 10 * (n_headers + 2), 0)
    verify_header_range(trusted, rest, 14 * 86400.0, now)  # warm/compile
    for lb in rest:
        lb.signed_header.header._hash_cache = None

    from tendermint_tpu.crypto import merkle

    def no_scalar_header_hash(items):
        if len(items) == 14:
            raise AssertionError(
                "scalar header hash ran inside range verify; the batched "
                "forest (precompute_header_hashes) must cover the range")
        return orig_hash(items)

    orig_hash = merkle.hash_from_byte_slices
    fc = _FlushCounter(monkeypatch)
    monkeypatch.setattr(merkle, "hash_from_byte_slices", no_scalar_header_hash)
    try:
        verify_header_range(trusted, rest, 14 * 86400.0, now)
    finally:
        monkeypatch.setattr(merkle, "hash_from_byte_slices", orig_hash)
    assert fc.kernel == 1, (
        f"range verify used {fc.kernel} kernel flushes, expected 1")
    assert fc.scalar == 0, "range verify fell back to the scalar loop"
