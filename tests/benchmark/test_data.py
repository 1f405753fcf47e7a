"""The benchmark's data: a function of the seed, whoever signs; its plain
reference agrees with the program's scalar verifiers on every corruption
kind; the dataset cache is reused for the same seed and only for it."""

import subprocess
import sys

import pytest

from benchmark.harness import correct, datagen, signing
from benchmark.harness.spec import ROOT
from benchmark.reference import ed25519_ref, sr25519_ref
from tendermint_tpu.crypto import ed25519, sr25519

TINY = {"dataset": {"chain_id": "bench-test", "voting_power": 10,
                    "validators": {"ed25519": 14, "sr25519": 6},
                    "heights": 3, "chained_blocks": True}}


def _generate(seed, openssl):
    with signing.SignerPool(workers=0, openssl=openssl) as pool:
        return datagen.generate(TINY, seed, pool)


@pytest.fixture(scope="module")
def tiny():
    return _generate(5, openssl=False)


@pytest.mark.skipif(not signing.have_openssl(), reason="no cryptography package")
def test_same_seed_same_bytes_through_openssl_and_the_fallback_signer(tiny):
    other = _generate(5, openssl=True)
    assert datagen.content_digest(other) == datagen.content_digest(tiny)
    assert other.sigs.tobytes() == tiny.sigs.tobytes()


def test_same_seed_twice_is_identical_and_another_seed_is_not(tiny):
    assert datagen.content_digest(_generate(5, False)) == datagen.content_digest(tiny)
    assert datagen.content_digest(_generate(6, False)) != datagen.content_digest(tiny)


def test_the_benchmarks_signers_equal_the_programs():
    seed, msg, rng = datagen.derive(1, "k"), b"m" * 111, datagen.derive(1, "r")
    pub = ed25519.pubkey_from_seed(seed)
    assert ed25519_ref.pubkey_fixed_base(seed) == pub
    assert ed25519_ref.sign_fixed_base(seed, pub, msg) == ed25519.sign(seed + pub, msg)
    spub = sr25519.pubkey_from_mini(seed)
    assert sr25519_ref.pubkey_fast(seed) == spub
    assert sr25519_ref.sign_fast(seed, spub, msg, rng) == sr25519.sign(seed, msg, rng)
    assert sr25519_ref.sign(seed, msg, rng) == sr25519.sign(seed, msg, rng)


def test_reference_copies_agree_with_the_program_on_every_corruption_kind(tiny):
    bad, corrupted = correct.corrupted_commit(tiny, 5)
    kinds = " ".join(corrupted.values())
    for kind in ("flipped signature bit", "S >= L", "truncated signature",
                 "off-curve pubkey"):
        assert kind in kinds
    assert {tiny.key_type(i) for i in corrupted} == {"ed25519", "sr25519"}
    program = {"ed25519": ed25519.verify, "sr25519": sr25519.verify}
    for i, v in enumerate(tiny.vals.validators):
        args = (v.pub_key.bytes(), bad.vote_sign_bytes(tiny.chain_id, i),
                bad.signatures[i].signature)
        want = program[v.pub_key.type](*args)
        assert correct.reference_lane(tiny, bad, i) == want
        assert want == (i not in corrupted), (i, corrupted.get(i))


def test_clean_commits_are_what_the_entry_points_accept(tiny):
    for commit in tiny.commits:
        assert commit.signatures[tiny.off_idx].absent()
        tiny.vals.verify_commit(tiny.chain_id, commit.block_id, commit.height,
                                commit)
    # the chain carries them: block h+1's LastCommit is the commit for h
    assert tiny.blocks[1].last_commit is tiny.commits[0]
    stamps = {(cs.timestamp.seconds, cs.timestamp.nanos)
              for cs in tiny.commits[0].signatures if not cs.absent()}
    assert len(stamps) == tiny.vals.size() - 1   # one clock per validator


def test_cached_dataset_is_reused_and_another_seed_is_not(tmp_path):
    a = datagen.load_or_generate("tiny", TINY, 7, str(tmp_path), workers=0)
    b = datagen.load_or_generate("tiny", TINY, 7, str(tmp_path), workers=0)
    c = datagen.load_or_generate("tiny", TINY, 8, str(tmp_path), workers=0)
    assert (a.meta["cached"], b.meta["cached"], c.meta["cached"]) == (False, True, False)
    assert datagen.content_digest(a) == datagen.content_digest(b)
    assert datagen.content_digest(a) != datagen.content_digest(c)
    assert [x.block_id for x in a.commits] == [x.block_id for x in b.commits]
    # other dataset parameters under the same name and seed: generated anew
    other = {"dataset": {**TINY["dataset"], "heights": 2}}
    d = datagen.load_or_generate("tiny", other, 7, str(tmp_path), workers=0)
    assert not d.meta["cached"] and len(d.commits) == 2


def test_signing_children_give_the_same_bytes_and_never_import_jax():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.harness import signing\n"
        "import hashlib\n"
        "derive = lambda *p: hashlib.sha256(repr(p).encode()).digest()\n"
        "jobs = [(derive(1, i), signing.public_keys('sr25519', False, [derive(1, i)])[0],"
        " b'm%%d' %% i, derive(2, i)) for i in range(40)]\n"
        "with signing.SignerPool(workers=2) as pool:\n"
        "    fanned = pool.sign('sr25519', jobs)\n"
        "assert fanned == signing.sign_jobs('sr25519', False, jobs)\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
        "print('ok')\n" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]


# --- PR 25: the commit a live network writes ---------------------------------

# as the parent (PR 24) prints them: `dataset.digest` of a --rehearse run
PARENT_REHEARSAL_DIGESTS = {
    ("hub-10k", 11): "5f9cd4167f674d1f84465ab40f788f3143c52b00b81559320b0d3e71669ba1d8",
    ("fastsync-1k-mixed", 11): "bead866614f858912bbd7aceba5c02fe2979b3ed121984f5cc74cb42c47c4986",
    ("hub-10k", 25): "2ffa3636fa5192c3fb1d3ed2d3b5b6562dcae9d9ab60facfe0f559a66a4633d8",
    ("fastsync-1k-mixed", 25): "b4928334fc97a95d575cdd26129e080ad144cef517824a1ac350b7df1070f7ec",
}


def _rehearsal_config(name):
    import json
    import os

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
    return cfg


@pytest.mark.parametrize("name, seed", sorted(PARENT_REHEARSAL_DIGESTS))
def test_the_two_old_configurations_generate_what_the_parent_did(name, seed, tmp_path):
    """The generator's new keys are optional: a configuration without them
    gets every public key and signature byte it got before PR 25."""
    cfg = _rehearsal_config(name)
    assert not {"absent_share", "nil_share"} & set(cfg["dataset"])
    ds = datagen.load_or_generate(name, cfg, seed, str(tmp_path), workers=0)
    assert datagen.content_digest(ds) == PARENT_REHEARSAL_DIGESTS[name, seed]
    assert ds.redraws == 0 and not ds.absent_per_decision
    assert not any(nil.any() for nil in ds.nil)
    for commit in ds.commits:       # one absentee, the off-curve validator
        assert [i for i, cs in enumerate(commit.signatures)
                if cs.absent()] == [ds.off_idx]


def _live(chained, **dataset):
    return {"dataset": {"chain_id": "bench-live", "validators":
                        {"ed25519": 40, "sr25519": 0},
                        "voting_power": {"zipf_exponent": 0.8, "top": 1000},
                        "absent_share": 0.1, "nil_share": 0.05, "heights": 5,
                        "chained_blocks": chained, **dataset}}


def _generate_live(cfg, seed):
    with signing.SignerPool(workers=0) as pool:
        return datagen.generate(cfg, seed, pool)


@pytest.fixture(scope="module")
def live_chain():
    return _generate_live(_live(True), 5)


@pytest.fixture(scope="module")
def live_pool():
    return _generate_live(_live(False), 5)


def _flags(commit):
    return [cs.block_id_flag for cs in commit.signatures]


def test_zipf_power_by_rank_and_an_integer_is_equal_power():
    assert datagen.voting_powers(10, 3) == [10, 10, 10]
    zipf = datagen.voting_powers({"zipf_exponent": 0.8, "top": 10_000_000}, 150)
    assert zipf[0] == 10_000_000 and zipf[1] == round(10_000_000 / 2 ** 0.8)
    assert zipf == sorted(zipf, reverse=True) and zipf[-1] >= 1
    total = sum(zipf)
    # what benchmark/configs/hub-150.json says of its skew
    assert sum(zipf[:7]) * 3 < total < sum(zipf[:8]) * 3
    assert sum(zipf[:41]) * 3 < 2 * total < sum(zipf[:42]) * 3
    assert datagen.voting_powers({"zipf_exponent": 3, "top": 5}, 4) == [5, 1, 1, 1]


def test_same_seed_same_absent_and_nil_pattern_another_seed_another(live_chain):
    again, other = _generate_live(_live(True), 5), _generate_live(_live(True), 6)
    assert [_flags(c) for c in again.commits] == [_flags(c) for c in live_chain.commits]
    assert datagen.content_digest(again) == datagen.content_digest(live_chain)
    assert [_flags(c) for c in other.commits] != [_flags(c) for c in live_chain.commits]
    # the pattern differs from height to height, and all three flags occur
    assert len({tuple(_flags(c)) for c in live_chain.commits}) == len(live_chain.commits)
    assert {f for c in live_chain.commits for f in _flags(c)} == {1, 2, 3}


def test_with_a_pattern_seed_every_seed_has_the_same_signers_and_other_keys(
        live_chain):
    a = _generate_live(_live(True, pattern_seed=3), 5)
    b = _generate_live(_live(True, pattern_seed=3), 6)
    absent = [[cs.absent() for j, cs in enumerate(c.signatures) if j != ds.off_idx]
              for ds in (a, b) for c in ds.commits]
    needed = a.vals.total_voting_power() * 2 // 3
    assert [len(a.vals.commit_light_prefix(c, needed)) for c in a.commits] == \
           [len(b.vals.commit_light_prefix(c, needed)) for c in b.commits]
    assert sum(map(sum, absent[:5])) == sum(map(sum, absent[5:])) > 0
    assert datagen.content_digest(a) != datagen.content_digest(b)
    # and it is another pattern than the seed's own
    assert [_flags(c) for c in a.commits] != [_flags(c) for c in live_chain.commits]
    # the off-curve absentee is part of the pattern: one slot for every seed
    # under a pattern seed, the seed's own without one
    assert a.off_idx == b.off_idx
    assert len({_generate_live(_live(True), s).off_idx for s in range(5, 9)}) > 1


@pytest.mark.parametrize("chained", [True, False], ids=["chain", "pool"])
def test_every_commit_reaches_two_thirds_and_both_entry_points_accept_it(
        chained, live_chain, live_pool):
    ds = live_chain if chained else live_pool
    needed = ds.vals.total_voting_power() * 2 // 3
    for k in range(len(ds.commits)):
        for decision in range(3):
            commit, absent = datagen.presented(ds, 5, k, decision)
            assert (absent is None) == chained
            for_block = sum(v.voting_power for v, cs in
                            zip(ds.vals.validators, commit.signatures)
                            if cs.for_block())
            assert for_block > needed
            for verify in (ds.vals.verify_commit, ds.vals.verify_commit_light):
                verify(ds.chain_id, commit.block_id, commit.height, commit)
    if chained:     # the chain carries the pattern: the block hash covers it
        assert ds.blocks[2].last_commit is ds.commits[1]


def test_a_draw_short_of_two_thirds_is_redrawn_from_the_same_seed():
    import numpy as np

    powers = np.full(12, 10, np.int64)
    redrawn = 0
    for scope in range(40):
        absent, nil, again = datagen.signer_pattern(7, powers, 0, 0.3, 0.1, scope)
        assert absent[0] and not (absent & nil).any()
        assert int(powers[~absent & ~nil].sum()) > int(powers.sum()) * 2 // 3
        redrawn += again
        same = datagen.signer_pattern(7, powers, 0, 0.3, 0.1, scope)
        assert (same[0] == absent).all() and (same[1] == nil).all()
    assert redrawn > 0      # at these shares a first draw does fall short
    with pytest.raises(ValueError):
        datagen.signer_pattern(7, powers, 0, 1.0, 0.0, "never")


def test_the_references_light_prefix_equals_the_programs_on_skewed_power(
        live_chain, live_pool):
    from benchmark.reference import light_prefix

    lengths = set()
    for ds in (live_chain, live_pool):
        validators = [(v.address, v.voting_power) for v in ds.vals.validators]
        needed = ds.vals.total_voting_power() * 2 // 3
        for k in range(len(ds.commits)):
            commit, _ = datagen.presented(ds, 5, k, "t")
            flags = {cs.validator_address: cs.block_id_flag
                     for cs in commit.signatures if not cs.absent()}
            # handed over in another order: the rule sorts for itself
            want = light_prefix.light_prefix(validators[::-1], flags)
            got = [ds.vals.validators[i].address
                   for i in ds.vals.commit_light_prefix(commit, needed)]
            assert got == want
            lengths.add(len(want))
            skipped = [cs for cs in commit.signatures[:len(want)]
                       if not cs.for_block()]
            assert all(cs.validator_address not in want for cs in skipped)
    assert len(lengths) > 1     # absences move the stopping point


def test_corruptions_land_on_present_signatures_and_on_a_nil_vote(live_pool):
    bad, corrupted = correct.corrupted_commit(live_pool, 5)
    clean, _ = datagen.presented(live_pool, 5, bad.height - 1, "check")
    assert all(not clean.signatures[i].absent() for i in corrupted
               if i != live_pool.off_idx)
    assert any(bad.signatures[i].block_id_flag == 3 for i in corrupted)
    for i in corrupted:
        assert not correct.reference_lane(live_pool, bad, i)


def test_no_two_decisions_of_a_window_present_the_same_signer_set():
    """The tip driver on an unchained pool with absences, at a size where a
    repeat would be a fault of the draw (300 validators, 5% absent)."""
    import types

    from benchmark.harness import spec

    ds = _generate_live(_live(False, heights=2, absent_share=0.05, nil_share=0.0,
                              validators={"ed25519": 300, "sr25519": 0}), 9)
    driver_mod = spec._module(spec.os.path.join(spec.BENCH_DIR, "drivers", "tip.py"),
                              "Driver")
    seen = []
    run = types.SimpleNamespace(seed=9, notes={})
    run.decide = lambda fn, sigs: seen.append(sigs) or fn()
    driver = driver_mod.Driver(run, ds, {"entry_point": "verify_commit",
                                         "warmup_decisions": 2})
    driver.warm_up()
    warm = set(driver._signer_sets)
    driver._signer_sets.clear()
    for _ in range(40):
        driver._decide_next()
    assert len(driver._signer_sets) == 40 and not warm & driver._signer_sets
    assert len(set(seen)) > 1 and max(seen) < 299   # signatures vary, some absent
    # the pooled commits stay as signed: one absentee each
    assert all(sum(cs.absent() for cs in c.signatures) == 1 for c in ds.commits)
