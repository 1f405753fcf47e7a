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
