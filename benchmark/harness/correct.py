"""What decides ``correct``: the program's answers against the benchmark's
own plain reference (benchmark/reference, pure Python), outside the timed
window, in every run.

The corruption kinds and the way they are drawn from the seed are
chip_smoke.py's (PR 21): a flipped signature bit, S >= L, a truncated
signature, and a validator whose registered key is no curve point. Lanes the
generator left alone are valid by construction, so the reference runs on the
corrupted lanes and on a seeded sample of the others, not on all of them.
"""

from __future__ import annotations

from benchmark.harness import datagen
from benchmark.reference import ed25519_ref, sr25519_ref

SAMPLE = {"ed25519": 256, "sr25519": 32}
_REFERENCE = {"ed25519": ed25519_ref.verify, "sr25519": sr25519_ref.verify}


def reference_lane(ds, commit, idx: int) -> bool:
    v = ds.vals.validators[idx]
    return _REFERENCE[v.pub_key.type](
        v.pub_key.bytes(), commit.vote_sign_bytes(ds.chain_id, idx),
        commit.signatures[idx].signature)


def corrupted_commit(ds, seed: int, k: int = 0):
    """Clean commit k with seeded corruptions -> (commit, {idx: kind}). Two
    land inside the +2/3 prefix, so the light entry point sees them too; one
    lands on an sr25519 lane where the set has any."""
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT

    clean = ds.commits[k]
    vals, n = ds.vals, ds.vals.size()
    sigs = list(clean.signatures)
    corrupted: dict[int, str] = {}

    def corrupt(idx: int, kind: str, sig: bytes) -> None:
        cs = sigs[idx]
        sigs[idx] = CommitSig.new_commit(cs.block_id_flag, cs.validator_address,
                                         cs.timestamp, sig)
        corrupted[idx] = kind

    off = ds.off_idx
    sigs[off] = CommitSig(BLOCK_ID_FLAG_COMMIT, vals.validators[off].address,
                          datagen._timestamp(seed, clean.height, off), b"")
    corrupt(off, "off-curve pubkey", ds.spare_sig)
    needed = vals.total_voting_power() * 2 // 3
    prefix = vals.commit_light_prefix(clean, needed)
    free = [i for i in range(n) if i != off]
    sr = [i for i in free if ds.key_type(i) == "sr25519"]
    pools = [prefix, prefix, free, free, free, free] + ([sr] if sr else [])
    picks: list[int] = []
    for j, pool in enumerate(pools):
        pool = [i for i in pool if i not in picks and i != off]
        if not pool:        # a rehearsal's tiny set can run out of lanes
            continue
        picks.append(pool[datagen.pick(seed, len(pool), "corrupt", j)])
    for j, idx in enumerate(picks):
        good = clean.signatures[idx].signature
        if j % 3 == 0:
            bit = datagen.pick(seed, 512, "bit", j)
            # bit 511 is schnorrkel's marker: flipping it is a format error,
            # which is a rejection too, but keep the kind what it says
            bit = bit if bit != 511 else 510
            flipped = bytearray(good)
            flipped[bit // 8] ^= 1 << (bit % 8)
            corrupt(idx, f"flipped signature bit {bit}", bytes(flipped))
        elif j % 3 == 1:
            corrupt(idx, "S >= L", good[:32] + b"\xff" * 32)
        else:
            corrupt(idx, "truncated signature", good[:63])
    bad = Commit(height=clean.height, round=clean.round,
                 block_id=clean.block_id, signatures=sigs)
    return bad, corrupted


def sample_lanes(ds, seed: int, exclude) -> list[int]:
    """A seeded sample of untouched lanes, per key type."""
    out: list[int] = []
    for kind, want in SAMPLE.items():
        lanes = [i for i in range(ds.vals.size())
                 if i not in exclude and ds.key_type(i) == kind]
        for j in range(min(want, len(lanes))):
            out.append(lanes.pop(datagen.pick(seed, len(lanes), "sample", kind, j)))
    return out


def check_decisions(run, ds, entry_points: list) -> None:
    """Checks (1), (2) and (4) of the issue; failures go to run.failures.
    ``entry_points``: bound ValidatorSet.verify_commit* methods the cell's
    traffic drives."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.types.validator_set import ErrWrongSignature

    fail = run.failures.append
    bad, corrupted = corrupted_commit(ds, run.seed)
    sample = sample_lanes(ds, run.seed, corrupted)
    reference = {i: reference_lane(ds, bad, i) for i in list(corrupted) + sample}
    for i, kind in corrupted.items():
        if reference[i]:
            fail(f"reference accepts lane {i} ({kind})")
    for i in sample:
        if not reference[i]:
            fail(f"reference rejects untouched lane {i}")
    run.notes["corruptions"] = {str(i): k for i, k in sorted(corrupted.items())}
    run.notes["reference_lanes"] = len(reference)

    # (1) the same entry point rejects it at the reference's first bad index
    needed = ds.vals.total_voting_power() * 2 // 3
    for verify in entry_points:
        lanes = (ds.vals.commit_light_prefix(bad, needed)
                 if verify.__name__.endswith("_light") else range(ds.vals.size()))
        want = next(i for i in lanes if i in corrupted)
        try:
            verify(ds.chain_id, bad.block_id, bad.height, bad)
        except ErrWrongSignature as e:
            if e.index != want:
                fail(f"{verify.__name__}: first bad index {e.index}, "
                     f"reference says {want}")
        except Exception as e:  # noqa: BLE001 - any other answer is wrong
            fail(f"{verify.__name__}: {type(e).__name__}: {e}")
        else:
            fail(f"{verify.__name__} accepted a corrupted commit "
                 f"(reference rejects index {want})")

    # (2) the whole bitmap through the registry
    key_types = {v.pub_key.type for v in ds.vals.validators}
    verifier = crypto_batch.create_batch_verifier(
        next(iter(key_types)) if len(key_types) == 1 else None)
    for i, v in enumerate(ds.vals.validators):
        verifier.add(v.pub_key, bad.vote_sign_bytes(ds.chain_id, i),
                     bad.signatures[i].signature)
    all_ok, bitmap = verifier.dispatch().resolve()
    if all_ok or len(bitmap) != ds.vals.size():
        fail("registry bitmap: all_ok or length wrong")
    else:
        wrong = [i for i in range(len(bitmap))
                 if bitmap[i] != reference.get(i, True)]
        if wrong:
            fail(f"registry bitmap differs from the reference at {wrong[:8]}")

    check_breakers(run)


def check_breakers(run) -> None:
    """(4): both device breakers at zero failures, nothing fell back."""
    from tendermint_tpu.crypto import verify_service
    from tendermint_tpu.ops import ed25519_batch, sr25519_batch

    for mod in (ed25519_batch, sr25519_batch):
        b = mod.BREAKER
        if b.failures:
            run.failures.append(f"breaker {b.name}: {b.failures} failure(s), "
                                f"last {b.last_error!r}")
    if verify_service.get().fallbacks:
        run.failures.append(
            f"verify service fell back {verify_service.get().fallbacks}x")
