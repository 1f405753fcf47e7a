"""What decides ``correct``: the program's answers against the benchmark's
own plain reference (benchmark/reference, pure Python), outside the timed
window, in every run.

The corruption kinds and the way they are drawn from the seed are
chip_smoke.py's (PR 21): a flipped signature bit, S >= L, a truncated
signature, and a validator whose registered key is no curve point. Lanes the
generator left alone are valid by construction, so the reference runs on the
corrupted lanes and on a seeded sample of the others, not on all of them.

Since PR 25 a commit may flag validators Absent or Nil
(``datagen.signer_pattern``): corruptions are drawn among the signatures that
are present, one lands on a nil vote where the commit has any, and the light
prefix of every pooled height is held to the benchmark's own rule
(benchmark/reference/light_prefix.py).
"""

from __future__ import annotations

from benchmark.harness import datagen
from benchmark.reference import ed25519_ref, light_prefix, sr25519_ref

SAMPLE = {"ed25519": 256, "sr25519": 32}
_REFERENCE = {"ed25519": ed25519_ref.verify, "sr25519": sr25519_ref.verify}


def reference_lane(ds, commit, idx: int) -> bool:
    v = ds.vals.validators[idx]
    return _REFERENCE[v.pub_key.type](
        v.pub_key.bytes(), commit.vote_sign_bytes(ds.chain_id, idx),
        commit.signatures[idx].signature)


def corrupted_commit(ds, seed: int, k: int | None = None):
    """Clean commit k (the first pooled height that holds a nil vote, when
    none is named) with seeded corruptions -> (commit, {idx: kind}). Two land
    inside the +2/3 prefix, so the light entry point sees them too; one lands
    on an sr25519 lane where the set has any and one on a nil vote where the
    commit has any. All are drawn among the signatures that are present."""
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL

    if k is None:
        k = next((h for h, nil in enumerate(ds.nil) if nil.any()), 0)
    clean, _absent = datagen.presented(ds, seed, k, "check")
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
    free = [i for i in range(n)
            if i != off and not clean.signatures[i].absent()]
    sr = [i for i in free if ds.key_type(i) == "sr25519"]
    nil = [i for i in free
           if clean.signatures[i].block_id_flag == BLOCK_ID_FLAG_NIL]
    pools = ([prefix, prefix, free, free, free, free] + ([sr] if sr else [])
             + ([nil] if nil else []))
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
    present = [i for i, cs in enumerate(bad.signatures) if not cs.absent()]
    missing = set(range(ds.vals.size())) - set(present)
    sample = sample_lanes(ds, run.seed, missing | set(corrupted))
    reference = {i: reference_lane(ds, bad, i) for i in list(corrupted) + sample}
    for i, kind in corrupted.items():
        if reference[i]:
            fail(f"reference accepts lane {i} ({kind})")
    for i in sample:
        if not reference[i]:
            fail(f"reference rejects untouched lane {i}")
    run.notes["corruptions"] = {str(i): k for i, k in sorted(corrupted.items())}
    run.notes["corrupted_nil_votes"] = sum(
        1 for i in corrupted if not bad.signatures[i].for_block())
    run.notes["reference_lanes"] = len(reference)

    # (1) the same entry point rejects it at the reference's first bad index
    needed = ds.vals.total_voting_power() * 2 // 3
    for verify in entry_points:
        lanes = (ds.vals.commit_light_prefix(bad, needed)
                 if verify.__name__.endswith("_light") else present)
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
    for i in present:
        verifier.add(ds.vals.validators[i].pub_key,
                     bad.vote_sign_bytes(ds.chain_id, i),
                     bad.signatures[i].signature)
    all_ok, bitmap = verifier.dispatch().resolve()
    if all_ok or len(bitmap) != len(present):
        fail("registry bitmap: all_ok or length wrong")
    else:
        wrong = [i for i, ok in zip(present, bitmap)
                 if ok != reference.get(i, True)]
        if wrong:
            fail(f"registry bitmap differs from the reference at {wrong[:8]}")

    check_light_prefix(run, ds)
    check_breakers(run)


def check_light_prefix(run, ds) -> None:
    """Every pooled height, as a caller presents it: the slots
    ``ValidatorSet.commit_light_prefix`` would verify are those of the
    benchmark's own rule, address for address."""
    validators = [(v.address, v.voting_power) for v in ds.vals.validators]
    needed = ds.vals.total_voting_power() * 2 // 3
    lengths = []
    for k in range(len(ds.commits)):
        commit, _absent = datagen.presented(ds, run.seed, k, "prefix-check", k)
        got = [ds.vals.validators[i].address
               for i in ds.vals.commit_light_prefix(commit, needed)]
        want = light_prefix.light_prefix(
            validators, {cs.validator_address: cs.block_id_flag
                         for cs in commit.signatures if not cs.absent()})
        lengths.append(len(want))
        if got != want:
            at = next((j for j, (g, w) in enumerate(zip(got, want)) if g != w),
                      min(len(got), len(want)))
            run.failures.append(
                f"light prefix of pooled height {commit.height}: the program "
                f"takes {len(got)} signatures, the reference {len(want)}; "
                f"they part at place {at}")
    run.notes["light_prefix_sigs"] = [min(lengths), max(lengths)]


def check_breakers(run) -> None:
    """(4): both device breakers at zero failures, nothing fell back."""
    from tendermint_tpu.crypto import verify_service
    from tendermint_tpu.ops import ed25519_batch, sr25519_batch

    for mod in (ed25519_batch, sr25519_batch):
        b = mod.BREAKER
        if b.failures:
            run.fail("breakers", f"breaker {b.name}: {b.failures} failure(s), "
                                 f"last {b.last_error!r}")
    if verify_service.get().fallbacks:
        run.fail("fallbacks",
                 f"verify service fell back {verify_service.get().fallbacks}x")
