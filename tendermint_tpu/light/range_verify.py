"""Batched sequential header-range verification — BASELINE config 3.

The reference light client verifies a header chain one header at a time, each
`VerifyAdjacent` paying a serial loop of ed25519 verifies
(light/verifier.go:93 -> types/validator_set.go:719). On TPU that is the wrong
shape: a 10k-header catch-up is ~10k * 2/3|V| signatures that are all known up
front.

`verify_header_range` does the cheap hash-linkage checks serially on host
(NextValidatorsHash chaining, time monotonicity, validator-hash match), queues
every commit's serial-semantics signature prefix into ONE BatchVerifier flush
(one wide TPU kernel launch), then replays each header's serial accept/reject
decision over the returned bitmap. The overall accept/reject matches running
verify_adjacent per header; the one reporting difference is error ORDERING:
a structural defect anywhere in the range is detected in the host pass and
therefore reported before a bad SIGNATURE at an earlier height (a sequential
loop would hit the earlier signature first) -- and the set-size check
(len(signatures) == validator set size) runs even earlier, in the dispatch
phase, so a set-size mismatch at a LATER height is reported before any
structural or signature error at an earlier one. Chains that a sequential
loop accepts are accepted with identical side effects.
"""

from __future__ import annotations

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.light import verifier as lv
from tendermint_tpu.types.light_block import LightBlock
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator_set import (
    ErrNotEnoughVotingPowerSigned,
    ErrWrongSignature,
)


class RangeVerifyError(lv.LightClientError):
    def __init__(self, height: int, reason: Exception | str):
        self.height = height
        self.reason = reason
        super().__init__(f"header range verification failed at height {height}: {reason}")


def verify_header_range(trusted: LightBlock, chain: list[LightBlock],
                        trusting_period_s: float, now: Time,
                        max_clock_drift_s: float = 10.0,
                        store=None) -> None:
    """Verify `chain` (ascending, adjacent heights) against `trusted`.

    Raises RangeVerifyError naming the failing height (see module docstring
    for the error-ordering caveat vs a sequential loop). When `store` is
    given, every verified block is saved into it.
    """
    if not chain:
        return
    # Hash every header in the range as one batched merkle forest before
    # the serial replay walks them (types/block.py precompute_header_hashes).
    from tendermint_tpu.types.block import precompute_header_hashes

    precompute_header_hashes(
        [lb.signed_header.header for lb in chain
         if lb.signed_header and lb.signed_header.header])
    # Phase 1 (DISPATCH): collect signature items and dispatch them in
    # chunks as early as possible: results dispatched now compute and travel
    # home (copy_to_host_async in ops dispatch) while phase 2 validates
    # structure on host.  EVERY chunk,
    # including the sub-crossover tail, is dispatched with
    # force_device=use_device, so once the range is device-sized the tail
    # flies with the other chunks instead of burning synchronous host CPU.
    from tendermint_tpu.ops import ed25519_batch as _edb

    # Split into EVEN device chunks of ~2,500 signatures: smaller chunks
    # dispatch earlier and overlap more of the device flight; much smaller
    # ones just multiply per-dispatch host overhead.
    # Chunks are FORCED onto the device path — a sub-crossover chunk would
    # otherwise run on host CPU synchronously (15 us/sig of 1-core time
    # that overlaps nothing) while a device flight is free. Ranges whose
    # whole signature count sits below the crossover stay one host flush.
    # Each chunk dispatch lands on the continuous-batching verify service
    # (crypto/verify_service.py): chunks queued within its coalescing
    # window share ONE kernel launch (and its sync floor) with each other
    # and with any concurrent drain/fast-sync traffic, which also removes
    # the per-chunk launch jitter behind the r05 spread (ISSUE 11
    # satellite 1) — the executor, not this caller, owns launch cadence
    # and the single batched readback.
    crossover = _edb.host_crossover()
    est_per = max(1, (2 * chain[0].validator_set.size()) // 3 + 1)
    est_total = est_per * len(chain)
    use_device = est_total > crossover
    k = max(1, round(est_total / 2500)) if use_device else 1
    chunk_sigs_target = (-(-est_total // k)) if k > 1 else est_total + 1
    verifier = crypto_batch.create_batch_verifier()
    plan = []  # (lb, prefix, needed)
    pending = []  # (plan_chunk, PendingVerify)
    for lb in chain:
        sh, vals = lb.signed_header, lb.validator_set
        commit = sh.commit
        if vals.size() != len(commit.signatures):
            # full structural pass runs in phase 2; this one gates the
            # prefix computation itself
            raise RangeVerifyError(
                sh.height, f"wrong set size: {vals.size()} vs {len(commit.signatures)}")
        needed = vals.total_voting_power() * 2 // 3
        prefix = vals.commit_light_prefix(commit, needed)
        vals.add_commit_sigs(verifier, sh.header.chain_id, commit, prefix, prefix)
        plan.append((lb, prefix, needed))
        if len(verifier) >= chunk_sigs_target:
            pending.append((plan, verifier.dispatch(force_device=use_device)))
            verifier = crypto_batch.create_batch_verifier()
            plan = []
    if plan:
        pending.append((plan, verifier.dispatch(force_device=use_device)))

    # Phase 2 (STRUCTURE, overlapping the signature flights): the serial
    # chain-linkage walk.  Same accept/reject set as the sequential loop;
    # the module docstring's error-ordering caveat (structural defects
    # reported before an earlier height's bad signature) already covers
    # this ordering.
    prev = trusted
    for lb in chain:
        sh, vals = lb.signed_header, lb.validator_set
        if sh.height != prev.height + 1:
            raise RangeVerifyError(sh.height, "headers must be adjacent in height")
        if lv.header_expired(prev.signed_header, trusting_period_s, now):
            raise RangeVerifyError(
                sh.height, lv.ErrOldHeaderExpired(
                    Time.from_unix_ns(prev.signed_header.header.time.unix_ns()
                                      + int(trusting_period_s * 1e9)), now))
        try:
            lv._verify_new_header_and_vals(
                sh, vals, prev.signed_header, now, max_clock_drift_s)
        except lv.LightClientError as e:
            raise RangeVerifyError(sh.height, e) from e
        if sh.header.validators_hash != prev.signed_header.header.next_validators_hash:
            raise RangeVerifyError(
                sh.height,
                f"expected old header next validators "
                f"({prev.signed_header.header.next_validators_hash.hex()}) to match "
                f"those from new header ({sh.header.validators_hash.hex()})"
            )
        prev = lb

    # Phase 3: ONE readback for every chunk's flush (crypto_batch.prefetch
    # batches every pending's device outputs into one device_get; most
    # results have already landed).
    crypto_batch.prefetch([pv for (_, pv) in pending])

    # Phase 4: replay each header's serial decision over its bitmap slice.
    for plan_chunk, pv in pending:
        _, bitmap = pv.resolve()
        pos = 0
        for lb, prefix, needed in plan_chunk:
            vals, commit = lb.validator_set, lb.signed_header.commit
            tallied = 0
            ok_height = False
            for idx, ok in zip(prefix, bitmap[pos:pos + len(prefix)]):
                if not ok:
                    raise RangeVerifyError(
                        lb.height,
                        ErrWrongSignature(idx, commit.signatures[idx].signature))
                tallied += vals.validators[idx].voting_power
                if tallied > needed:
                    ok_height = True
                    break
            pos += len(prefix)
            if not ok_height:
                raise RangeVerifyError(
                    lb.height, ErrNotEnoughVotingPowerSigned(tallied, needed))
            if store is not None:
                store.save_light_block(lb)
