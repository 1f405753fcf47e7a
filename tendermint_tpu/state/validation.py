"""Block validation against state (reference: state/validation.go:15-151)."""

from __future__ import annotations

import time

from tendermint_tpu.state.state import State
from tendermint_tpu.types.block import Block
from tendermint_tpu.types.ttime import Time


class BlockValidationError(Exception):
    pass


def validate_block(state: State, block: Block, block_store=None,
                   commit_pending=None, tr=None) -> None:
    """reference: state/validation.go:15. Includes the batched
    LastValidators.VerifyCommit at the same point the reference does (line 93),
    which on TPU is one kernel launch instead of N serial verifies.

    `commit_pending` (a resolvable handle from
    BlockExecutor.dispatch_commit_verify, already stale-checked by the
    caller) replaces the synchronous verify with a resolve of the
    already-dispatched device work — the commit→apply overlap seam
    (docs/EXECUTION.md). Resolution replays the exact serial accept/reject
    decision, so accept/reject and error attribution are unchanged.

    Under a tracer (`tr`) the block time's weighted median is timed into
    the caller's open span, tag `median_s`, and so is the LastCommit: tags
    `last_commit` (`pending`: the handle was resolved, `sync`: verify_commit
    ran here, `none`: the initial block), `last_commit_s` (the seconds the
    resolve or the verify took: what of the device round trip stayed
    exposed) and `sigs` (the commit's slots that are not Absent)."""
    block.validate_basic()

    h = block.header
    if h.version != state.version:
        raise BlockValidationError(
            f"wrong Block.Header.Version. Expected {state.version}, got {h.version}"
        )
    if h.chain_id != state.chain_id:
        raise BlockValidationError(
            f"wrong Block.Header.ChainID. Expected {state.chain_id}, got {h.chain_id}"
        )
    if state.last_block_height == 0 and h.height != state.initial_height:
        raise BlockValidationError(
            f"wrong Block.Header.Height. Expected {state.initial_height} (initial height), got {h.height}"
        )
    if state.last_block_height > 0 and h.height != state.last_block_height + 1:
        raise BlockValidationError(
            f"wrong Block.Header.Height. Expected {state.last_block_height + 1}, got {h.height}"
        )
    if h.last_block_id != state.last_block_id:
        raise BlockValidationError(
            f"wrong Block.Header.LastBlockID. Expected {state.last_block_id}, got {h.last_block_id}"
        )
    if h.app_hash != state.app_hash:
        raise BlockValidationError(
            f"wrong Block.Header.AppHash. Expected {state.app_hash.hex().upper()}, got {h.app_hash.hex().upper()}"
        )
    if h.consensus_hash != state.consensus_params.hash():
        raise BlockValidationError("wrong Block.Header.ConsensusHash")
    if h.last_results_hash != state.last_results_hash:
        raise BlockValidationError("wrong Block.Header.LastResultsHash")
    if h.validators_hash != state.validators.hash():
        raise BlockValidationError(
            f"wrong Block.Header.ValidatorsHash. Expected {state.validators.hash().hex().upper()}, "
            f"got {h.validators_hash.hex().upper()}"
        )
    if h.next_validators_hash != state.next_validators.hash():
        raise BlockValidationError("wrong Block.Header.NextValidatorsHash")

    # LastCommit
    if block.header.height == state.initial_height:
        if block.last_commit is not None and len(block.last_commit.signatures) != 0:
            raise BlockValidationError("initial block can't have LastCommit signatures")
        if tr is not None:
            tr.annotate(last_commit="none")
    else:
        t0 = time.perf_counter()
        try:
            if commit_pending is not None:
                # dispatched earlier (overlapped with store save / WAL
                # fsync); resolve() is idempotent and raises exactly what
                # the synchronous verify would
                commit_pending.resolve()
            else:
                # THE hot call (reference: state/validation.go:93): one
                # batched kernel.
                state.last_validators.verify_commit(
                    state.chain_id, state.last_block_id,
                    block.header.height - 1, block.last_commit)
        finally:
            if tr is not None and block.last_commit is not None:
                tr.annotate(
                    last_commit="sync" if commit_pending is None else "pending",
                    last_commit_s=time.perf_counter() - t0,
                    sigs=sum(1 for cs in block.last_commit.signatures
                             if not cs.absent()))

    # proposer must be in the current validator set
    if not state.validators.has_address(h.proposer_address):
        raise BlockValidationError(
            f"block.Header.ProposerAddress {h.proposer_address.hex().upper()} is not a validator"
        )

    # time validation (reference: state/validation.go:118-145)
    if block.header.height > state.initial_height:
        if not block.header.time > state.last_block_time:
            raise BlockValidationError(
                f"block time {block.header.time} not greater than last block time {state.last_block_time}"
            )
        if block.last_commit is not None and len(state.last_validators.validators) > 0:
            t0 = time.perf_counter()
            median = median_time(block.last_commit, state.last_validators)
            if tr is not None:
                tr.annotate(median_s=time.perf_counter() - t0)
            if block.header.time != median:
                raise BlockValidationError(
                    f"invalid block time. Expected {median}, got {block.header.time}"
                )
    elif block.header.height == state.initial_height:
        if block.header.time < state.last_block_time:
            raise BlockValidationError("block time is earlier than genesis time")


def median_time(commit, validators) -> Time:
    """Weighted median of commit timestamps (reference: types/validator_set.go
    / state MedianTime via types/time.WeightedMedian)."""
    weighted: list[tuple[Time, int]] = []
    for i, cs in enumerate(commit.signatures):
        if cs.absent():
            continue
        _, val = validators.get_by_address(cs.validator_address)
        if val is not None:
            weighted.append((cs.timestamp, val.voting_power))
    if not weighted:
        return Time.zero()
    weighted.sort(key=lambda tv: (tv[0].seconds, tv[0].nanos))
    total = sum(w for _, w in weighted)
    median = total // 2
    acc = 0
    for t, w in weighted:
        acc += w
        if acc > median:
            return t
    return weighted[-1][0]
