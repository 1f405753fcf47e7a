"""The live chain the ``vote-drain`` mix plays to a node: for every height a
block that passes the program's own ``validate_block``, the proposer's signed
proposal, and the prevote and the precommit of every validator who votes at
that height, all a function of ``--seed`` and the configuration.

``harness/datagen.py`` is not touched: it still makes the validator set and
the keys (``Dataset``); this file takes both from it. What is expensive
(5,000 x 2 signatures a height) is signed by ``signing.SignerPool``'s
children, which never import jax, and cached under ``benchmark/.data/`` like
the rest; the program objects are rebuilt from the cache in every run.

The chain is made one height at a time, because block h + 1 carries the
precommits of h as its LastCommit and takes its time from them:
  - who is absent and who votes nil at a height is ``datagen.signer_pattern``
    over the configuration's ``absent_share`` / ``nil_share`` (an absent
    validator's prevote and precommit never exist, a nil one's are votes for
    nil); the off-curve validator is always absent;
  - the measured node's own validator has no vote here: the node signs its
    own, with its own clock, and no peer delivers them. The proposer of h + 1
    has not seen the node's precommit either: its slot is Absent in every
    LastCommit, which keeps +2/3 by a wide margin;
  - the state (app hash, results hash, validator hashes, proposer rotation)
    advances through a ``BlockExecutor`` over the in-process kvstore of the
    generator's own, one block at a time: the chain is its own
    one-block-at-a-time replay, and ``Height.app_hash`` is what a node that
    commits these blocks must hold after each.

With equal power the proposer of height h is the validator in slot h - 1 of
the set. The off-curve validator cannot sign a proposal, and the measured
node must not propose (its block would carry its own clock), so the
proposer priorities of the genesis state are advanced by ``rotate`` steps,
the fewest that keep both out of the window's proposers: 0 for all but
``heights / validators`` of the seeds. The hashes of a block do not cover
priorities, so the chain is valid from that state as from any other.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.harness import datagen, signing

FORMAT = 1
PREVOTE, PRECOMMIT = 1, 2


@dataclass
class Height:
    height: int
    block: object                 # types.Block
    parts: object                 # types.PartSet
    block_id: object              # types.BlockID
    proposal: object              # types.Proposal, signed by the proposer
    proposer: int                 # its slot in the set
    votes: dict                   # PREVOTE / PRECOMMIT -> [Vote or None] by slot
    app_hash: bytes               # the app hash after this block


@dataclass
class LiveChain:
    chain_id: str
    genesis: object               # types.GenesisDoc
    rotate: int                   # proposer-priority steps before height 1
    node_slot: int                # the measured node's validator
    node_secret: bytes            # its ed25519 seed
    heights: list                 # [Height], height 1 first
    sigs: np.ndarray              # (heights, 2, n, 64) uint8: what was signed
    proposal_sigs: np.ndarray     # (heights, 64) uint8
    meta: dict = field(default_factory=dict)


def genesis_state(chain: "LiveChain"):
    """The state a node of this chain starts from: the genesis state with the
    proposer priorities advanced by ``chain.rotate`` steps."""
    from tendermint_tpu.state.state import make_genesis_state

    state = make_genesis_state(chain.genesis)
    if chain.rotate:
        state.validators = state.validators.copy_increment_proposer_priority(
            chain.rotate)
        state.next_validators = state.validators.copy_increment_proposer_priority(1)
    return state


def _window_slots(n: int, rotate: int, heights: int) -> set:
    """Slots that propose in heights 1..heights (round 0), and one more."""
    return {(rotate + h) % n for h in range(heights + 1)}


def plan(ds, seed: int, heights: int) -> tuple[int, int]:
    """-> (rotate, node slot): see the module docstring."""
    n = ds.vals.size()
    rotate = 0
    while ds.off_idx in _window_slots(n, rotate, heights):
        rotate += 1
    free = [i for i in range(n)
            if i != ds.off_idx and i not in _window_slots(n, rotate, heights)]
    return rotate, free[datagen.pick(seed, len(free), "live-node-slot")]


def _secret_of(ds, seed: int) -> list[bytes]:
    """Slot of the set -> the ed25519 seed the generator derived its key from."""
    key_of = {bytes(pub): k for k, pub in enumerate(ds.pubs)}
    return [datagen.derive(seed, "val", signing.ED25519,
                           key_of[v.pub_key.bytes()])
            for v in ds.vals.validators]


def _genesis(ds):
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.ttime import Time

    return GenesisDoc(
        chain_id=ds.chain_id,
        genesis_time=Time(datagen.BASE_SECONDS, 0),
        validators=[GenesisValidator(b"", v.pub_key, v.voting_power)
                    for v in ds.vals.validators])


def _executor():
    """A BlockExecutor over stores and a kvstore of the generator's own."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.db import MemDB

    return BlockExecutor(StateStore(MemDB()), KVStoreApplication())


def _assemble(ds, cfg: dict, seed: int, heights: int, sign) -> LiveChain:
    """Program objects from a source of signatures. ``sign(k, jobs) ->
    [signature]``: jobs are (slot or -1 for the proposal, type, sign bytes) of
    chain height k + 1, answered in order."""
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.types.proposal import Proposal
    from tendermint_tpu.types.vote import (
        BLOCK_ID_FLAG_COMMIT,
        BLOCK_ID_FLAG_NIL,
        Vote,
    )

    d = cfg["dataset"]
    vals, n = ds.vals, ds.vals.size()
    rotate, node_slot = plan(ds, seed, heights)
    chain = LiveChain(
        chain_id=ds.chain_id, genesis=_genesis(ds), rotate=rotate,
        node_slot=node_slot, node_secret=_secret_of(ds, seed)[node_slot],
        heights=[], sigs=np.zeros((heights, 2, n, 64), np.uint8),
        proposal_sigs=np.zeros((heights, 64), np.uint8))
    state = genesis_state(chain)
    block_exec = _executor()
    block_exec.store.save(state)
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    redraws = 0
    for k in range(heights):
        h = k + 1
        proposer_slot = (rotate + k) % n
        proposer = state.validators.get_proposer()
        if proposer.address != vals.validators[proposer_slot].address:
            raise AssertionError(f"height {h}: the proposer is not slot "
                                 f"{proposer_slot}")
        block = state.make_block(h, [], last_commit, [], proposer.address)
        block_hash = block.hash()           # fills the header's hashes in
        parts = PartSet.from_data(block.marshal())
        block_id = BlockID(hash=block_hash, part_set_header=parts.header())
        # the node signs its own votes; the others alone must carry +2/3
        # (a rehearsal's small set can miss it: draw again)
        needed = int(ds.powers.sum()) * 2 // 3
        for attempt in range(1 << 16):
            absent, nil, again = datagen.signer_pattern(
                seed, ds.powers, ds.off_idx, d["absent_share"], d["nil_share"],
                "live", h, attempt)
            redraws += again + attempt
            absent[node_slot] = True
            if int(ds.powers[~absent & ~nil].sum()) > needed:
                break
        proposal = Proposal(height=h, round=0, pol_round=-1, block_id=block_id,
                            timestamp=datagen._timestamp(seed, h, proposer_slot))
        jobs = [(-1, 0, proposal.sign_bytes(ds.chain_id))]
        votes = {PREVOTE: [None] * n, PRECOMMIT: [None] * n}
        for type_ in (PREVOTE, PRECOMMIT):
            for i in np.flatnonzero(~absent):
                i = int(i)
                v = Vote(type=type_, height=h, round=0,
                         block_id=BlockID() if nil[i] else block_id,
                         timestamp=datagen._timestamp(seed, h, i),
                         validator_address=vals.validators[i].address,
                         validator_index=i)
                votes[type_][i] = v
                jobs.append((i, type_, v.sign_bytes(ds.chain_id)))
        signed = sign(k, jobs)
        proposal.signature = signed[0]
        chain.proposal_sigs[k] = np.frombuffer(signed[0], np.uint8)
        for (i, type_, _msg), sig in zip(jobs[1:], signed[1:]):
            votes[type_][i].signature = sig
            chain.sigs[k, type_ - 1, i] = np.frombuffer(sig, np.uint8)
        last_commit = Commit(height=h, round=0, block_id=block_id, signatures=[
            CommitSig.new_absent() if v is None else CommitSig(
                BLOCK_ID_FLAG_NIL if v.block_id.is_zero() else BLOCK_ID_FLAG_COMMIT,
                v.validator_address, v.timestamp, v.signature)
            for v in votes[PRECOMMIT]])
        state, _retain = block_exec.apply_block(state, block_id, block)
        chain.heights.append(Height(h, block, parts, block_id, proposal,
                                    proposer_slot, votes, state.app_hash))
    block_exec.stop()
    chain.meta["redraws"] = redraws
    return chain


def _config_digest(cfg: dict, heights: int) -> str:
    return hashlib.sha256(json.dumps([cfg["dataset"], heights], sort_keys=True)
                          .encode()).hexdigest()


def content_digest(chain: LiveChain) -> str:
    """What "the same chain" means: every signature byte, the last block's
    hash (which covers every block before it) and who the node is."""
    last = chain.heights[-1]
    return hashlib.sha256(
        chain.sigs.tobytes() + chain.proposal_sigs.tobytes() + last.block_id.hash
        + last.app_hash + bytes([chain.rotate % 256])
        + chain.node_slot.to_bytes(4, "big")).hexdigest()


def load_or_generate(name: str, ds, cfg: dict, seed: int, heights: int,
                     data_dir: str = datagen.DATA_DIR,
                     workers: int | None = None,
                     openssl: bool | None = None) -> LiveChain:
    """The chain of this seed: from the cache when it was signed in this
    checkout before (same ``dataset`` parameters and length), else signed
    and stored. ``meta`` says which and how long it took."""
    t0 = time.monotonic()
    path = os.path.join(data_dir, f"{name}-live-{seed}.npz")
    want = {"format": FORMAT, "config": _config_digest(cfg, heights),
            "seed": seed}
    secrets = _secret_of(ds, seed)
    pubs = [v.pub_key.bytes() for v in ds.vals.validators]
    chain = None
    if os.path.exists(path):
        with np.load(path) as z:
            stored = json.loads(str(z["meta"]))
            if {k: stored.get(k) for k in want} == want:
                sigs, proposal_sigs = z["sigs"], z["proposal_sigs"]

                def cached(k, jobs):
                    return [proposal_sigs[k].tobytes() if i < 0
                            else sigs[k, type_ - 1, i].tobytes()
                            for i, type_, _msg in jobs]

                chain = _assemble(ds, cfg, seed, heights, cached)
                chain.meta.update(stored, cached=True)
    if chain is None:
        rotate = plan(ds, seed, heights)[0]
        with signing.SignerPool(workers, openssl) as pool:
            # the off-curve validator never votes and never proposes, so
            # every job has a key that can sign
            def sign(k, jobs):
                proposer = (rotate + k) % len(pubs)
                return pool.sign(signing.ED25519, [
                    (secrets[proposer if i < 0 else i],
                     pubs[proposer if i < 0 else i], msg, b"")
                    for i, _type, msg in jobs])

            chain = _assemble(ds, cfg, seed, heights, sign)
            chain.meta.update(want, cached=False, workers=pool.workers,
                              ed25519_signer="openssl" if pool.openssl
                              else "benchmark/reference")
        os.makedirs(data_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"   # two runs may share a seed
        np.savez(tmp, sigs=chain.sigs, proposal_sigs=chain.proposal_sigs,
                 meta=json.dumps({**chain.meta,
                                  "digest": content_digest(chain)}))
        os.replace(tmp, path)
    chain.meta["path"] = path
    chain.meta["seconds"] = time.monotonic() - t0
    return chain
