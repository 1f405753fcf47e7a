"""The chain the ``churn-sync`` mix plays to a syncing node: a hub whose
validator set changes, every block one that passes the program's own
``validate_block``, all a function of ``--seed`` and the configuration.

``harness/datagen.py`` is not touched: it still makes the genesis set and
its keys (``Dataset``, the off-curve validator among them); this file takes
both from it, as ``drivers/livechain.py`` does. The chain is made one height
at a time by a source ``BlockExecutor`` over the in-process kvstore:

  - block H (H a multiple of ``update_every``, from ``update_every`` to the
    last but one appliable height) carries ``val:<base64 key>!<power>``
    transactions and nothing else; the others carry none. The kinds
    alternate as ``update_kinds`` says. **join**: a validator whose key the
    chain has never held (its secret is ``derive(seed, "joiner", k)``)
    enters with the power the law gives a drawn rank, and the sitting
    validator at the last place of the set (least power) leaves in the same
    block, so the set stays at its size. **reweight**: ``reweight_validators``
    sitting validators, drawn by their place in the set, each take the law's
    power of another drawn rank. The draws come from ``pattern_seed`` where
    the configuration fixes one, so every ``--seed`` makes the same changes at
    the same places of the set (other keys, so other addresses: ties of
    power fall as the addresses do);
  - the updates reach the set only through the executor: EndBlock,
    ``update_state``, in force at H+2. This file keeps no set of its own;
  - every height is signed by ``state.validators`` of that height, under
    the absent and nil pattern of ``datagen.signer_pattern``'s derivation
    (the off-curve validator absent for as long as it sits in the set), each
    validator with its own timestamp; a block's time is the weighted median
    of its LastCommit's, as ``state.make_block`` computes it.

What is expensive (a set's worth of signatures a height) is signed by
``signing.SignerPool``'s children, which never import jax, and cached under
``benchmark/.data/`` by seed and a digest of ``dataset``; the program objects
are rebuilt from the cache through the source executor in every run.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.drivers import livechain
from benchmark.harness import datagen, signing

FORMAT = 1


@dataclass
class ChurnChain:
    chain_id: str
    genesis: object                # types.GenesisDoc
    blocks: list                   # [Block], height 1 first; the last only
    #                                carries the commit for the one before
    raws: list                     # their marshalled bytes
    block_ids: list                # [BlockID]
    updates: dict                  # height -> [(key, power)] its block carries
    prefix_sigs: list              # light-prefix signatures of height k + 1
    secrets: dict                  # key -> ed25519 seed, sitting or joined
    sigs: np.ndarray               # (heights - 1, n, 64) uint8: what was signed
    final: dict = field(default_factory=dict)   # the source's state at the end
    meta: dict = field(default_factory=dict)

    @property
    def heights(self) -> int:
        """Appliable heights: every block but the last."""
        return len(self.blocks) - 1


def update_heights(d: dict) -> range:
    """The heights whose blocks carry updates: each in force, two heights
    on, at a height a pass still applies."""
    return range(d["update_every"], d["chain_heights"] - 1, d["update_every"])


def _pattern(seed: int, powers: np.ndarray, off_idx, absent_share: float,
             nil_share: float, height: int):
    """``datagen.signer_pattern`` for a set the off-curve validator may have
    left: the same derivation, the same redraw rule."""
    if off_idx is not None:
        return datagen.signer_pattern(seed, powers, off_idx, absent_share,
                                      nil_share, "height", height)
    n, needed = len(powers), int(powers.sum()) * 2 // 3
    for counter in range(1 << 16):
        absent = datagen.bernoulli(seed, n, absent_share, "absent", "height",
                                   height, counter)
        nil = datagen.bernoulli(seed, n, nil_share, "nil", "height", height,
                                counter) & ~absent
        if int(powers[~absent & ~nil].sum()) > needed:
            return absent, nil, counter
    raise ValueError(f"no draw of height {height} reaches +2/3")


def planned_updates(d: dict, seed: int, k: int, sitting: list, joiner: bytes):
    """The k-th update (1-based) -> [(key, power)]. ``sitting``: the keys of
    the set the update applies to, in the set's order; ``joiner``: the key
    that enters if this is a join."""
    draw = d.get("pattern_seed", seed)
    n = len(sitting)
    law = datagen.voting_powers(d["voting_power"], n)   # power by rank
    kind = d["update_kinds"][(k - 1) % len(d["update_kinds"])]
    if kind == "join":
        rank = datagen.pick(draw, n, "join-rank", k)
        return [(joiner, law[rank]), (sitting[-1], 0)]
    if kind == "reweight":
        places = list(range(n))
        out = []
        for j in range(d["reweight_validators"]):
            place = places.pop(datagen.pick(draw, len(places), "reweight", k, j))
            out.append((sitting[place],
                        law[datagen.pick(draw, n, "reweight-rank", k, j)]))
        return out
    raise ValueError(f"update kind {kind!r}")


def _assemble(ds, cfg: dict, seed: int, secrets: dict, joiners: list,
              sign) -> ChurnChain:
    """Program objects from a source of signatures. ``secrets``: key -> seed
    of every validator, sitting or to join; ``joiners``: the key the k-th
    update brings in if it is a join; ``sign(k, jobs) -> [signature]``: jobs
    are (slot, key, sign bytes) of chain height k + 1, answered in order."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.state.state import make_genesis_state
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL

    d = cfg["dataset"]
    n_blocks = d["chain_heights"]
    genesis = livechain._genesis(ds)
    off_key = ds.vals.validators[ds.off_idx].pub_key.bytes()
    update_at = {h: k for k, h in enumerate(update_heights(d), 1)}
    state = make_genesis_state(genesis)
    block_exec = livechain._executor()
    block_exec.store.save(state)
    chain = ChurnChain(
        chain_id=ds.chain_id, genesis=genesis, blocks=[], raws=[],
        block_ids=[], updates={}, prefix_sigs=[], secrets=secrets,
        sigs=np.zeros((n_blocks - 1, ds.vals.size(), 64), np.uint8))
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    redraws = 0
    for k in range(n_blocks):
        h = k + 1
        txs = []
        if h in update_at:
            # EndBlock's updates go onto the next set: draw by its places
            sitting = [v.pub_key.bytes()
                       for v in state.next_validators.validators]
            chain.updates[h] = planned_updates(
                d, seed, update_at[h], sitting, joiners[update_at[h] - 1])
            txs = [KVStoreApplication.make_val_tx(key, power)
                   for key, power in chain.updates[h]]
        block = state.make_block(h, txs, last_commit, [],
                                 state.validators.get_proposer().address)
        raw = block.marshal()
        block_id = BlockID(hash=block.hash(),
                           part_set_header=PartSet.from_data(raw).header())
        chain.blocks.append(block)
        chain.raws.append(raw)
        chain.block_ids.append(block_id)
        if h == n_blocks:
            break         # the last block is never applied, nor signed
        vals = state.validators
        keys = [v.pub_key.bytes() for v in vals.validators]
        powers = np.array([v.voting_power for v in vals.validators], np.int64)
        absent, nil, again = _pattern(
            d.get("pattern_seed", seed), powers,
            keys.index(off_key) if off_key in keys else None,
            d["absent_share"], d["nil_share"], h)
        redraws += again
        commit = Commit(height=h, round=0, block_id=block_id, signatures=[
            CommitSig.new_absent() if absent[i] else CommitSig(
                BLOCK_ID_FLAG_NIL if nil[i] else BLOCK_ID_FLAG_COMMIT,
                v.address, datagen._timestamp(seed, h, i), b"")
            for i, v in enumerate(vals.validators)])
        slots = [int(i) for i in np.flatnonzero(~absent)]
        signed = sign(k, [(i, keys[i], commit.vote_sign_bytes(ds.chain_id, i))
                          for i in slots])
        for i, sig in zip(slots, signed):
            commit.signatures[i].signature = sig
            chain.sigs[k, i] = np.frombuffer(sig, np.uint8)
        chain.prefix_sigs.append(len(vals.commit_light_prefix(
            commit, vals.total_voting_power() * 2 // 3)))
        last_commit = commit
        state, _retain = block_exec.apply_block(state, block_id, block)
    block_exec.stop()
    chain.final = {"app_hash": state.app_hash,
                   "validators_hash": state.validators.hash(),
                   "next_validators_hash": state.next_validators.hash()}
    chain.meta["redraws"] = redraws
    return chain


def _keys(ds, d: dict, seed: int):
    """-> (key -> ed25519 seed of every validator of the chain, [the key the
    k-th update brings in if it is a join]). A joiner's secret is
    ``derive(seed, "joiner", k)``; a reweight's k keeps its key unused."""
    joiner_secrets = [datagen.derive(seed, "joiner", k)
                      for k in range(1, len(update_heights(d)) + 1)]
    joiners = signing.public_keys(signing.ED25519, signing.have_openssl(),
                                  joiner_secrets)
    secrets = {v.pub_key.bytes(): s for v, s in
               zip(ds.vals.validators, livechain._secret_of(ds, seed))}
    secrets.update(zip(joiners, joiner_secrets))
    return secrets, joiners


def _config_digest(cfg: dict) -> str:
    return hashlib.sha256(json.dumps(cfg["dataset"], sort_keys=True)
                          .encode()).hexdigest()


def content_digest(chain: ChurnChain) -> str:
    """What "the same chain" means: every signature byte and the last
    block's hash, which covers every block, transaction and set before it."""
    return hashlib.sha256(chain.sigs.tobytes()
                          + chain.block_ids[-1].hash).hexdigest()


def load_or_generate(name: str, ds, cfg: dict, seed: int,
                     data_dir: str = datagen.DATA_DIR,
                     workers: int | None = None,
                     openssl: bool | None = None) -> ChurnChain:
    """The chain of this seed: from the cache when it was signed in this
    checkout before (same ``dataset`` parameters), else signed and stored.
    ``meta`` says which and how long it took."""
    t0 = time.monotonic()
    d = cfg["dataset"]
    path = os.path.join(data_dir, f"{name}-churn-{seed}.npz")
    want = {"format": FORMAT, "config": _config_digest(cfg), "seed": seed}
    secrets, joiners = _keys(ds, d, seed)
    chain = None
    if os.path.exists(path):
        with np.load(path) as z:
            stored = json.loads(str(z["meta"]))
            if {k: stored.get(k) for k in want} == want:
                sigs = z["sigs"]
                chain = _assemble(
                    ds, cfg, seed, secrets, joiners,
                    lambda k, jobs: [sigs[k, slot].tobytes()
                                     for slot, _key, _msg in jobs])
                chain.meta.update(stored, cached=True)
    if chain is None:
        with signing.SignerPool(workers, openssl) as pool:
            chain = _assemble(
                ds, cfg, seed, secrets, joiners,
                lambda _k, jobs: pool.sign(signing.ED25519, [
                    (secrets[key], key, msg, b"") for _slot, key, msg in jobs]))
            chain.meta.update(want, cached=False, workers=pool.workers,
                              ed25519_signer="openssl" if pool.openssl
                              else "benchmark/reference")
        os.makedirs(data_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"   # two runs may share a seed
        np.savez(tmp, sigs=chain.sigs,
                 meta=json.dumps({**chain.meta,
                                  "digest": content_digest(chain)}))
        os.replace(tmp, path)
    chain.meta["path"] = path
    chain.meta["seconds"] = time.monotonic() - t0
    return chain
