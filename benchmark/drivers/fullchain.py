"""The chain the ``full-sync`` mix plays to a syncing node: a hub whose every
block is full, each one a block that passes the program's own
``validate_block``, all a function of ``--seed`` and the configuration.

``harness/datagen.py`` is not touched: it makes the validator set and its
keys (``Dataset``, the off-curve validator among them), and this file takes
both from it, as ``drivers/churnchain.py`` does (whose pattern of absent and
nil votes, digests and cache layout it imports). The chain is made one height
at a time by a source ``BlockExecutor`` over ``MemDB`` and the in-process
kvstore:

  - every block carries ``txs_per_block`` transactions of exactly
    ``tx_bytes`` bytes, ``<16 hex digits>=<random bytes>``: the key is eight
    bytes of ``derive(seed, "tx-key", height, index)`` in hex (every key of a
    chain distinct: checked when the chain is made), the value the block's own
    stream of random bytes (``numpy`` PCG64 seeded from ``derive(seed,
    "tx-values", height)``). The last block, which only carries the commit for
    the one before and is never applied, is full like the others;
  - the set is static: the genesis validators sign every height, under the
    absent and nil pattern of ``datagen.signer_pattern``'s derivation
    (``pattern_seed``), each validator with its own timestamp; a block's time
    is the weighted median of its LastCommit's, as ``state.make_block``
    computes it;
  - header H+1 names the app hash and ``last_results_hash`` the source
    executor computed for block H, so a node that replays the chain has to
    deliver every transaction to pass ``validate_block`` at the next height.

What is expensive (a set's worth of signatures a height) is signed by
``signing.SignerPool``'s children, which never import jax, and cached under
``benchmark/.data/`` by seed and a digest of ``dataset``; the blocks are made
anew from the cache through the source executor in every run (the
transactions are a function of the seed and are not stored).
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.drivers import churnchain, livechain
from benchmark.harness import datagen, signing

FORMAT = 1
KEY_HEX = 16


@dataclass
class FullChain:
    chain_id: str
    genesis: object                # types.GenesisDoc
    raws: list                     # marshalled blocks, height 1 first; the
    #                                last only carries the commit for the one
    #                                before
    block_ids: list                # [BlockID]
    prefix_sigs: list              # light-prefix signatures of height k + 1
    sigs: np.ndarray               # (heights, n, 64) uint8: what was signed
    txs_per_block: int
    tx_bytes: int
    final: dict = field(default_factory=dict)   # the source's state at the end
    meta: dict = field(default_factory=dict)

    @property
    def heights(self) -> int:
        """Appliable heights: every block but the last."""
        return len(self.raws) - 1


def block_txs(seed: int, height: int, count: int, size: int) -> list[bytes]:
    """The transactions of one block: ``<16 hex digits>=<random bytes>``,
    each exactly ``size`` bytes."""
    if size <= KEY_HEX + 1:
        raise ValueError(f"tx_bytes {size} leaves no room for a value")
    rng = np.random.Generator(np.random.PCG64(
        int.from_bytes(datagen.derive(seed, "tx-values", height)[:16], "big")))
    width = size - KEY_HEX - 1
    values = rng.integers(0, 256, (count, width), np.uint8)
    return [datagen.derive(seed, "tx-key", height, i)[:KEY_HEX // 2].hex()
            .encode() + b"=" + values[i].tobytes() for i in range(count)]


def _assemble(ds, cfg: dict, seed: int, sign) -> FullChain:
    """Blocks from a source of signatures. ``sign(k, jobs) -> [signature]``:
    jobs are (slot, key, sign bytes) of chain height k + 1, in order."""
    from tendermint_tpu.state.state import make_genesis_state
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL

    d = cfg["dataset"]
    n_blocks, count, size = d["chain_heights"], d["txs_per_block"], d["tx_bytes"]
    genesis = livechain._genesis(ds)
    state = make_genesis_state(genesis)
    block_exec = livechain._executor()
    block_exec.store.save(state)
    vals = state.validators
    keys = [v.pub_key.bytes() for v in vals.validators]
    powers = np.array([v.voting_power for v in vals.validators], np.int64)
    chain = FullChain(
        chain_id=ds.chain_id, genesis=genesis, raws=[], block_ids=[],
        prefix_sigs=[], txs_per_block=count, tx_bytes=size,
        sigs=np.zeros((n_blocks - 1, vals.size(), 64), np.uint8))
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    seen_keys, redraws, data_bytes = set(), 0, 0
    for k in range(n_blocks):
        h = k + 1
        txs = block_txs(seed, h, count, size)
        seen_keys.update(tx[:KEY_HEX] for tx in txs)
        block = state.make_block(h, txs, last_commit, [],
                                 state.validators.get_proposer().address)
        raw = block.marshal()
        parts = PartSet.from_data(raw)
        block_id = BlockID(hash=block.hash(), part_set_header=parts.header())
        chain.raws.append(raw)
        chain.block_ids.append(block_id)
        data_bytes += len(block.data.marshal())
        chain.meta["parts"] = parts.count
        if h == n_blocks:
            break         # the last block is never applied, nor signed
        absent, nil, again = churnchain._pattern(
            d.get("pattern_seed", seed), powers, ds.off_idx,
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
    if len(seen_keys) != n_blocks * count:
        raise ValueError(f"{n_blocks * count - len(seen_keys)} transaction "
                         f"keys of seed {seed} collide")
    chain.final = {"app_hash": state.app_hash,
                   "last_results_hash": state.last_results_hash}
    chain.meta.update(redraws=redraws, block_bytes=len(chain.raws[0]),
                      data_bytes_a_block=data_bytes // n_blocks,
                      chain_bytes=sum(map(len, chain.raws)))
    return chain


def load_or_generate(name: str, ds, cfg: dict, seed: int,
                     data_dir: str = datagen.DATA_DIR,
                     workers: int | None = None,
                     openssl: bool | None = None) -> FullChain:
    """The chain of this seed: its signatures from the cache when they were
    made in this checkout before (same ``dataset`` parameters), else signed
    and stored. ``meta`` says which and how long it took."""
    t0 = time.monotonic()
    path = os.path.join(data_dir, f"{name}-full-{seed}.npz")
    want = {"format": FORMAT, "config": churnchain._config_digest(cfg),
            "seed": seed}
    secrets = dict(zip((v.pub_key.bytes() for v in ds.vals.validators),
                       livechain._secret_of(ds, seed)))
    chain = None
    if os.path.exists(path):
        with np.load(path) as z:
            stored = json.loads(str(z["meta"]))
            if {k: stored.get(k) for k in want} == want:
                sigs = z["sigs"]
                chain = _assemble(
                    ds, cfg, seed,
                    lambda k, jobs: [sigs[k, slot].tobytes()
                                     for slot, _key, _msg in jobs])
                chain.meta.update({k: stored[k] for k in want}, cached=True)
    if chain is None:
        with signing.SignerPool(workers, openssl) as pool:
            chain = _assemble(
                ds, cfg, seed,
                lambda _k, jobs: pool.sign(signing.ED25519, [
                    (secrets[key], key, msg, b"") for _slot, key, msg in jobs]))
            chain.meta.update(want, cached=False, workers=pool.workers,
                              ed25519_signer="openssl" if pool.openssl
                              else "benchmark/reference")
        os.makedirs(data_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"   # two runs may share a seed
        np.savez(tmp, sigs=chain.sigs,
                 meta=json.dumps({**chain.meta,
                                  "digest": churnchain.content_digest(chain)}))
        os.replace(tmp, path)
    chain.meta["path"] = path
    chain.meta["seconds"] = time.monotonic() - t0
    return chain
