"""The chain the ``mixed-sync`` mix plays to a syncing node: BASELINE config
4's 1,000 validators of two key types on a chain whose every block passes the
program's own ``validate_block``, all a function of ``--seed`` and the
configuration.

``drivers/fullchain.py`` makes the blocks (``fullchain._assemble``: a source
``BlockExecutor`` over ``MemDB`` and the in-process kvstore, the absent and
nil pattern of ``datagen.signer_pattern``'s derivation drawn per chain
height, each validator's own timestamp, a block's time the weighted median
of its LastCommit's) and is not touched; its ``load_or_generate`` signs with
one key type, so this file is the signer for two. ``harness/datagen.py``
makes the set and its keys as it does for ``fastsync-1k-mixed``: the same
seed gives the same 700 ed25519 and 300 sr25519 keys in both
configurations.

  - ``Data`` is empty (config 4 states no transactions): ``txs_per_block``
    is 0 and the width ``fullchain.block_txs`` asks for is never used;
  - an ed25519 validator signs through ``signing.SignerPool`` (OpenSSL where
    it imports), an sr25519 one through the benchmark's pure-Python
    schnorrkel signer, its witness randomness ``derive(seed, "sr-chain-rng",
    height, slot)``: the chain is a function of the seed;
  - the signatures (a set's worth a height, ~3 ms each for sr25519) are
    signed by the pool's children, which never import jax, and cached under
    ``benchmark/.data/`` by seed and a digest of ``dataset``; the blocks are
    made anew from the cache through the source executor in every run.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark.drivers import churnchain, fullchain
from benchmark.harness import datagen, signing

FORMAT = 1
UNUSED_TX_BYTES = fullchain.KEY_HEX + 2     # no transaction is ever made


def secrets_of(ds, cfg: dict, seed: int) -> dict:
    """Public key -> (key type, the secret the generator derived it from),
    for every validator of the set (``datagen.generate``: the ed25519 keys
    first, then the sr25519 ones, each kind counted from 0). The off-curve
    validator's registered bytes are no key and map to the secret of the key
    it should have had; it is absent from every commit and never signs."""
    n_ed = cfg["dataset"]["validators"]["ed25519"]
    out = {}
    for k, pub in enumerate(ds.pubs):
        kind = signing.ED25519 if k < n_ed else signing.SR25519
        out[bytes(pub)] = (kind, datagen.derive(
            seed, "val", kind, k if k < n_ed else k - n_ed))
    return out


def _signer(pool: signing.SignerPool, secrets: dict, seed: int):
    """``fullchain._assemble``'s ``sign(k, jobs)`` over both key types: the
    jobs of a height split by the key's type, each kind signed in one fan-out,
    the signatures put back in the jobs' order."""
    def sign(k, jobs):
        out = [None] * len(jobs)
        by_kind = {signing.ED25519: [], signing.SR25519: []}
        for at, (slot, key, msg) in enumerate(jobs):
            kind, secret = secrets[key]
            by_kind[kind].append((at, (secret, key, msg, datagen.derive(
                seed, "sr-chain-rng", k + 1, slot))))
        for kind, rows in by_kind.items():
            if rows:
                for (at, _job), sig in zip(
                        rows, pool.sign(kind, [job for _at, job in rows])):
                    out[at] = sig
        return out
    return sign


def key_types(chain) -> list[str]:
    """The key type of every slot of the (static) set, in the set's order."""
    return [v.pub_key.type for v in chain.genesis.validators]


def load_or_generate(name: str, ds, cfg: dict, seed: int,
                     data_dir: str = datagen.DATA_DIR,
                     workers: int | None = None,
                     openssl: bool | None = None) -> fullchain.FullChain:
    """The chain of this seed: its signatures from the cache when they were
    made in this checkout before (same ``dataset`` parameters), else signed
    and stored. ``meta`` says which, how long it took and how many signatures
    of each key type a run that signs makes."""
    t0 = time.monotonic()
    cfg = {**cfg, "dataset": {"tx_bytes": UNUSED_TX_BYTES, **cfg["dataset"]}}
    path = os.path.join(data_dir, f"{name}-mixed-{seed}.npz")
    want = {"format": FORMAT, "config": churnchain._config_digest(cfg),
            "seed": seed}
    chain = None
    if os.path.exists(path):
        with np.load(path) as z:
            stored = json.loads(str(z["meta"]))
            if {k: stored.get(k) for k in want} == want:
                sigs = z["sigs"]
                chain = fullchain._assemble(
                    ds, cfg, seed,
                    lambda k, jobs: [sigs[k, slot].tobytes()
                                     for slot, _key, _msg in jobs])
                chain.meta.update({k: stored[k] for k in want}, cached=True)
    if chain is None:
        with signing.SignerPool(workers, openssl) as pool:
            chain = fullchain._assemble(
                ds, cfg, seed, _signer(pool, secrets_of(ds, cfg, seed), seed))
            chain.meta.update(want, cached=False, workers=pool.workers,
                              ed25519_signer="openssl" if pool.openssl
                              else "benchmark/reference")
        os.makedirs(data_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"   # two runs may share a seed
        np.savez(tmp, sigs=chain.sigs,
                 meta=json.dumps({**chain.meta,
                                  "digest": churnchain.content_digest(chain)}))
        os.replace(tmp, path)
    kinds = np.array(key_types(chain))
    signed = chain.sigs.any(axis=2)                    # (heights, n)
    chain.meta["signatures"] = {
        kind: int(signed[:, kinds == kind].sum())
        for kind in (signing.ED25519, signing.SR25519)}
    chain.meta["path"] = path
    chain.meta["seconds"] = time.monotonic() - t0
    return chain
