"""One general data generator: a validator set and a pool of pre-signed
commits (optionally a chain of blocks that carries them), all a function of
``--seed`` and the configuration file's ``dataset`` parameters.

What is generated (the expensive part: keys and signatures) is cached under
``benchmark/.data/<config>-<seed>.npz``; the program objects (ValidatorSet,
Commit, Block) are rebuilt from it in every run. The generators are copies
(PR 22) of ``chip_smoke._derive`` / ``_mixed_valset`` and
``blockchain/replay.signed_commit`` / ``make_chain``, changed in two ways: each
validator signs with its own timestamp, as in a real commit (so the sign
bytes differ lane by lane), and sr25519 witness randomness comes from the
seed.

Since PR 25 the generator also writes the commit a live network writes, from
optional ``dataset`` keys (benchmark/README.md): ``voting_power`` may be a
Zipf law; ``absent_share`` / ``nil_share`` flag each validator Absent or Nil
per commit, so that the signer set differs from commit to commit
(``signer_pattern``, ``presented``); with ``pattern_seed`` who is missing at
which height, the off-curve absentee included, is the same for every
``--seed``, so that the seed changes keys and signatures and not the amount
of work. A configuration without them
generates byte for byte what it did before.

Imports no jax: the structure types of the program do not need it.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.harness import signing
from benchmark.reference import ed25519_ref

DATA_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        ".data")
FORMAT = 1
BASE_SECONDS = 1_700_000_000


def derive(seed: int, *path) -> bytes:
    """32 bytes for this seed and purpose: every key, message, timestamp and
    corruption index of a run comes from here (chip_smoke._derive)."""
    return hashlib.sha256(repr((seed,) + path).encode()).digest()


def pick(seed: int, n: int, *path) -> int:
    return int.from_bytes(derive(seed, *path)[:8], "big") % n


def bernoulli(seed: int, n: int, share: float, *path) -> np.ndarray:
    """(n,) bool, each True with probability ``share`` independently of the
    others: slot i compares four bytes of SHAKE-256(derive(seed, *path))."""
    if not share:
        return np.zeros(n, bool)
    raw = hashlib.shake_256(derive(seed, *path)).digest(4 * n)
    return np.frombuffer(raw, "<u4") < round(share * 2 ** 32)


def voting_powers(spec, n: int) -> list[int]:
    """Power by rank (generation order, 1-based): an integer is equal power,
    ``{"zipf_exponent": s, "top": P}`` gives rank i ``max(1, round(P / i**s))``."""
    if isinstance(spec, dict):
        s, top = spec["zipf_exponent"], spec["top"]
        return [max(1, round(top / i ** s)) for i in range(1, n + 1)]
    return [spec] * n


@dataclass
class Dataset:
    chain_id: str
    vals: object                    # types.ValidatorSet
    commits: list                   # clean commits, one per pooled height
    blocks: list | None             # the chain that carries them, if chained
    off_idx: int                    # the validator whose key is no curve point
    spare_sig: bytes                # a well-formed signature for that slot
    sigs: np.ndarray                # (heights, n, 64) uint8, set order
    pubs: np.ndarray                # (n, 32) uint8, generation order
    powers: np.ndarray = None       # (n,) int64 voting power, set order
    nil: list = None                # per pooled height: (n,) bool, voted nil
    absent_per_decision: float = 0.0  # unchained pools: see ``presented``
    redraws: int = 0                # patterns redrawn for want of +2/3
    meta: dict = field(default_factory=dict)

    def key_type(self, idx: int) -> str:
        return self.vals.validators[idx].pub_key.type


def _secrets(seed: int, kind: str, n: int) -> list[bytes]:
    return [derive(seed, "val", kind, i) for i in range(n)]


def _off_curve_key(seed: int) -> bytes:
    k = 0
    while ed25519_ref._decompress(derive(seed, "offcurve", k)) is not None:
        k += 1
    return derive(seed, "offcurve", k)


def off_curve_key_index(cfg: dict, seed: int) -> int:
    """Which ed25519 key (generation order) is replaced by bytes that are no
    curve point; its validator is absent from every clean commit, so it is
    part of the absence pattern and follows ``pattern_seed`` where the
    configuration fixes one."""
    d = cfg["dataset"]
    return pick(d.get("pattern_seed", seed), d["validators"]["ed25519"],
                "offcurve-slot")


def _pub_key(kind: str, data: bytes):
    from tendermint_tpu.crypto import ed25519, sr25519

    return ed25519.PubKey(data) if kind == signing.ED25519 \
        else sr25519.PubKey(data)


def _validator_set(kinds: list[str], pubs: list[bytes], powers: list[int]):
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    keys = [_pub_key(k, p) for k, p in zip(kinds, pubs)]
    vals = ValidatorSet([Validator.new(pk, power)
                         for pk, power in zip(keys, powers)])
    by_addr = {pk.address(): i for i, pk in enumerate(keys)}
    order = [by_addr[v.address] for v in vals.validators]  # set slot -> key
    return vals, order


def _timestamp(seed: int, height: int, slot: int):
    """Each validator's own clock: the block's second plus a seeded offset
    below one second, distinct lane by lane."""
    from tendermint_tpu.types.ttime import Time

    base = int.from_bytes(derive(seed, "ts", height)[:4], "big")
    return Time(BASE_SECONDS + height, (base + slot * 7919) % 1_000_000_000)


def _unsigned_commit(seed, vals, height, bid, nil, round_=1):
    """Every validator's slot, flagged Nil where ``nil`` says so (its vote
    then signs the nil vote's sign bytes), Commit elsewhere."""
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL

    sigs = [CommitSig(BLOCK_ID_FLAG_NIL if nil[i] else BLOCK_ID_FLAG_COMMIT,
                      v.address, _timestamp(seed, height, i), b"")
            for i, v in enumerate(vals.validators)]
    return Commit(height=height, round=round_, block_id=bid, signatures=sigs)


def _finish_commit(commit, row: np.ndarray, absent: np.ndarray):
    """Fill the signatures in; the validators of ``absent`` (the off-curve
    one always among them) are flagged Absent."""
    from tendermint_tpu.types.block import CommitSig

    raw = row.tobytes()
    for i, cs in enumerate(commit.signatures):
        cs.signature = raw[64 * i: 64 * i + 64]
    for i in np.flatnonzero(absent):
        commit.signatures[i] = CommitSig.new_absent()
    return commit


def signer_pattern(seed: int, powers: np.ndarray, off_idx: int,
                   absent_share: float, nil_share: float, *scope,
                   nil: np.ndarray | None = None):
    """Who is missing from one commit -> (absent, nil, redraws): (n,) bool
    masks in set order. Each validator is absent with probability
    ``absent_share`` and votes nil with ``nil_share``, independently per
    validator and per ``scope`` (a pooled height, a decision of a run), on top
    of the off-curve absentee; ``nil`` given, it is kept (a nil vote has a
    signature of its own, so it is fixed when the height is signed). A draw
    that leaves the block no more than 2/3 of the power is drawn again from
    the next counter of the same derivation, so the pattern stays a function
    of the seed."""
    n = len(powers)
    needed = int(powers.sum()) * 2 // 3
    for counter in range(1 << 16):
        absent = bernoulli(seed, n, absent_share, "absent", *scope, counter)
        absent[off_idx] = True
        voted_nil = (bernoulli(seed, n, nil_share, "nil", *scope, counter)
                     if nil is None else nil) & ~absent
        if int(powers[~absent & ~voted_nil].sum()) > needed:
            return absent, voted_nil, counter
    raise ValueError(f"no draw of {scope} reaches +2/3 at absent_share "
                     f"{absent_share}, nil_share {nil_share}")


def presented(ds: Dataset, seed: int, k: int, *scope):
    """The commit a caller presents for pooled height ``k`` -> (commit,
    absent mask or None). A chained pool's pattern is part of the chain (the
    next block's hash covers it). An unchained pool with ``absent_share`` was
    signed in full once; here a copy loses the absentees of ``scope`` (the
    decision's number in the run), which needs no signing because a signature
    covers only its own vote. So no signer set repeats however long the window,
    and the pooled commit stays as signed."""
    from tendermint_tpu.types.block import Commit, CommitSig

    pooled = ds.commits[k]
    if not ds.absent_per_decision:
        return pooled, None
    absent, _nil, _redraws = signer_pattern(
        seed, ds.powers, ds.off_idx, ds.absent_per_decision, 0.0,
        "decision", *scope, nil=ds.nil[k])
    sigs = list(pooled.signatures)
    for i in np.flatnonzero(absent):
        sigs[i] = CommitSig.new_absent()
    return Commit(height=pooled.height, round=pooled.round,
                  block_id=pooled.block_id, signatures=sigs), absent


def _derived_block_id(seed: int, height: int):
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader

    return BlockID(hash=derive(seed, "block", height),
                   part_set_header=PartSetHeader(
                       total=1, hash=derive(seed, "parts", height)))


def _chain_block(chain_id, vals, height, prev_bid, prev_commit):
    """blockchain/replay.make_chain's block: real part-set block IDs, empty
    Data (BASELINE config 4 states no transactions)."""
    from tendermint_tpu.types.block import Block, Data, Header
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.types.ttime import Time

    header = Header(chain_id=chain_id, height=height,
                    time=Time(BASE_SECONDS + height, 0),
                    last_block_id=prev_bid, validators_hash=vals.hash(),
                    next_validators_hash=vals.hash(),
                    proposer_address=vals.validators[0].address)
    block = Block(header=header, data=Data(), last_commit=prev_commit)
    block_hash = block.hash()      # fills the header's hashes in: before marshal
    parts = PartSet.from_data(block.marshal())
    return block, BlockID(hash=block_hash, part_set_header=parts.header())


def _assemble(cfg: dict, seed: int, pubs: list[bytes], sign_row) -> Dataset:
    """Program objects from public keys and a source of signature rows.
    ``sign_row(k, order, commit) -> (n, 64) uint8`` in set order."""
    from tendermint_tpu.types.block_id import BlockID

    d = cfg["dataset"]
    chain_id = d["chain_id"]
    kinds = ([signing.ED25519] * d["validators"]["ed25519"]
             + [signing.SR25519] * d["validators"]["sr25519"])
    vals, order = _validator_set(kinds, pubs,
                                 voting_powers(d["voting_power"], len(kinds)))
    off_idx = order.index(off_curve_key_index(cfg, seed))
    heights, chained = d["heights"], d["chained_blocks"]
    powers = np.array([v.voting_power for v in vals.validators], np.int64)
    absent_share, nil_share = d.get("absent_share", 0.0), d.get("nil_share", 0.0)
    commits, blocks, rows = [], ([] if chained else None), []
    nils, redraws = [], 0
    prev_commit, prev_bid = None, BlockID()
    for k in range(heights):
        height = k + 1
        if chained:
            block, bid = _chain_block(chain_id, vals, height, prev_bid,
                                      prev_commit)
            blocks.append(block)
        else:
            bid = _derived_block_id(seed, height)
        # chained: the whole pattern belongs to the height. Unchained: only
        # the nil votes do; ``presented`` draws the absentees per decision
        absent, nil, again = signer_pattern(
            d.get("pattern_seed", seed), powers, off_idx,
            absent_share if chained else 0.0, nil_share, "height", height)
        nils.append(nil)
        redraws += again
        commit = _unsigned_commit(seed, vals, height, bid, nil)
        row = sign_row(k, order, commit)
        rows.append(row)
        prev_commit, prev_bid = _finish_commit(commit, row, absent), bid
        commits.append(prev_commit)
    sigs = np.stack(rows)
    # a well-formed signature the off-curve validator can "sign" with in the
    # corrupted commit (chip_smoke: a foreign signature over the same bytes)
    spare = sigs[0, off_idx].tobytes()
    return Dataset(chain_id=chain_id, vals=vals, commits=commits,
                   blocks=blocks, off_idx=off_idx, spare_sig=spare, sigs=sigs,
                   pubs=np.frombuffer(b"".join(pubs), np.uint8).reshape(-1, 32),
                   powers=powers, nil=nils, redraws=redraws,
                   absent_per_decision=0.0 if chained else absent_share)


def generate(cfg: dict, seed: int, pool: signing.SignerPool) -> Dataset:
    d = cfg["dataset"]
    n_ed, n_sr = d["validators"]["ed25519"], d["validators"]["sr25519"]
    ed_secrets = _secrets(seed, signing.ED25519, n_ed)
    sr_secrets = _secrets(seed, signing.SR25519, n_sr)
    secrets = ed_secrets + sr_secrets
    true_pubs = (pool.public_keys(signing.ED25519, ed_secrets)
                 + pool.public_keys(signing.SR25519, sr_secrets))
    pubs = list(true_pubs)
    pubs[off_curve_key_index(cfg, seed)] = _off_curve_key(seed)
    chain_id = d["chain_id"]

    def sign_row(k, order, commit):
        jobs = {signing.ED25519: [], signing.SR25519: []}
        slots = {signing.ED25519: [], signing.SR25519: []}
        for slot, key in enumerate(order):
            kind = signing.ED25519 if key < n_ed else signing.SR25519
            # the off-curve validator signs with the key it should have had:
            # a well-formed signature that its registered key cannot verify
            jobs[kind].append((secrets[key], true_pubs[key],
                               commit.vote_sign_bytes(chain_id, slot),
                               derive(seed, "sr-rng", k, key)))
            slots[kind].append(slot)
        row = np.zeros((len(order), 64), np.uint8)
        for kind, kind_jobs in jobs.items():
            if kind_jobs:
                out = pool.sign(kind, kind_jobs)
                row[slots[kind]] = np.frombuffer(
                    b"".join(out), np.uint8).reshape(-1, 64)
        return row

    return _assemble(cfg, seed, pubs, sign_row)


def _config_digest(cfg: dict) -> str:
    return hashlib.sha256(json.dumps(cfg["dataset"], sort_keys=True)
                          .encode()).hexdigest()


def content_digest(ds: Dataset) -> str:
    """What "the same dataset" means: every public key and signature byte."""
    return hashlib.sha256(ds.pubs.tobytes() + ds.sigs.tobytes()).hexdigest()


def load_or_generate(name: str, cfg: dict, seed: int, data_dir: str = DATA_DIR,
                     workers: int | None = None,
                     openssl: bool | None = None) -> Dataset:
    """The cell's data: from the cache when this seed was generated in this
    checkout before (same ``dataset`` parameters), else generated and stored.
    ``meta`` says which, how long it took, and who signed."""
    t0 = time.monotonic()
    path = os.path.join(data_dir, f"{name}-{seed}.npz")
    want = {"format": FORMAT, "config": _config_digest(cfg), "seed": seed}
    ds = None
    if os.path.exists(path):
        with np.load(path) as z:
            stored = json.loads(str(z["meta"]))
            if {k: stored.get(k) for k in want} == want:
                pubs, sigs = z["pubs"], z["sigs"]
                ds = _assemble(cfg, seed, [p.tobytes() for p in pubs],
                               lambda k, *_: sigs[k])
                ds.meta = {**stored, "cached": True}
    if ds is None:
        with signing.SignerPool(workers, openssl) as pool:
            ds = generate(cfg, seed, pool)
            ds.meta = {**want, "cached": False, "workers": pool.workers,
                       "ed25519_signer": "openssl" if pool.openssl
                       else "benchmark/reference"}
        os.makedirs(data_dir, exist_ok=True)
        tmp = path + ".tmp.npz"
        np.savez(tmp, pubs=ds.pubs, sigs=ds.sigs,
                 meta=json.dumps({**ds.meta, "digest": content_digest(ds)}))
        os.replace(tmp, path)
    ds.meta["path"] = path
    ds.meta["redraws"] = ds.redraws
    ds.meta["seconds"] = time.monotonic() - t0
    return ds
