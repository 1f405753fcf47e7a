"""The chain the ``light-skip`` mix serves to a light client: a chain whose
validator set rotates, a function of ``--seed`` and the configuration.

The source's mock node (``light/helpers_test.go`` ``genMockNodeWithKeys``,
as recalled): at every step the oldest keys leave the set and as many new
ones join (``ChangeKeys``), block h is signed by set(h), its header names
set(h)'s hash as ``validators_hash`` and set(h+1)'s as
``next_validators_hash``, and each header's ``last_block_id`` is the hash of
the header before it. Here:

  - key k's secret is ``derive(seed, "rot-val", k)``; set(h) holds keys
    ``lo(h) .. lo(h) + n`` with ``lo(h) = rotate_keys * ((h - 1) //
    rotate_every)``, every one at the configuration's equal power, in the
    program's canonical order (by address, the powers being equal);
  - **every** header of heights 1..``chain_heights`` exists and is chained:
    the hash of each of the ``chain_heights + 1`` sets is computed (RFC 6962
    over SimpleValidator encodings, ``hashlib`` alone, in child processes),
    and the program's ``ValidatorSet.hash()`` must agree wherever a set is
    built;
  - **light blocks** (a commit, so a set's worth of signatures) are made
    only for the heights a sync from height 1 to the last height asks its
    primary for. They are a property of the chain, not of the program: the
    plain reference (``benchmark/reference/light_skipping.py``) names them,
    run over the unsigned chain with a verifier that believes every
    signature (who signs, and so which hop has the power, is fixed before
    anything is signed);
  - who is absent or votes nil at a height is ``datagen``'s derivation over
    the set in force there (``pattern_seed`` where the configuration fixes
    one), each validator signs with its own timestamp;
  - **a header's hash and a vote's sign bytes are the benchmark's own**
    (``benchmark/reference/canonical.py``): each header names the hash that
    encoder gives the one before, every signature is over that encoder's
    bytes, and ``header_record`` / ``sign_bytes`` state both to the plain
    reference. The program's ``Header``, ``Commit`` and ``LightBlock`` only
    carry the values: a program that hashes a header or encodes a vote
    otherwise refuses this chain.

What is expensive (keys, signatures, set hashes) is cached under
``benchmark/.data/`` by seed and a digest of ``dataset``; the program's
objects are rebuilt from the cache in every run. ``harness/datagen.py`` is
not touched.
"""

from __future__ import annotations

import bisect
import concurrent.futures
import hashlib
import json
import multiprocessing
import os
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.harness import datagen, signing
from benchmark.reference import canonical, light_skipping
from benchmark.reference.light_sync import simple_validator

FORMAT = 1
FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3


def lo(d: dict, h: int) -> int:
    """The oldest key of set(h)."""
    return d["rotate_keys"] * ((h - 1) // d["rotate_every"])


def time_ns(h: int) -> int:
    """Header h's time: one second a height."""
    return (datagen.BASE_SECONDS + h) * 10**9


def total_keys(d: dict) -> int:
    """Keys of set(1) .. set(chain_heights + 1)."""
    return d["validators"]["ed25519"] + lo(d, d["chain_heights"] + 1)


# --- set hashes: hashlib alone, so that children can compute them ---------------


def _root(level: list[bytes]) -> bytes:
    """RFC 6962 root over leaf hashes: pair up, carry an odd last one."""
    sha = hashlib.sha256
    while len(level) > 1:
        nxt = [sha(b"\x01" + level[i] + level[i + 1]).digest()
               for i in range(0, len(level) - 1, 2)]
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]


def set_hashes(blob: bytes, n: int, rotate: int, every: int, power: int,
               first: int, last: int) -> list[bytes]:
    """validators_hash of set(first) .. set(last): the members kept in
    address order from one height to the next."""
    sha = hashlib.sha256
    k0 = rotate * ((first - 1) // every)
    k1 = rotate * ((last - 1) // every) + n
    addr = {k: sha(blob[32 * k:32 * k + 32]).digest()[:20] for k in range(k0, k1)}
    leaf = {k: sha(b"\x00" + simple_validator(blob[32 * k:32 * k + 32], power)).digest()
            for k in range(k0, k1)}
    members = sorted((addr[k], k) for k in range(k0, k0 + n))
    out, at = [], k0
    for h in range(first, last + 1):
        now = rotate * ((h - 1) // every)
        for k in range(at, now):
            del members[bisect.bisect_left(members, (addr[k], k))]
            bisect.insort(members, (addr[k + n], k + n))
        at = now
        out.append(_root([leaf[k] for _a, k in members]))
    return out


def all_set_hashes(blob: bytes, d: dict, workers: int) -> list[bytes]:
    """[validators_hash of set(h)] for h = 1 .. chain_heights + 1."""
    n, last = d["validators"]["ed25519"], d["chain_heights"] + 1
    args = (blob, n, d["rotate_keys"], d["rotate_every"], d["voting_power"])
    if workers <= 0 or last < 64:
        return set_hashes(*args, 1, last)
    step = -(-last // (workers * 2))
    spans = [(a, min(a + step - 1, last)) for a in range(1, last + 1, step)]
    with concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as ex:
        futs = [ex.submit(set_hashes, *args, a, b) for a, b in spans]
        return [h for f in futs for h in f.result()]


# --- the chain --------------------------------------------------------------


@dataclass
class RotatingChain:
    chain_id: str
    d: dict                           # the configuration's `dataset`
    seed: int
    pubs: np.ndarray                  # (keys, 32) uint8, generation order
    addrs: list                       # address of every key
    set_hash: list                    # [h - 1] -> hash of set(h), to H + 1
    headers: dict                     # height -> types.Header, every height
    block_ids: dict                   # height -> types.BlockID
    block_id_bytes: dict              # height -> canonical.block_id(...)
    visited: list                     # the heights that have a light block
    blocks: dict                      # height -> types.LightBlock, visited
    sigs: np.ndarray                  # (len(visited), n, 64) uint8, set order
    # visited height -> [None | canonical sign bytes], slot by slot
    sign_bytes: dict = field(default_factory=dict)
    plan: tuple = ()                  # the reference's clean sync, unsigned
    plan_sigs: int = 0                # signatures its serial loops consult
    redraws: int = 0
    meta: dict = field(default_factory=dict)
    _patterns: dict = field(default_factory=dict)

    @property
    def target(self) -> int:
        return self.d["chain_heights"]

    def members(self, h: int) -> list[int]:
        """Key numbers of set(h) in the set's order."""
        k0, n = lo(self.d, h), self.d["validators"]["ed25519"]
        return sorted(range(k0, k0 + n), key=self.addrs.__getitem__)

    def pattern(self, h: int):
        """(absent, nil) masks of height h's commit, in set order."""
        if h not in self._patterns:
            self._patterns[h] = self._draw_pattern(h)
        return self._patterns[h]

    def _draw_pattern(self, h: int):
        d = self.d
        n = d["validators"]["ed25519"]
        powers = np.full(n, d["voting_power"], np.int64)
        needed = int(powers.sum()) * 2 // 3
        pseed = d.get("pattern_seed", self.seed)
        for counter in range(1 << 16):
            absent = datagen.bernoulli(pseed, n, d["absent_share"], "absent",
                                       "height", h, counter)
            nil = datagen.bernoulli(pseed, n, d["nil_share"], "nil", "height",
                                    h, counter) & ~absent
            if int(powers[~absent & ~nil].sum()) > needed:
                self.redraws += counter
                return absent, nil
        raise ValueError(f"no draw of height {h} reaches +2/3")

    def header_record(self, h: int) -> dict:
        """Header h as the plain reference reads it: what the generator's
        own encoders state, nothing the program computed."""
        return {"height": h, "time_ns": time_ns(h),
                "hash": self.block_ids[h].hash,
                "validators_hash": self.set_hash[h - 1],
                "next_validators_hash": self.set_hash[h]}

    def unsigned_record(self, h: int) -> dict:
        """Height h as the plain reference reads it, before anything is
        signed: who is in the set and who votes how."""
        members = self.members(h)
        absent, nil = self.pattern(h)
        power = self.d["voting_power"]
        return {
            **self.header_record(h),
            "commit_height": h, "commit_block_hash": self.block_ids[h].hash,
            "validators": [(self.addrs[k], self.pubs[k].tobytes(), power)
                           for k in members],
            "slots": [None if absent[i] else
                      (self.addrs[k], FLAG_NIL if nil[i] else FLAG_COMMIT,
                       None, None) for i, k in enumerate(members)],
        }


def now_ns(chain: RotatingChain, options: dict) -> int:
    return time_ns(chain.target) + int(options["now_after_target_s"] * 1e9)


def plan_sync(chain: RotatingChain, options: dict):
    """The reference's sync over the unsigned chain, every signature
    believed -> (light_skipping.sync's answer, signatures consulted): its
    ``fetched`` are the heights that need a light block; the count is what a
    session verifies, the trust root's own light prefix included."""
    consulted = []
    root = chain.unsigned_record(1)
    light_skipping.verify_commit_light(
        root["validators"], root["slots"], lambda *a: consulted.append(1) or True)
    answer = light_skipping.sync(
        root, chain.target, chain.unsigned_record,
        int(options["trusting_period_s"] * 1e9), now_ns(chain, options),
        int(options["max_clock_drift_s"] * 1e9),
        tuple(options["trust_level"]),
        verify_sig=lambda *a: consulted.append(1) or True)
    return answer, len(consulted)


def _headers(chain_id: str, d: dict, seed: int, addrs, set_hash):
    """Every header, chained by ``canonical.header_hash`` -> ({h: Header},
    {h: BlockID}, {h: the block id's canonical bytes})."""
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.ttime import Time

    n = d["validators"]["ed25519"]
    headers, block_ids, id_bytes = {}, {}, {}
    prev, prev_bytes = BlockID(), canonical.block_id(b"", 0, b"")
    for h in range(1, d["chain_heights"] + 1):
        k0 = lo(d, h)
        proposer = min(addrs[k0:k0 + n])
        headers[h] = Header(
            chain_id=chain_id, height=h,
            time=Time(datagen.BASE_SECONDS + h, 0), last_block_id=prev,
            validators_hash=set_hash[h - 1], next_validators_hash=set_hash[h],
            proposer_address=proposer)
        own = canonical.header_hash(
            chain_id=chain_id, height=h, seconds=datagen.BASE_SECONDS + h,
            nanos=0, last_block_id=prev_bytes, validators_hash=set_hash[h - 1],
            next_validators_hash=set_hash[h], proposer_address=proposer)
        parts = datagen.derive(seed, "parts", h)
        prev = BlockID(hash=own, part_set_header=PartSetHeader(total=1, hash=parts))
        prev_bytes = canonical.block_id(own, 1, parts)
        block_ids[h], id_bytes[h] = prev, prev_bytes
    return headers, block_ids, id_bytes


def _light_block(chain: RotatingChain, h: int, sign):
    """Height h's LightBlock. ``sign(jobs) -> [signature]``: jobs are (key
    number, sign bytes), one per slot that is not Absent, in slot order."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    d = chain.d
    members = chain.members(h)
    absent, nil = chain.pattern(h)
    vals = ValidatorSet([
        Validator.new(ed25519.PubKey(chain.pubs[k].tobytes()), d["voting_power"])
        for k in members])
    if ([v.address for v in vals.validators] != [chain.addrs[k] for k in members]
            or vals.hash() != chain.set_hash[h - 1]):
        raise AssertionError(f"set({h}): the program orders or hashes it "
                             f"otherwise than the generator")
    commit = Commit(height=h, round=0, block_id=chain.block_ids[h], signatures=[
        CommitSig.new_absent() if absent[i] else CommitSig(
            FLAG_NIL if nil[i] else FLAG_COMMIT, v.address,
            datagen._timestamp(chain.seed, h, i), b"")
        for i, v in enumerate(vals.validators)])
    stamped = [None if cs.absent() else
               (cs.block_id_flag, cs.timestamp.seconds, cs.timestamp.nanos)
               for cs in commit.signatures]
    msgs = chain.sign_bytes[h] = canonical.commit_sign_bytes(
        chain.chain_id, h, 0, chain.block_id_bytes[h], stamped)
    slots = [int(i) for i in np.flatnonzero(~absent)]
    row = np.zeros((len(members), 64), np.uint8)
    for i, sig in zip(slots, sign([(members[i], msgs[i]) for i in slots])):
        commit.signatures[i].signature = sig
        row[i] = np.frombuffer(sig, np.uint8)
    return LightBlock(SignedHeader(chain.headers[h], commit), vals), row


def _config_digest(cfg: dict) -> str:
    return hashlib.sha256(json.dumps(
        [cfg["dataset"], cfg["assumed"]["client"]], sort_keys=True)
        .encode()).hexdigest()


def content_digest(chain: RotatingChain) -> str:
    """What "the same chain" means: every key, every signature, and the last
    header's hash, which covers every header and set hash before it."""
    return hashlib.sha256(chain.pubs.tobytes() + chain.sigs.tobytes()
                          + chain.block_ids[chain.target].hash).hexdigest()


def load_or_generate(name: str, cfg: dict, seed: int,
                     data_dir: str = datagen.DATA_DIR,
                     workers: int | None = None,
                     openssl: bool | None = None) -> RotatingChain:
    """The chain of this seed: from the cache when it was made in this
    checkout before (same ``dataset`` and client options), else made and
    stored. ``meta`` says which and how long it took."""
    t0 = time.monotonic()
    d, options = cfg["dataset"], cfg["assumed"]["client"]
    path = os.path.join(data_dir, f"{name}-rotating-{seed}.npz")
    want = {"format": FORMAT, "config": _config_digest(cfg), "seed": seed}
    stored = None
    if os.path.exists(path):
        with np.load(path) as z:
            meta = json.loads(str(z["meta"]))
            if {k: meta.get(k) for k in want} == want:
                stored = {k: z[k] for k in ("pubs", "sigs", "set_hash", "visited")}
                stored["meta"] = meta
    with signing.SignerPool(workers, openssl) as pool:
        secrets = [datagen.derive(seed, "rot-val", k) for k in range(total_keys(d))]
        if stored is None:
            pubs = pool.public_keys(signing.ED25519, secrets)
            blob = b"".join(pubs)
            set_hash = all_set_hashes(blob, d, pool.workers)
        else:
            blob = stored["pubs"].tobytes()
            set_hash = [r.tobytes() for r in stored["set_hash"]]
        pubs_arr = np.frombuffer(blob, np.uint8).reshape(-1, 32)
        addrs = [hashlib.sha256(blob[32 * k:32 * k + 32]).digest()[:20]
                 for k in range(len(pubs_arr))]
        headers, block_ids, id_bytes = _headers(d["chain_id"], d, seed, addrs,
                                                set_hash)
        chain = RotatingChain(
            chain_id=d["chain_id"], d=d, seed=seed, pubs=pubs_arr, addrs=addrs,
            set_hash=set_hash, headers=headers, block_ids=block_ids,
            block_id_bytes=id_bytes, visited=[], blocks={}, sigs=None)
        chain.plan, chain.plan_sigs = plan_sync(chain, options)
        chain.visited = sorted({1, *chain.plan[1]})
        if stored is not None and list(stored["visited"]) != chain.visited:
            raise AssertionError("the cached chain was signed for other "
                                 "heights than the plan names")

        def sign_new(jobs):
            return pool.sign(signing.ED25519, [
                (secrets[k], blob[32 * k:32 * k + 32], msg, b"")
                for k, msg in jobs])

        rows = []
        for j, h in enumerate(chain.visited):
            sign = sign_new
            if stored is not None:
                place = {k: i for i, k in enumerate(chain.members(h))}

                def sign(jobs, row=stored["sigs"][j], place=place):
                    return [row[place[k]].tobytes() for k, _msg in jobs]
            chain.blocks[h], row = _light_block(chain, h, sign)
            rows.append(row)
        chain.sigs = np.stack(rows)
        if stored is None:
            chain.meta = {**want, "cached": False, "workers": pool.workers,
                          "ed25519_signer": "openssl" if pool.openssl
                          else "benchmark/reference"}
            os.makedirs(data_dir, exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp.npz"   # two runs may share a seed
            np.savez(tmp, pubs=chain.pubs, sigs=chain.sigs,
                     set_hash=np.frombuffer(b"".join(set_hash), np.uint8)
                     .reshape(-1, 32),
                     visited=np.array(chain.visited, np.int64),
                     meta=json.dumps({**chain.meta,
                                      "digest": content_digest(chain)}))
            os.replace(tmp, path)
        else:
            chain.meta = {**stored["meta"], "cached": True}
    chain.meta["path"] = path
    chain.meta["plan_sigs"] = chain.plan_sigs
    chain.meta["redraws"] = chain.redraws
    chain.meta["seconds"] = time.monotonic() - t0
    return chain
