#!/usr/bin/env python3
"""chip_smoke.py: the verify path, end to end, on the TPU this process holds.

    python chip_smoke.py --seed N

Drives the system's main path once through the entry points a user calls,
at the width of the north-star deployment (BASELINE.json: a 10,000-validator
commit; 10,000 is the reference's MaxVotesCount), and checks every answer
against the plain serial reference ``crypto.ed25519.verify``. Everything
runs in THIS process: a chip belongs to one process at a time, so the
script starts no child that needs JAX (the C libraries' gcc builds are the
only processes it starts, and they end before it does).

Phases, each pass/fail (any failure, or a device circuit breaker that
recorded a failure, is a non-zero exit):

 1. commit    ValidatorSet.verify_commit / verify_commit_light over a full
              commit: cold call (table build + compile) timed as set-up, a
              few warm calls, seeded corruptions rejected with the serial
              path's first-bad-index, full bitmap equal to the reference.
 2. fastsync  blockchain/replay.make_chain + ReplayCtx driven by
              VerifyAheadPipeline at BASELINE config 4's shape (1,000
              validators, 700 ed25519 / 300 sr25519): default depth and
              depth 1 agree on the app hash; a corrupted commit is rejected
              at its height.
 3. node      `cli init` + the start path, kvstore app, one validator:
              warm-up reports success, heights commit, txs sent over RPC
              are read back with proofs, /metrics shows closed breakers.
 4. end state breakers at zero failures; calibration, C libraries, cold
              compile seconds and compile-cache hits printed.

It refuses to run unless ``jax.default_backend() == "tpu"``. On success the
last line of stdout is ``{"ok": true, "device": {...}}``; on any failure no
such line is printed. The phase functions take their sizes as arguments so
tests/test_chip_smoke.py can run them tiny on the CPU
(``on_chip=False`` drops only the assertions that need a TPU).
"""

from __future__ import annotations

import argparse
import copy
import hashlib
import json
import os
import re
import sys
import tempfile
import time
import traceback
import urllib.request

CHAIN_ID = "chip-smoke"

# The sizes a run on the chip uses; anything smaller is listed in `reduced`.
N_VALIDATORS = 10_000          # BASELINE.json north star; MaxVotesCount
FASTSYNC_ED, FASTSYNC_SR = 700, 300   # BASELINE config 4
FASTSYNC_BLOCKS = 8
NODE_HEIGHTS, NODE_TXS = 5, 20
REDUCED = [
    "fastsync: %d of BASELINE config 4's 1,000 blocks (widths kept: 1,000 "
    "validators, 700 ed25519 / 300 sr25519; signing the chain in pure Python "
    "costs ~4 s per block)" % FASTSYNC_BLOCKS,
]


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _derive(seed: int, *path) -> bytes:
    """32 bytes for this seed and purpose: every key, message and corruption
    index of a run comes from here."""
    return hashlib.sha256(repr((seed,) + path).encode()).digest()


def _pick(seed: int, n: int, *path) -> int:
    return int.from_bytes(_derive(seed, *path)[:8], "big") % n


def _median_ms(fn, calls: int) -> float:
    out = []
    for _ in range(calls):
        t0 = time.monotonic()
        fn()
        out.append((time.monotonic() - t0) * 1e3)
    return sorted(out)[len(out) // 2]


# ---------------------------------------------------------------------------
# Device and compile cache
# ---------------------------------------------------------------------------


def device_info() -> dict:
    import importlib.metadata as md

    import jax

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "libtpu": libtpu}


class CacheWatch:
    """Counts jax's persistent-compile-cache hits and misses in this process
    (jax.monitoring events) and the entries in the cache directory. Create
    it after importing an ops module (that import places the cache,
    utils/jaxcache.py) and before the first compile."""

    def __init__(self) -> None:
        import jax

        self.dir = jax.config.jax_compilation_cache_dir
        self.hits = self.misses = 0
        self.entries_at_start = self._entries()
        jax.monitoring.register_event_listener(self._on_event)

    def _entries(self) -> int:
        try:
            return len(os.listdir(self.dir)) if self.dir else 0
        except OSError:
            return 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self) -> dict:
        return {"dir": self.dir, "hits": self.hits, "misses": self.misses,
                "entries_at_start": self.entries_at_start,
                "entries_now": self._entries(),
                "warm": self.hits > 0 and self.misses == 0}


# ---------------------------------------------------------------------------
# Phase 1: the north-star commit
# ---------------------------------------------------------------------------


def _serial_commit_bitmap(vals, commit) -> list[bool]:
    """The plain serial reference over every slot of a commit: pure-Python
    crypto.ed25519.verify, independent of ops/."""
    from tendermint_tpu.crypto import ed25519 as ref

    return [ref.verify(v.pub_key.bytes(), commit.vote_sign_bytes(CHAIN_ID, i),
                       commit.signatures[i].signature)
            for i, v in enumerate(vals.validators)]


def _expect_commit_error(verify, commit, want_idx) -> None:
    """Run the production entry point (a bound ValidatorSet.verify_commit*);
    it must raise ErrWrongSignature at want_idx."""
    from tendermint_tpu.types.validator_set import ErrWrongSignature

    what = verify.__name__
    try:
        verify(CHAIN_ID, commit.block_id, commit.height, commit)
    except ErrWrongSignature as e:
        check(e.index == want_idx,
              f"{what}: first bad index {e.index}, serial reference says "
              f"{want_idx}")
        return
    raise SmokeFailure(f"{what}: accepted, serial reference rejects index "
                       f"{want_idx}")


def phase_commit(seed: int, n_vals: int = N_VALIDATORS, on_chip: bool = True,
                 warm_calls: int = 3) -> dict:
    import numpy as np

    from tendermint_tpu.blockchain.replay import signed_commit
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto import ed25519 as ref
    from tendermint_tpu.crypto import verify_service
    from tendermint_tpu.ops import ed25519_batch as edb
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.ttime import Time
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    out: dict = {"validators": n_vals}
    t0 = time.monotonic()
    privs = [ref.gen_priv_key(_derive(seed, "val", i)) for i in range(n_vals)]
    # one validator holds a pubkey that is not a curve point
    k = 0
    while ref._decompress(_derive(seed, "offcurve", k)) is not None:
        k += 1
    off_pub = ref.PubKey(_derive(seed, "offcurve", k))
    slot = _pick(seed, n_vals, "offcurve-slot")
    pubs = [p.pub_key() for p in privs]
    pubs[slot] = off_pub
    vals = ValidatorSet([Validator.new(pk, 10) for pk in pubs])
    by_addr = {pk.address(): p for pk, p in zip(pubs, privs)}
    privs = [by_addr[v.address] for v in vals.validators]  # set order
    off_idx = next(i for i, v in enumerate(vals.validators)
                   if v.pub_key.equals(off_pub))

    height = 7
    ts = Time(1_700_000_000 + seed % 1000, 0)
    bid = BlockID(hash=_derive(seed, "block"),
                  part_set_header=PartSetHeader(
                      total=1, hash=_derive(seed, "parts")))
    signed = signed_commit(CHAIN_ID, vals, privs, height, bid, ts)
    # the clean commit: everyone signed, the off-curve validator is absent
    sigs = list(signed.signatures)
    sigs[off_idx] = CommitSig.new_absent()
    clean = Commit(height=height, round=signed.round, block_id=bid,
                   signatures=sigs)
    out["gen_s"] = round(time.monotonic() - t0, 1)

    needed = vals.total_voting_power() * 2 // 3
    prefix = vals.commit_light_prefix(clean, needed)

    # --- cold call: table build + compile, timed as set-up -----------------
    svc = verify_service.get()
    launches0, fallbacks0 = svc.launches, svc.fallbacks
    t0 = time.monotonic()
    pending = vals.verify_commit_async(CHAIN_ID, bid, height, clean)
    if on_chip:
        check(pending.pending.has_device_output(),
              "verify_commit dispatch has no device output")
    pending.resolve()
    out["cold_s"] = round(time.monotonic() - t0, 1)
    vals.verify_commit_light(CHAIN_ID, bid, height, clean)
    out["warm_verify_commit_ms"] = round(_median_ms(
        lambda: vals.verify_commit(CHAIN_ID, bid, height, clean),
        warm_calls), 2)
    out["warm_verify_commit_light_ms"] = round(_median_ms(
        lambda: vals.verify_commit_light(CHAIN_ID, bid, height, clean),
        warm_calls), 2)
    out["light_prefix_sigs"] = len(prefix)

    # --- seeded corruptions --------------------------------------------------
    sigs = list(clean.signatures)
    corrupted: dict[int, str] = {}

    def corrupt(idx: int, kind: str, sig: bytes) -> None:
        cs = signed.signatures[idx]
        sigs[idx] = CommitSig.new_commit(cs.block_id_flag,
                                         cs.validator_address, cs.timestamp,
                                         sig)
        corrupted[idx] = kind

    # the off-curve validator now "signs" (a well-formed foreign signature)
    corrupt(off_idx, "off-curve pubkey", signed.signatures[off_idx].signature)
    free = [i for i in range(n_vals) if i != off_idx]
    # two of the picks land inside the light prefix, the rest anywhere
    picks = []
    for j, pool in enumerate([prefix, prefix, free, free, free, free]):
        pool = [i for i in pool if i not in picks and i != off_idx]
        picks.append(pool[_pick(seed, len(pool), "corrupt", j)])
    for j, idx in enumerate(picks):
        good = signed.signatures[idx].signature
        if j % 3 == 0:
            bit = _pick(seed, 512, "bit", j)
            flipped = bytearray(good)
            flipped[bit // 8] ^= 1 << (bit % 8)
            corrupt(idx, f"flipped signature bit {bit}", bytes(flipped))
        elif j % 3 == 1:
            corrupt(idx, "S >= L", good[:32] + b"\xff" * 32)
        else:
            corrupt(idx, "truncated signature", good[:63])
    bad = Commit(height=height, round=signed.round, block_id=bid,
                 signatures=sigs)
    out["corruptions"] = {str(i): k for i, k in sorted(corrupted.items())}

    # --- the serial reference ------------------------------------------------
    t0 = time.monotonic()
    serial = _serial_commit_bitmap(vals, bad)
    out["serial_reference_s"] = round(time.monotonic() - t0, 1)
    out["serial_reference_sigs"] = len(serial)
    check(not any(serial[i] for i in corrupted),
          "serial reference accepts a corrupted signature")
    check(all(ok for i, ok in enumerate(serial) if i not in corrupted),
          "serial reference rejects an untouched signature")
    first_bad = serial.index(False)
    # the light walk stops at +2/3: only its prefix is consulted
    first_bad_light = next(
        (i for i in vals.commit_light_prefix(bad, needed) if not serial[i]),
        None)
    check(first_bad_light is not None,
          "no corruption landed in the light prefix")
    _expect_commit_error(vals.verify_commit, bad, first_bad)
    _expect_commit_error(vals.verify_commit_light, bad, first_bad_light)
    out["first_bad_index"] = first_bad
    out["first_bad_index_light"] = first_bad_light

    # --- full bitmap through the registry ------------------------------------
    items = [(vals.validators[i].pub_key, bad.vote_sign_bytes(CHAIN_ID, i),
              bad.signatures[i].signature) for i in range(n_vals)]
    verifier = crypto_batch.create_batch_verifier("ed25519")
    for it in items:
        verifier.add(*it)
    pv = verifier.dispatch()
    if on_chip:
        check(pv.has_device_output(), "registry dispatch has no device output")
    all_ok, bitmap = pv.resolve()
    check(not all_ok and len(bitmap) == n_vals, "bitmap shape/all_ok wrong")
    diff = [i for i in range(n_vals) if bitmap[i] != serial[i]]
    check(not diff, f"bitmap differs from the serial reference at {diff[:8]}")

    # --- the device really did it --------------------------------------------
    if on_chip:
        import jax

        raw = [(pk.bytes(), m, s) for pk, m, s in items]
        sharded = edb.should_shard(len(raw))
        dev, finish = edb.dispatch_batch(raw)
        check(dev is not None, "ops dispatch_batch answered from the host")
        # the packed pieces of the bitmap: one on a one-chip host, a chunk's
        # a piece, each on the chip that computed it, on the sharded route
        devs = set().union(*(p.devices() for p in dev))
        check({d.platform for d in devs} == {"tpu"},
              f"device output lives on {devs}")
        got = finish(jax.device_get(dev))
        check(np.array_equal(got, np.array(bitmap)),
              "direct dispatch bitmap != registry bitmap")
        out["route"] = ("pallas _verify_chunk, a chunk a device over %d"
                        % len(devs) if sharded else "pallas _verify_chunk")
        out["output_devices"] = len(devs)
        want_devs = (min(len(dev), jax.local_device_count()) if sharded else 1)
        check(len(devs) == want_devs and finish.route == (
            "sharded" if sharded else "pallas"),
              f"route {finish.route}: {len(dev)} pieces on {len(devs)} "
              f"devices, have {jax.device_count()}")
        check(edb._use_pallas(), "_use_pallas() is false on a TPU backend")
        out["pallas_lowering"] = _pallas_lowering()
        check(svc.launches > launches0, "verify service launched nothing")
        check(svc.fallbacks == fallbacks0, "verify service fell back to host")
    return out


def _pallas_lowering() -> str:
    """The production chunk kernel lowers to a Mosaic custom call (it is not
    in interpret mode)."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import ed25519_pallas as edp

    c = edp.CHUNK
    u8 = jnp.uint8
    text = edp._verify_chunk.lower(
        jax.ShapeDtypeStruct((960, c), jnp.int32),
        jax.ShapeDtypeStruct((64, c), u8), jax.ShapeDtypeStruct((32, c), u8),
        jax.ShapeDtypeStruct((32, c), u8), jax.ShapeDtypeStruct((1, c), u8),
    ).as_text()
    check("tpu_custom_call" in text,
          "_verify_chunk does not lower to a tpu_custom_call")
    return f"tpu_custom_call CHUNK={c} TILE={edp.TILE}"


# ---------------------------------------------------------------------------
# Phase 2: fast-sync replay, mixed keys
# ---------------------------------------------------------------------------


def _mixed_valset(seed: int, n_ed: int, n_sr: int):
    from tendermint_tpu.crypto import ed25519, sr25519
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    privs = [ed25519.gen_priv_key(_derive(seed, "fs-ed", i))
             for i in range(n_ed)]
    privs += [sr25519.gen_priv_key(_derive(seed, "fs-sr", i))
              for i in range(n_sr)]
    by_addr = {p.pub_key().address(): p for p in privs}
    vals = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
    return [by_addr[v.address] for v in vals.validators], vals


def _replay(vals, blocks, depth: int | None):
    """All pooled blocks through VerifyAheadPipeline, as blockchain/reactor.py
    drives it. depth None = the default."""
    from tendermint_tpu.blockchain import pipeline as bpipe
    from tendermint_tpu.blockchain.replay import ReplayCtx

    class Ctx(ReplayCtx):
        rejected = None

        def _punish_invalid(self, height, e):
            self.rejected = (height, e)
            super()._punish_invalid(height, e)

    prev = os.environ.get("TM_TPU_VERIFY_AHEAD")
    if depth is not None:
        os.environ["TM_TPU_VERIFY_AHEAD"] = str(depth)
    try:
        ctx = Ctx(vals, CHAIN_ID)
        for i, b in enumerate(blocks):
            ctx.pool.add_block("pA" if i % 2 == 0 else "pB", b)
        pipe = bpipe.VerifyAheadPipeline()
        while pipe.process_next(ctx):
            pass
        return ctx
    finally:
        if depth is not None:
            if prev is None:
                os.environ.pop("TM_TPU_VERIFY_AHEAD", None)
            else:
                os.environ["TM_TPU_VERIFY_AHEAD"] = prev


def phase_fastsync(seed: int, n_ed: int = FASTSYNC_ED, n_sr: int = FASTSYNC_SR,
                   n_blocks: int = FASTSYNC_BLOCKS, on_chip: bool = True) -> dict:
    from tendermint_tpu.blockchain import pipeline as bpipe
    from tendermint_tpu.blockchain.replay import make_chain
    from tendermint_tpu.crypto import verify_service
    from tendermint_tpu.types.block import CommitSig
    from tendermint_tpu.types.validator_set import ErrWrongSignature

    out: dict = {"validators": {"ed25519": n_ed, "sr25519": n_sr},
                 "blocks": n_blocks, "depth": bpipe.DEFAULT_DEPTH}
    t0 = time.monotonic()
    privs, vals = _mixed_valset(seed, n_ed, n_sr)
    # n_blocks + 1 pooled blocks -> n_blocks appliable heights
    blocks = make_chain(CHAIN_ID, n_blocks + 1, vals, privs)
    out["gen_s"] = round(time.monotonic() - t0, 1)

    svc = verify_service.get()
    launches0, fallbacks0 = svc.launches, svc.fallbacks
    t0 = time.monotonic()
    deep = _replay(vals, blocks, None)
    out["cold_replay_s"] = round(time.monotonic() - t0, 1)
    want = list(range(1, n_blocks + 1))
    check(deep.applied == want and not deep.punished,
          f"default depth: applied {deep.applied}, punished {deep.punished}")
    serial = _replay(vals, blocks, 1)
    check(serial.applied == want and not serial.punished,
          f"depth 1: applied {serial.applied}, punished {serial.punished}")
    check(deep.app_hash == serial.app_hash, "app hash differs from depth 1")
    t0 = time.monotonic()
    _replay(vals, blocks, None)
    out["warm_replay_s"] = round(time.monotonic() - t0, 2)
    out["app_hash"] = deep.app_hash.hex()

    # one corrupted commit signature: blocks[h] carries the commit FOR
    # height h, so corrupting it must stop the replay with h unapplied
    h = 2 + _pick(seed, n_blocks - 1, "fs-bad-height")   # 2..n_blocks
    bad_blocks = list(blocks)
    carrier = copy.copy(blocks[h])
    commit = copy.copy(carrier.last_commit)
    needed = vals.total_voting_power() * 2 // 3
    prefix = vals.commit_light_prefix(commit, needed)
    idx = prefix[_pick(seed, len(prefix), "fs-bad-sig")]
    cs = commit.signatures[idx]
    flipped = bytearray(cs.signature)
    flipped[_pick(seed, 64, "fs-bad-byte")] ^= 0x40
    commit.signatures = list(commit.signatures)
    commit.signatures[idx] = CommitSig.new_commit(
        cs.block_id_flag, cs.validator_address, cs.timestamp, bytes(flipped))
    carrier.last_commit = commit
    bad_blocks[h] = carrier
    rej = _replay(vals, bad_blocks, None)
    check(rej.applied == list(range(1, h)),
          f"corrupted commit for height {h}: applied {rej.applied}")
    check(rej.rejected is not None and rej.rejected[0] == h,
          f"rejected at {rej.rejected}, want height {h}")
    err = rej.rejected[1]
    check(isinstance(err, ErrWrongSignature) and err.index == idx,
          f"rejected with {err!r}, want ErrWrongSignature index {idx}")
    check(rej.punished, "nobody punished for the corrupted commit")
    out["rejected"] = {"height": h, "index": idx,
                       "key_type": vals.validators[idx].pub_key.type}

    if on_chip:
        import jax

        from tendermint_tpu.ops import sr25519_batch

        check(svc.launches > launches0, "verify service launched nothing")
        check(svc.fallbacks == fallbacks0, "verify service fell back to host")
        # the sr25519 kernel itself, directly: one commit's sr signatures
        commit = blocks[1].last_commit
        sr_items = [(vals.validators[i].pub_key.bytes(),
                     commit.vote_sign_bytes(CHAIN_ID, i),
                     commit.signatures[i].signature)
                    for i in range(vals.size())
                    if vals.validators[i].pub_key.type == "sr25519"]
        sr_items[0] = (sr_items[0][0], sr_items[0][1] + b"x", sr_items[0][2])
        dev, finish = sr25519_batch.dispatch_batch(sr_items, force_device=True)
        check(dev is not None, "sr25519 dispatch answered from the host")
        # the packed pieces of the bitmap, as in phase_commit
        devs = set().union(*(p.devices() for p in dev))
        check({d.platform for d in devs} == {"tpu"},
              f"sr25519 output lives on {devs}")
        got = finish(jax.device_get(dev))
        check(not got[0] and got[1:].all(), "sr25519 device bitmap wrong")
        out["sr25519_device_sigs"] = len(sr_items)
    return out


# ---------------------------------------------------------------------------
# Phase 3: a node
# ---------------------------------------------------------------------------


def _wait(pred, timeout_s: float, what: str, step: float = 0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(step)
    raise SmokeFailure(f"timed out after {timeout_s:.0f}s waiting for {what}")


def phase_node(seed: int, heights: int = NODE_HEIGHTS, n_txs: int = NODE_TXS,
               on_chip: bool = True) -> dict:
    import base64

    from tendermint_tpu.cli import main as cli
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto.merkle import Proof
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.rpc.client import HTTPClient
    from tendermint_tpu.types.tx import tx_hash

    out: dict = {}
    with tempfile.TemporaryDirectory(prefix="chip-smoke-node-") as home:
        check(cli.main(["--home", home, "init", "--chain-id",
                        f"{CHAIN_ID}-{seed}"]) == 0, "cli init failed")
        # the start path (cli.cmd_start) minus its signal loop
        cfg = cli._load_config(home)
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
        node = Node(cfg)
        t0 = time.monotonic()
        node.start()
        try:
            if on_chip:
                check(crypto_batch.WARMUP.join(600), "warm-up still running")
                check(crypto_batch.WARMUP.state == "done",
                      f"warm-up {crypto_batch.WARMUP.state}: "
                      f"{crypto_batch.WARMUP.error!r}")
                out["warmup_s"] = round(time.monotonic() - t0, 1)
            out["warmup"] = crypto_batch.WARMUP.state
            _wait(lambda: node.block_store.height >= heights, 120,
                  f"{heights} heights")
            rpc = HTTPClient("http://" + node.rpc_server.laddr.split("://")[1],
                             timeout=30.0)
            for i in range(n_txs):
                key = b"smoke-%d-%d" % (seed, i)
                value = _derive(seed, "tx", i).hex().encode()
                tx = key + b"=" + value
                res = rpc.broadcast_tx_commit(tx)
                check(res["check_tx"]["code"] == 0
                      and res["deliver_tx"]["code"] == 0,
                      f"tx {i} not committed: {res}")
                txh = tx_hash(tx)

                def indexed():
                    try:
                        return rpc.tx(txh, prove=True)
                    except Exception:  # noqa: BLE001 - the indexer drains
                        return None    # the event bus asynchronously

                got = _wait(indexed, 15, f"tx {i} in the index")
                check(base64.b64decode(got["tx"]) == tx
                      and got["height"] == res["height"],
                      f"tx {i} read back wrong: {got}")
                pd = got["proof"]["proof"]
                proof = Proof(total=int(pd["total"]), index=int(pd["index"]),
                              leaf_hash=base64.b64decode(pd["leaf_hash"]),
                              aunts=[base64.b64decode(a) for a in pd["aunts"]])
                root = bytes.fromhex(got["proof"]["root_hash"])
                blk = rpc.block(height=int(res["height"]))
                check(proof.compute_root_hash() == root
                      and blk["block"]["header"]["data_hash"].lower()
                      == root.hex(), f"tx {i}: proof does not verify")
                q = rpc.abci_query("", key)["response"]
                check(base64.b64decode(q["value"]) == value,
                      f"abci_query {key!r} -> {q}")
            out["txs"] = n_txs
            out["height"] = node.block_store.height

            def metrics():
                with urllib.request.urlopen(
                        f"http://{node.metrics_server.addr}/metrics",
                        timeout=10) as r:
                    return r.read().decode()

            text = metrics()
            for name in ("ops_breaker_open", "ops_breaker_trips_total"):
                got = dict(re.findall(
                    name + r'\{kernel="(\w+)"\} (\S+)', text))
                check(set(got) == {"ed25519", "sr25519"}
                      and all(float(v) == 0 for v in got.values()),
                      f"/metrics {name}: {got}")
            if on_chip:
                import jax

                if jax.device_count() > 1:
                    series = 'verify_sharded_total{devices="%d"}' \
                        % jax.device_count()
                    check(series in text, f"/metrics lacks {series}")
                    out["sharded_series"] = series
        finally:
            node.stop()
            check(crypto_batch.WARMUP.join(600),
                  "warm-up thread outlived the node")
    return out


# ---------------------------------------------------------------------------
# Phase 4: end state
# ---------------------------------------------------------------------------


def phase_end_state(cache: CacheWatch | None = None) -> dict:
    from tendermint_tpu.ops import chash, chost, ed25519_batch, sr25519_batch

    out: dict = {"breakers": {}}
    for mod in (ed25519_batch, sr25519_batch):
        b = mod.BREAKER
        out["breakers"][b.name] = {"failures": b.failures, "trips": b.trips}
        check(b.failures == 0,
              f"{b.name} recorded {b.failures} failure(s): {b.last_error!r}")
    out["calibration"] = dict(ed25519_batch._HOST_CAL)
    out["chost"] = chost.available()
    out["chash"] = chash.available()
    check(out["chost"] and out["chash"], "a C library did not load")
    if cache is not None:
        out["compile_cache"] = cache.report()
    return out


# ---------------------------------------------------------------------------


def run(seed: int) -> int:
    import jax

    dev = device_info()
    print(json.dumps({"device": dev}), flush=True)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: refusing to run: jax.default_backend() is "
              f"{jax.default_backend()!r}, not 'tpu' (this script proves the "
              f"verify path on a chip; CPU runs are tests/test_chip_smoke.py)",
              file=sys.stderr)
        return 2

    from tendermint_tpu.ops import ed25519_batch

    cache = CacheWatch()
    # what a node's warm-up does first: the routing below uses this crossover
    ed25519_batch.calibrate_host_crossover()
    print(json.dumps({"calibration": ed25519_batch._HOST_CAL}), flush=True)

    failed = []
    for name, fn in (("commit", lambda: phase_commit(seed)),
                     ("fastsync", lambda: phase_fastsync(seed)),
                     ("node", lambda: phase_node(seed)),
                     ("end_state", lambda: phase_end_state(cache))):
        t0 = time.monotonic()
        try:
            detail = fn()
            ok = True
        except Exception as e:  # noqa: BLE001 - report, run the next phase
            traceback.print_exc()
            detail = {"error": f"{type(e).__name__}: {e}"}
            ok = False
            failed.append(name)
        print(json.dumps({"phase": name, "ok": ok,
                          "seconds": round(time.monotonic() - t0, 1),
                          **detail}, default=str), flush=True)
    print(json.dumps({"seed": seed, "reduced": REDUCED, "failed": failed}),
          flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="every key, message and corruption derives from it")
    return run(ap.parse_args(argv).seed)


if __name__ == "__main__":
    sys.exit(main())
