"""The ``replay`` mix: fast sync. The benchmark's own copy (PR 22) of the
minimal reactor surface of ``blockchain/replay.ReplayCtx`` -- a real
BlockPool, stub store and executor, the app hash chained over accepted block
IDs -- driven by ``VerifyAheadPipeline.process_next`` at the default depth
until the chain is applied, pass after pass."""

from __future__ import annotations

import copy
import hashlib
import time
import types as pytypes

from benchmark.harness import correct, datagen


class ReactorSurface:
    """What VerifyAheadPipeline drives: ``pool``, ``state``, ``block_store``,
    ``block_exec`` and ``_punish_invalid``."""

    def __init__(self, vals, chain_id: str, blocks):
        from tendermint_tpu.blockchain.reactor import BlockPool

        self.pool = BlockPool(1)
        self.state = pytypes.SimpleNamespace(validators=vals, chain_id=chain_id)
        self.applied: list[int] = []
        self.punished: list[str] = []
        self.rejected = None
        self.app_hash = b"\x00" * 32
        self.block_store = self
        self.block_exec = self
        for i, b in enumerate(blocks):
            self.pool.add_block("pA" if i % 2 == 0 else "pB", b)

    def save_block(self, block, parts, seen_commit) -> None:
        pass

    def apply_block(self, state, block_id, block):
        self.applied.append(block.header.height)
        self.app_hash = hashlib.sha256(self.app_hash + block_id.hash).digest()
        return state, 0

    def _punish_invalid(self, height, e) -> None:
        self.rejected = (height, e)
        bad = self.pool.redo_request(height)
        bad2 = self.pool.redo_request(height + 1)
        self.punished.extend(sorted({bad, bad2} - {None}))


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        self.run, self.ds, self.traffic = run, dataset, traffic
        self.heights = len(dataset.blocks) - 1     # appliable heights
        needed = dataset.vals.total_voting_power() * 2 // 3
        # decision j of a pass applies height j + 1 on the light commit
        # blocks[j + 1] carries for it, whose signers differ height by height
        # where the configuration has absent or nil votes. ``signer_sets``
        # counts distinct light prefixes: what the chain holds, not what the
        # program's key-set cache sees (it keys on a launch's union of the
        # pipeline's requests): ``sync_keyset_miss_share`` says that
        prefixes = [tuple(dataset.vals.commit_light_prefix(c, needed))
                    for c in dataset.commits[:self.heights]]
        self.sigs = [len(p) for p in prefixes]
        run.notes["signer_sets"] = {"decisions": len(prefixes),
                                    "distinct": len(set(prefixes))}
        self.app_hash = None

    def _pass(self, blocks, decide) -> ReactorSurface:
        from tendermint_tpu.blockchain.pipeline import VerifyAheadPipeline

        surface = ReactorSurface(self.ds.vals, self.ds.chain_id, blocks)
        pipe = VerifyAheadPipeline()
        while decide(lambda: pipe.process_next(surface),
                     self.sigs[len(surface.applied)]):
            if len(surface.applied) == self.heights:
                break
        return surface

    def warm_up(self) -> None:
        for _ in range(self.traffic["warmup_passes"]):
            s = self._pass(self.ds.blocks, lambda fn, _sigs: fn())
            self.app_hash = s.app_hash

    def measure(self) -> None:
        run = self.run
        run.open_window("process_next")
        while run.elapsed() < run.seconds:
            t0 = time.monotonic()
            s = self._pass(self.ds.blocks, run.decide)
            t1 = time.monotonic()
            if len(s.applied) == self.heights and s.app_hash == self.app_hash:
                run.passes.append((t0, t1, self.heights))
            else:
                run.failures.append(
                    f"pass applied {len(s.applied)} of {self.heights} heights, "
                    f"rejected {s.rejected}, app hash "
                    f"{'equal' if s.app_hash == self.app_hash else 'differs'}")
                break
        run.close_window()

    def check(self) -> None:
        from tendermint_tpu.types.block import CommitSig
        from tendermint_tpu.types.validator_set import ErrWrongSignature

        run, ds = self.run, self.ds
        fail = run.failures.append
        # (3a) one block at a time, verified synchronously before it applies
        app_hash = b"\x00" * 32
        for h in range(1, self.heights + 1):
            commit = ds.blocks[h].last_commit      # the commit FOR height h
            try:
                ds.vals.verify_commit_light(ds.chain_id, commit.block_id, h,
                                            commit)
            except Exception as e:  # noqa: BLE001
                fail(f"synchronous replay: height {h}: {type(e).__name__}: {e}")
                break
            app_hash = hashlib.sha256(app_hash + commit.block_id.hash).digest()
        if app_hash != self.app_hash:
            fail("pipeline app hash differs from the one-block-at-a-time replay")
        # (3b) a corrupted block is rejected at its height
        h = 2 + datagen.pick(run.seed, self.heights - 1, "bad-height")
        blocks = list(ds.blocks)
        carrier = copy.copy(blocks[h])
        commit = copy.copy(carrier.last_commit)
        needed = ds.vals.total_voting_power() * 2 // 3
        prefix = ds.vals.commit_light_prefix(commit, needed)
        idx = prefix[datagen.pick(run.seed, len(prefix), "bad-sig")]
        cs = commit.signatures[idx]
        flipped = bytearray(cs.signature)
        flipped[datagen.pick(run.seed, 63, "bad-byte")] ^= 0x40
        commit.signatures = list(commit.signatures)
        commit.signatures[idx] = CommitSig.new_commit(
            cs.block_id_flag, cs.validator_address, cs.timestamp, bytes(flipped))
        carrier.last_commit = commit
        blocks[h] = carrier
        s = self._pass(blocks, lambda fn, _sigs: fn())
        err = s.rejected[1] if s.rejected else None
        if (s.applied != list(range(1, h)) or s.rejected is None
                or s.rejected[0] != h or not isinstance(err, ErrWrongSignature)
                or err.index != idx or not s.punished):
            fail(f"corrupted commit for height {h}, lane {idx}: applied "
                 f"{len(s.applied)} heights, rejected {s.rejected}")
        run.notes["rejected_block"] = {"height": h, "lane": idx,
                                       "key_type": ds.key_type(idx)}
        correct.check_decisions(run, ds, [ds.vals.verify_commit_light])
