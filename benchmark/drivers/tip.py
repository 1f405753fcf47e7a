"""The ``tip`` mix: one caller verifies the next height's commit through a
``ValidatorSet.verify_commit*`` entry point, waits for the answer, and asks
again. The pool of pre-signed heights is cycled in order; where the
configuration has an ``absent_share``, each decision presents its pooled
height with absentees of its own (``datagen.presented``), copied before the
timed call, so no two decisions of a run show the same signer set."""

from __future__ import annotations

import hashlib

from benchmark.harness import correct, datagen


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        self.run, self.ds, self.traffic = run, dataset, traffic
        self.verify = getattr(dataset.vals, traffic["entry_point"])
        self.sigs = [sum(1 for cs in c.signatures if not cs.absent())
                     for c in dataset.commits]
        self._next = 0
        self._signer_sets: set[bytes] = set()

    def _present(self, k: int, *scope):
        """-> (the commit for pooled height k as this decision shows it, the
        signatures it holds)."""
        commit, absent = datagen.presented(self.ds, self.run.seed, k, *scope)
        if absent is None:
            return commit, self.sigs[k]
        self._signer_sets.add(hashlib.sha256(absent.tobytes()).digest())
        return commit, len(absent) - int(absent.sum())

    def _decide_next(self):
        k = self._next % len(self.ds.commits)
        commit, sigs = self._present(k, self._next)
        self._next += 1
        ds = self.ds
        return self.run.decide(
            lambda: self.verify(ds.chain_id, commit.block_id, commit.height,
                                commit), sigs)

    def warm_up(self) -> None:
        for k in range(min(self.traffic["warmup_decisions"],
                           len(self.ds.commits))):
            commit, _sigs = self._present(k, "warmup", k)
            self.verify(self.ds.chain_id, commit.block_id, commit.height, commit)

    def measure(self) -> None:
        run = self.run
        self._signer_sets.clear()
        run.open_window("decision")
        while run.elapsed() < run.seconds:
            self._decide_next()
        run.close_window()
        if self.ds.absent_per_decision:
            run.notes["signer_sets"] = {"decisions": self._next,
                                        "distinct": len(self._signer_sets)}

    def check(self) -> None:
        correct.check_decisions(self.run, self.ds, [self.verify])
