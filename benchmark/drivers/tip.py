"""The ``tip`` mix: one caller verifies the next height's commit through a
``ValidatorSet.verify_commit*`` entry point, waits for the answer, and asks
again. The pool of pre-signed heights is cycled in order."""

from __future__ import annotations

from benchmark.harness import correct


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        self.run, self.ds, self.traffic = run, dataset, traffic
        self.verify = getattr(dataset.vals, traffic["entry_point"])
        self.sigs = [sum(1 for cs in c.signatures if not cs.absent())
                     for c in dataset.commits]
        self._next = 0

    def _decide_next(self):
        k = self._next % len(self.ds.commits)
        self._next += 1
        ds, commit = self.ds, self.ds.commits[k]
        return self.run.decide(
            lambda: self.verify(ds.chain_id, commit.block_id, commit.height,
                                commit), self.sigs[k])

    def warm_up(self) -> None:
        for commit in self.ds.commits[:self.traffic["warmup_decisions"]]:
            self.verify(self.ds.chain_id, commit.block_id, commit.height, commit)

    def measure(self) -> None:
        run = self.run
        run.open_window("decision")
        while run.elapsed() < run.seconds:
            self._decide_next()
        run.close_window()

    def check(self) -> None:
        correct.check_decisions(self.run, self.ds, [self.verify])
