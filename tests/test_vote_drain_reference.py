"""PR 30: the state machine's batched vote drain, fed wire bytes through
``ConsensusReactor.receive`` by the ``vote-drain`` driver, against the
benchmark's plain reference (benchmark/reference/vote_tally.py) delivery by
delivery: 24 validators, seeded, on the CPU. Streams with three copies of
every vote, each of the five corruptions alone, a height whose +2/3 falls in
the middle of a drain (the rest of it become late precommits) and a peer
queue too small for the stream (what it admits is still tallied as the
reference tallies it, and the driver says that live votes were shed)."""

import ast
import os
import shutil

import pytest

from benchmark.harness import datagen, record, spec
from benchmark.reference import vote_tally

CELL = "localnet-5k.vote-drain"
SEED = 3000000131


def _driver(tmp_path_factory, **traffic):
    cell = spec.Cell(CELL)
    cfg = dict(cell.config)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
    data = str(tmp_path_factory.mktemp("data"))
    ds = datagen.load_or_generate("drain-test", cfg, SEED, data_dir=data,
                                  workers=0)
    run = record.Run(cell=cell, seed=SEED, seconds=1.0, traced=False,
                     rehearse=True)
    return cell.driver.Driver(run, ds, {**cell.traffic, "height_timeout_s": 20,
                                        **traffic}), cell.driver


@pytest.fixture(scope="module")
def drv(tmp_path_factory):
    driver, module = _driver(tmp_path_factory)
    yield driver, module
    shutil.rmtree(driver.wal_root, ignore_errors=True)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(spec.BENCH_DIR, "reference", "vote_tally.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert names and all(n in ("struct", "__future__")
                         or n.startswith("benchmark.reference") for n in names)


def _one_pass(driver, module, heights, kinds=None):
    """A node from genesis fed ``heights`` heights, then held to the
    reference -> (record, the reference's tally, failures)."""
    plan = module.Corruptions(driver, kinds) if kinds is not None else None
    rec = {**driver._pass(heights, edit=plan.edit if plan else None),
           "name": "test pass", "sample": True}
    if plan is not None:
        rec["plan"] = plan
    failures: list = []
    validators = [(v.address, v.pub_key.bytes(), v.voting_power)
                  for v in driver.ds.vals.validators]
    stream, _counts = driver._wal_stream(rec)
    assert all(d["check"] for d in stream if d["kind"] == "vote")
    want = vote_tally.tally(validators, stream)
    driver._check_pass(rec, failures.append)
    shutil.rmtree(rec["node"].wal_dir, ignore_errors=True)
    return rec, want, failures


CORRUPTION = {"second_copy_flipped": vote_tally.INVALID,
              "first_copy_flipped": vote_tally.INVALID,
              "wrong_address": vote_tally.REJECTED,
              "other_block": vote_tally.CONFLICT,
              "future_height": vote_tally.IGNORED}


@pytest.mark.parametrize("case", ["copies", *CORRUPTION, "commits_mid_drain",
                                  "full_queue"])
def test_the_drain_agrees_with_the_plain_reference(case, drv, tmp_path_factory,
                                                   monkeypatch, request):
    driver, module = drv
    if case == "full_queue":
        from tendermint_tpu.consensus import state_machine

        monkeypatch.setattr(state_machine, "MSG_QUEUE_MIN", 40)
        monkeypatch.setattr(state_machine, "MSG_QUEUE_PER_VALIDATOR", 0)
        driver, module = _driver(tmp_path_factory, height_timeout_s=2)
        request.addfinalizer(
            lambda: shutil.rmtree(driver.wal_root, ignore_errors=True))
    kinds = (case,) if case in CORRUPTION else None
    rec, want, failures = _one_pass(driver, module, 2, kinds)
    node = rec["node"]
    # delivery by delivery: what the node counted, whom it sanctioned and
    # what it reported are the reference's, in the reference's order
    assert node.counted == want["counted"]
    assert node.conflicts == want["conflicts"]
    sanctioned = {k.split(":")[0]: n for k, n in
                  node.switch.scoreboard.describe()["offenses"].items()}
    assert sanctioned == want["invalid_by_peer"]
    verdicts = set(want["verdicts"])
    if case == "full_queue":
        shed = node.cs.shed_counts()
        assert sum(shed["live"].values()) > 0
        assert any("were shed" in f for f in failures), failures
        return
    assert failures == []
    assert rec["heights"] == 2 and len(want["commits"]) == 2
    assert {vote_tally.COUNTED, vote_tally.DUPLICATE} <= verdicts
    if kinds:
        assert CORRUPTION[case] in verdicts
        assert [n["reference"] for n in rec["plan"].notes] == [
            [CORRUPTION[case]]] * 2          # a prevote and a precommit
        assert sum(want["invalid_by_peer"].values()) == (
            2 if CORRUPTION[case] == vote_tally.INVALID else 0)
        assert len(want["conflicts"]) == (2 if case == "other_block" else 0)
    if case == "commits_mid_drain":
        # the precommits behind the one that tipped +2/3 were counted too:
        # into the last commit, while the node waited in NewHeight
        first = want["commits"][0]
        late = [c for c in want["counted"]
                if c[0] == vote_tally.PRECOMMIT and c[1] == first["height"]]
        assert len(late) > len(first["signers"])
        seen = node.block_store.load_seen_commit(first["height"])
        assert [i for i, cs in enumerate(seen.signatures)
                if not cs.absent()] == first["signers"]
