"""PR 32: the ``hub-150-churn.fastsync`` cell on the CPU, tiny: its rehearsal
traced and untraced, the two controls (a reactor surface that keeps the old
set; a reference fed the updates one height early), the refusal of a program
without the seam, the generator as a function of the seed held to the plain
reference's hashes, the reference's change-set rule against the program's on
seeded random change sets, the new per-layer readers, and what the cell
lists."""

import json
import os

import pytest

from benchmark.drivers import churnchain
from benchmark.harness import datagen, spec
from benchmark.reference import light_sync, valset_replay
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

CELL = "hub-150-churn.fastsync"
NEW = ["churn_discarded_share", "churn_changes_per_pass",
       "churn_table_build_ms", "churn_table_fill", "catchup_apply_validate_ms",
       "catchup_apply_exec_ms", "catchup_apply_update_state_ms",
       "catchup_apply_save_ms"]
APPENDED = ["catchup_apply_ms", "catchup_host_prep_ms", "catchup_queue_ms",
            "catchup_requests_per_launch", "catchup_kernel_us_per_sig",
            "catchup_device_idle_share", "catchup_lane_fill",
            "catchup_dispatch_ms", "catchup_head_wait_ms",
            "catchup_keyset_miss_share", "catchup_prep_keyset_ms",
            "catchup_dispatches_per_decision", "light_verify_kernel_roofline"]


def _rehearsal_config():
    cfg = dict(spec.Cell(CELL).config)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
    return cfg


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    """(dataset, chain, the same chain loaded again) of seed 32, rehearsal
    sizes: 24 validators, 20 appliable heights, updates at 5, 10 and 15."""
    tmp = str(tmp_path_factory.mktemp("churn"))
    cfg = _rehearsal_config()
    ds = datagen.load_or_generate("churn", cfg, 32, data_dir=tmp, workers=0)
    made = churnchain.load_or_generate("churn", ds, cfg, 32, data_dir=tmp,
                                       workers=0)
    again = churnchain.load_or_generate("churn", ds, cfg, 32, data_dir=tmp,
                                        workers=0)
    return ds, made, again


def _genesis(made):
    return [(v.pub_key.bytes(), v.power) for v in made.genesis.validators]


def _replay(made, verify_at=(), **kw):
    return valset_replay.replay(made.chain_id, _genesis(made), made.raws,
                                [b.hash for b in made.block_ids], verify_at, **kw)


# --- the rehearsal ---------------------------------------------------------------


@pytest.mark.parametrize("traced", ["0", "1"], ids=["untraced", "traced"])
def test_rehearsal_prints_the_contracts_last_line(traced):
    out = _run(["--workload", CELL, "--seed", f"320000011{traced}",
                "--seconds", "1", "--trace", traced, "--rehearse"])
    line = _last_line(out)
    notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    got = line["metrics"]
    if traced == "0":
        assert set(got) == {"catchup_blocks_per_s", "setup_s"}
    else:
        # every reader that needs no device reads on the CPU
        for name in ("churn_discarded_share", "churn_changes_per_pass",
                     "catchup_apply_validate_ms", "catchup_apply_exec_ms",
                     "catchup_apply_update_state_ms", "catchup_apply_save_ms",
                     "catchup_apply_ms", "catchup_dispatches_per_decision"):
            assert name in got, name
        assert got["churn_changes_per_pass"]["value"] == 3.0
        # 20 decisions, three changes of four entries each
        assert got["catchup_dispatches_per_decision"]["value"] == 32 / 20
        assert got["churn_discarded_share"]["value"] == 100.0 * 12 / 32
        phases = sum(got[f"catchup_apply_{p}_ms"]["value"]
                     for p in ("validate", "exec", "update_state", "save"))
        assert 0.5 * got["catchup_apply_ms"]["value"] < phases \
            <= got["catchup_apply_ms"]["value"]
        assert "catchup_blocks_per_s" not in got
    assert notes["chain"]["heights"] == 20 and notes["chain"]["updates"] == 3
    assert notes["reference"]["changes"] == 3
    rejected = notes["rejected"]
    assert len(rejected) == 2
    for r in rejected.values():
        assert r["reference"][1] == "wrong_signature"
        assert r["program"] == [r["reference"][0], "ErrWrongSignature",
                                r["reference"][2]]
        assert r["applied"] == r["reference"][0] - 1
    pipe = notes["pipeline"]
    assert pipe["dispatched"] - pipe["discarded"] == 20 * pipe["passes"]


def _surface_keeps_the_old_set(monkeypatch):
    """Guarantee (b) broken: the pipeline's entries always look as if they
    had been dispatched against the set the reactor holds now, so a
    verification made against the old set is resolved for a height of the
    new one."""
    from tendermint_tpu.blockchain.pipeline import VerifyAheadPipeline

    real = VerifyAheadPipeline._process_next

    def blind(self, reactor):
        for e in self._entries:
            e.vals_hash = reactor.state.validators.hash()
        return real(self, reactor)

    monkeypatch.setattr(VerifyAheadPipeline, "_process_next", blind)


@pytest.mark.parametrize("break_it, correct", [
    (None, True),
    (_surface_keeps_the_old_set, False),
], ids=["sound", "surface_keeps_the_old_set"])
def test_a_broken_sync_comes_out_not_correct(break_it, correct, monkeypatch,
                                             capsys):
    bench_run = spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")
    if break_it is not None:
        break_it(monkeypatch)
    rc = bench_run.main(["--workload", CELL, "--seed", "3200000113",
                         "--seconds", "0.3", "--trace", "0", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is correct, lines[-2]
    if not correct:
        assert json.loads(lines[-2])["failures"]


def test_a_program_without_the_seam_is_refused_at_load(monkeypatch, capsys):
    """The parent commit: the driver's file refuses to load there, and run.py
    exits 2 before it makes any data, traced or not."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.utils import trace

    bench_run = spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")

    def refused(needle):
        for traced in ("0", "1"):
            rc = bench_run.main(["--workload", CELL, "--seed", "3200000114",
                                 "--seconds", "0.3", "--trace", traced,
                                 "--rehearse"])
            out = capsys.readouterr()
            assert rc == bench_run.EXIT_REFUSED
            assert needle in out.err and not out.out.strip()

    with monkeypatch.context() as m:
        m.setattr(trace, "CANONICAL_SPANS", {
            k: v for k, v in trace.CANONICAL_SPANS.items()
            if k != "fastsync.discard"})
        refused("fastsync.discard")
    monkeypatch.delattr(batch, "forget_keys")
    refused("forget_keys")


# --- the generator ---------------------------------------------------------------


def test_the_chain_is_a_function_of_the_seed(chain):
    """Keys, who signs, every signature, every block and every update: made
    twice (signed, then from the cache) the bytes are the same, and another
    seed makes another chain with the same plan of changes."""
    ds, made, again = chain
    assert not made.meta["cached"] and again.meta["cached"]
    assert churnchain.content_digest(made) == churnchain.content_digest(again)
    assert made.raws == again.raws and made.updates == again.updates
    assert made.heights == 20 and sorted(made.updates) == [5, 10, 15]
    # join, reweight, join; a join's second update is the leaver's power 0
    assert [len(u) for _h, u in sorted(made.updates.items())] == [2, 3, 2]
    sitting = {v.pub_key.bytes() for v in ds.vals.validators}
    for h in (5, 10, 15):
        if h == 10:
            assert all(key in sitting and power > 0
                       for key, power in made.updates[h])
            continue
        (joiner, power), (leaver, zero) = made.updates[h]
        assert joiner not in sitting and leaver in sitting
        assert power > 0 and zero == 0
        sitting = sitting - {leaver} | {joiner}
    cfg = _rehearsal_config()
    other_ds = datagen.load_or_generate(
        "churn-other", cfg, 33, data_dir=os.path.dirname(made.meta["path"]),
        workers=0)
    other = churnchain.load_or_generate(
        "churn-other", other_ds, cfg, 33,
        data_dir=os.path.dirname(made.meta["path"]), workers=0)
    assert other.raws != made.raws
    assert other.prefix_sigs == made.prefix_sigs      # pattern_seed: same work
    assert [[p for _k, p in u] for _h, u in sorted(other.updates.items())] \
        == [[p for _k, p in u] for _h, u in sorted(made.updates.items())]


def test_every_header_names_the_references_sets(chain):
    """The plain reference replays the block bytes by the H+2 rule, every
    light prefix verified signature by signature, and accepts the chain; the
    hashes in the program's headers are those of the reference's own sets,
    and the sets change exactly two heights after each update."""
    _ds, made, _again = chain
    ref = _replay(made, verify_at=range(1, made.heights + 1))
    assert ref["refused"] is None
    assert ref["applied"] == list(range(1, made.heights + 1))
    assert ref["changes"] == [7, 12, 17]
    assert ref["last_height_validators_changed"] == 17
    assert [len(ref["prefixes"][h]) for h in ref["applied"]] == made.prefix_sigs
    for k, block in enumerate(made.blocks[:-1]):
        h = k + 1
        assert block.header.validators_hash == ref["set_hashes"][h] \
            == light_sync.validators_hash(ref["sets"][h]), h
        assert block.header.next_validators_hash == ref["set_hashes"][h + 1], h
    assert ref["app_hash"] == made.final["app_hash"]
    assert light_sync.validators_hash(ref["validators"]) \
        == made.final["validators_hash"]
    assert light_sync.validators_hash(ref["next_validators"]) \
        == made.final["next_validators_hash"]


def test_a_reference_fed_the_updates_a_height_early_disagrees(chain):
    """The control of the H+2 rule: with the updates of block H in force at
    H+1 the reference's sets part from the chain's at the first update."""
    _ds, made, _again = chain
    early = _replay(made, delay=1)
    assert early["refused"] == (6, "validators_hash", None)
    assert early["applied"] == [1, 2, 3, 4, 5]


def test_the_references_sign_bytes_are_the_programs(chain):
    _ds, made, _again = chain
    for block in (made.blocks[1], made.blocks[12]):
        commit = valset_replay.parse_block(block.marshal())["last_commit"]
        for i, cs in enumerate(block.last_commit.signatures):
            if not cs.absent():
                assert valset_replay.vote_sign_bytes(made.chain_id, commit, i) \
                    == block.last_commit.vote_sign_bytes(made.chain_id, i)
    assert any(cs.block_id_flag == valset_replay.NIL
               for b in made.blocks[1:] for cs in b.last_commit.signatures)


def _program_set(triples):
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    return ValidatorSet([Validator.new(ed25519.PubKey(k), p)
                         for _a, k, p in triples])


def _triples(vals):
    return [(v.address, v.pub_key.bytes(), v.voting_power)
            for v in vals.validators]


@pytest.mark.parametrize("kind", ["join", "leave", "reweight", "two_in_one_block",
                                  "mixed"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_references_change_set_rule_is_the_programs(kind, seed):
    """apply_updates against ValidatorSet.update_with_change_set on seeded
    random change sets: the same members, powers, order and hash."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.validator import Validator

    def key(*path):
        return ed25519.gen_priv_key(datagen.derive(seed, kind, *path)
                                    ).pub_key().bytes()

    def power(*path):
        return 1 + datagen.pick(seed, 1000, kind, "power", *path)

    n = 12
    ref = valset_replay.ordered(
        (valset_replay.address(k), k, power("genesis", i))
        for i, k in enumerate(key("genesis", i) for i in range(n)))
    vals = _program_set(ref)
    assert _triples(vals) == ref
    for step in range(6):
        sitting = [k for _a, k, _p in ref]
        pick = lambda *p: datagen.pick(seed, len(sitting), kind, step, *p)  # noqa: E731
        updates = {
            "join": [(key("new", step), power(step))],
            "leave": [(sitting[pick("out")], 0)],
            "reweight": [(sitting[pick("rw")], power(step))],
            "two_in_one_block": [(key("new", step), power(step)),
                                 (sitting[-1], 0)],
            "mixed": [(key("new", step), power(step)),
                      (sitting[pick("out")], 0),
                      (sitting[(pick("out") + 1) % len(sitting)], power(step, 2))],
        }[kind]
        ref = valset_replay.apply_updates(ref, updates)
        vals.update_with_change_set(
            [Validator.new(ed25519.PubKey(k), p) for k, p in updates])
        assert _triples(vals) == ref, (kind, step)
        assert vals.hash() == light_sync.validators_hash(ref)


@pytest.mark.parametrize("updates, why", [
    (lambda s: [(s[0], 5), (s[0], 6)], "duplicate"),
    (lambda s: [(b"\x07" * 32, 0)], "failed to find"),
    (lambda s: [(s[0], -1)], "negative"),
], ids=["duplicate", "remove_a_stranger", "negative_power"])
def test_the_reference_refuses_what_the_program_refuses(updates, why):
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSetError

    keys = [ed25519.gen_priv_key(bytes([i + 1]) * 32).pub_key().bytes()
            for i in range(4)]
    ref = valset_replay.ordered((valset_replay.address(k), k, 10) for k in keys)
    change = updates(keys)
    with pytest.raises(valset_replay.ChangeSetError, match=why):
        valset_replay.apply_updates(ref, change)
    with pytest.raises(ValidatorSetError):
        _program_set(ref).update_with_change_set(
            [Validator.new(ed25519.PubKey(k), p) for k, p in change])


# --- the new readers ---------------------------------------------------------------


def _reader(name):
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     name + ".py"), "read").read


def _churn_run(monkeypatch):
    from tendermint_tpu.utils import trace

    spans_ = [
        _span("fastsync.discard", 10.1, 0.0, entries=4, reason="valset", height=7),
        _span("fastsync.discard", 10.3, 0.0, entries=4, reason="valset", height=12),
        _span("fastsync.discard", 10.5, 0.0, entries=2, reason="pool", height=14),
        _span("apply.validate", 10.0, 0.030), _span("apply.validate", 10.2, 0.010),
        _span("apply.exec", 10.04, 0.008), _span("apply.update_state", 10.05, 0.002),
        _span("apply.save", 10.06, 0.006),
    ]
    run = _synthetic_run(spans_)
    run.passes = [(10.0, 10.4, 2), (10.4, 10.8, 2)]
    run.notes = {"pipeline": {"dispatched": 50, "discarded": 10, "passes": 2}}
    ring = trace.Tracer(name="startup", cold=True)
    monkeypatch.setattr(trace, "STARTUP", ring)
    t0 = run.window[0]
    ring.record("startup.table_build", 0.5, start=t0 - 5.0, keys=45, rows=256)
    ring.record("startup.table_build", 0.030, start=t0 + 0.1, keys=44, rows=256)
    ring.record("startup.table_build", 0.022, start=t0 + 0.2, keys=1, rows=256)
    ring.record("startup.table_build", 0.022, start=t0 + 0.3, keys=1, rows=256)
    return run


def test_the_churn_readers_on_synthetic_spans(monkeypatch):
    run = _churn_run(monkeypatch)
    n = len(run.decisions)
    want = {
        "churn_discarded_share": 20.0,
        "churn_changes_per_pass": 1.0,
        "churn_table_build_ms": 74.0 / 46,
        "churn_table_fill": 100.0 * 46 / 768,
        "catchup_apply_validate_ms": 40.0 / n,
        "catchup_apply_exec_ms": 8.0 / n,
        "catchup_apply_update_state_ms": 2.0 / n,
        "catchup_apply_save_ms": 6.0 / n,
    }
    assert set(want) == set(NEW)
    for name, value in want.items():
        assert _reader(name)(run) == pytest.approx(value), name


def test_the_churn_readers_read_nothing_from_a_program_without_the_spans(
        monkeypatch):
    """Laid over the parent commit: no such span, no rows tag on the ring's
    builds, and no driver wrote the counters' note."""
    from tendermint_tpu.utils import trace

    run = _churn_run(monkeypatch)
    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items()
        if k != "fastsync.discard" and not (
            k.startswith("apply.") and k != "apply.post_commit")})
    ring = trace.Tracer(name="startup", cold=True)
    monkeypatch.setattr(trace, "STARTUP", ring)
    ring.record("startup.table_build", 0.022, start=run.window[0] + 0.2, keys=1)
    run.notes = {}
    for name in NEW:
        assert _reader(name)(run) is None, name


def test_the_cell_lists_what_issue_32_says():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hub-150-churn", "churn-sync", 1)
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert listed == set(NEW) | set(APPENDED) | {"catchup_blocks_per_s"}
    # by membership, never by position (D14): a later PR may list a cell
    # of its own after this one
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"][0] == CELL
            assert m["moves"] == "catchup_blocks_per_s"
        if m["name"] in APPENDED:
            assert m["workloads"].index(CELL) > 0
    config, hub = spec.Cell(CELL).config, spec.Cell("hub-150.fastsync").config
    assert config["architecture"] is None
    for key in ("validators", "voting_power", "absent_share", "nil_share",
                "pattern_seed"):
        assert config["dataset"][key] == hub["dataset"][key], key
    assert config["dataset"]["chain_heights"] == 201
    assert config["dataset"]["update_every"] == 5
    assert len(churnchain.update_heights(config["dataset"])) == 39
    assert list(config["reduced"]) == ["heights"]
    assert len(config["guarantees"]) == 6
    assert spec.Cell(CELL).traffic["warmup_passes"] == 1
    with open(os.path.join(spec.BENCH_DIR, "reference", "valset_replay.py")) as f:
        assert "tendermint_tpu" not in f.read().replace(
            "tendermint_tpu/abci/kvstore.py", "")
