"""PR 50: the ``fastsync-1k-mixed-node.fastsync`` cell on the CPU, tiny: its
rehearsal untraced and traced (both corruptions refused where
``reference/mixed_commit.py`` refuses them, by kind and slot), syncs broken
on purpose that come out not correct, the refusal of a program without the
seam's counters, the chain as a function of the seed (one digest pinned),
``mixed_commit.py`` against the program's two entry points and against
``correct.check_decisions``' references on a mixed pool, the new per-layer
readers, and what the cell lists, by what each entry protects and by no
position in a list."""

import functools
import json
import os

import pytest

from benchmark.drivers import churnchain, mixedchain
from benchmark.harness import correct, datagen, record, spec
from benchmark.reference import mixed_commit, valset_replay
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

CELL = "fastsync-1k-mixed-node.fastsync"
CONFIG = "fastsync-1k-mixed-node"
NEW = ["mixed_lastcommit_exposed_ms", "mixed_speculative_fresh_share",
       "mixed_state_save_ms", "mixed_sr_host_route_share"]
# ISSUE 50 names three more (mixed_state_bytes_per_block,
# mixed_sigs_verified_per_commit_sig, mixed_sr_kernel_share): the contract
# allows 128 per-layer metrics and the benchmark had 124, so four were added
# and the three that read a constant of the configuration were left out
PER_LAYER_LIMIT = 128
APPENDED = ["catchup_apply_ms", "catchup_host_prep_ms", "catchup_queue_ms",
            "catchup_requests_per_launch", "catchup_kernel_us_per_sig",
            "catchup_device_idle_share", "catchup_lane_fill",
            "catchup_dispatch_ms", "catchup_head_wait_ms",
            "catchup_keyset_miss_share", "catchup_prep_keyset_ms",
            "catchup_dispatches_per_decision", "light_verify_kernel_roofline",
            "catchup_apply_validate_ms", "catchup_apply_exec_ms",
            "catchup_apply_update_state_ms", "catchup_apply_save_ms",
            "catchup_host_prep_cpu_ms",
            "full_part_set_ms", "full_block_save_ms", "full_post_commit_ms",
            "full_index_lag_ms", "full_backlog_max_heights", "full_body_share",
            "full_cpu_sync_share", "full_cpu_post_commit_share",
            "full_cpu_indexer_share", "full_cpu_process_share"]
# per transaction, and the chain carries none: nothing to divide by
LEFT_OUT = ["full_deliver_us_per_tx", "full_index_us_per_tx",
            "full_index_cpu_us_per_tx"]
CORRUPTIONS = {
    "flipped ed25519 bit inside a light prefix": "light",
    "flipped sr25519 bit outside a light prefix, in a signed block": "full"}


def _rehearsal_config():
    cfg = dict(spec.Cell(CELL).config)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
    return cfg


def _made(tmp, seed):
    cfg = _rehearsal_config()
    ds = datagen.load_or_generate("mixed", cfg, seed, data_dir=tmp, workers=0)
    return ds, cfg, mixedchain.load_or_generate("mixed", ds, cfg, seed,
                                                data_dir=tmp, workers=0)


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    """(dataset, chain, the same chain loaded again) of seed 50, rehearsal
    sizes: 21 + 9 validators, 7 appliable heights."""
    tmp = str(tmp_path_factory.mktemp("mixed"))
    ds, cfg, made = _made(tmp, 50)
    again = mixedchain.load_or_generate("mixed", ds, cfg, 50, data_dir=tmp,
                                        workers=0)
    return ds, made, again


def _genesis(made):
    return [(v.pub_key.type, v.pub_key.bytes(), v.power)
            for v in made.genesis.validators]


def _replay(made, raws=None, hashes=None, at=()):
    return mixed_commit.replay(
        made.chain_id, _genesis(made), made.raws if raws is None else raws,
        hashes or [b.hash for b in made.block_ids], light_at=at, full_at=at)


def _bench_run():
    return spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")


# --- the rehearsal ---------------------------------------------------------------


@pytest.mark.parametrize("traced", ["0", "1"], ids=["untraced", "traced"])
def test_rehearsal_prints_the_contracts_last_line(traced):
    out = _run(["--workload", CELL, "--seed", f"500000011{traced}",
                "--seconds", "1", "--trace", traced, "--rehearse"])
    line = _last_line(out)
    notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["failures"]["n"] == 0
    assert line["failures"]["compared"]["reference_heights"] == [4, 4]
    got = line["metrics"]
    if traced == "0":
        assert set(got) == {"catchup_blocks_per_s", "setup_s"}
    else:
        # every reader that needs neither the device nor ten heights of one
        # pass (the census marks) reads on the CPU
        for name in NEW + ["catchup_apply_ms", "catchup_apply_validate_ms",
                               "catchup_apply_save_ms", "full_part_set_ms",
                               "full_block_save_ms", "full_body_share",
                               "catchup_dispatches_per_decision"]:
            assert name in got, name
        for name in LEFT_OUT:
            assert name not in got
        assert got["catchup_dispatches_per_decision"]["value"] == 1.0
        assert got["mixed_speculative_fresh_share"]["value"] == 100.0
        # 30 validators never leave the host verifier
        assert got["mixed_sr_host_route_share"]["value"] == 100.0
        assert got["mixed_state_save_ms"]["value"] \
            <= got["catchup_apply_save_ms"]["value"]
        assert got["mixed_lastcommit_exposed_ms"]["value"] \
            <= got["catchup_apply_validate_ms"]["value"]
        assert "catchup_blocks_per_s" not in got
    chain = notes["chain"]
    assert chain["heights"] == 7 and chain["data_bytes_a_block"] == 0
    assert chain["validators"] == {"ed25519": 21, "sr25519": 9}
    assert chain["light_prefix_sigs"] == [21, 21]
    assert chain["signatures"]["sr25519"] > 0
    passes = notes["full"]["passes"]
    assert passes >= 1
    assert notes["full"]["counters"]["heights_indexed"] == 7
    assert notes["full"]["counters"]["txs_indexed"] == 0
    assert notes["full"]["pipeline"] == {"dispatched": 7 * passes,
                                         "discarded": 0}
    # a handle for every height but the first, every one consumed fresh
    assert notes["mixed"]["seam"] == {"dispatched": 6 * passes,
                                      "fresh": 6 * passes, "stale": 0}
    assert len(notes["reference"]["heights_verified"]) == 4
    # both corrupted chains: refused where the reference refuses them, by
    # its check, at its slot (and, inside check, the files read back: a pass
    # that failed its read-back would have made the run not correct)
    rejected = notes["rejected"]
    assert set(rejected) == set(CORRUPTIONS)
    for name, by in CORRUPTIONS.items():
        r = rejected[name]
        (height, kind, slot), check = r["reference"]
        assert (kind, check) == ("wrong_signature", by)
        assert r["applied"] == height - 1 >= 1
        if by == "light":
            assert r["program"][:4] == [height, "ErrWrongSignature", slot,
                                        ["pA", "pB"]]
            assert r["scored"] == ["pA", "pB"] and r["raised"] == "None"
        else:
            assert r["program"] is None and r["scored"] == []
            assert r["raised"].startswith(
                f"ErrWrongSignature('wrong signature (#{slot})")
    assert not os.path.exists(os.path.join(
        spec.BENCH_DIR, ".homes", f"{CELL}-500000011{traced}-rehearse"))


def _the_seam_is_bypassed(monkeypatch):
    """Guarantee (a) broken: no handle is dispatched ahead, so every
    LastCommit is verified synchronously inside validate_block."""
    from tendermint_tpu.state.execution import BlockExecutor

    monkeypatch.setattr(BlockExecutor, "dispatch_commit_verify",
                        lambda self, state, block: None)


def _the_full_check_accepts_anything(monkeypatch):
    """Guarantee (d) broken: verify_commit's deferred twin resolves to
    accept whatever the signatures are."""
    from tendermint_tpu.types.validator_set import (
        PendingCommitVerify,
        ValidatorSet,
    )

    monkeypatch.setattr(
        ValidatorSet, "verify_commit_async",
        lambda self, *a, **kw: PendingCommitVerify(finalize=lambda bits: None))


def _the_state_is_saved_a_height_late(monkeypatch):
    """Guarantee (c) broken: the last height's state never reaches the
    state store."""
    from tendermint_tpu.state.store import StateStore

    real = StateStore.save

    def late(self, state):
        if state.last_block_height != 7:
            real(self, state)

    monkeypatch.setattr(StateStore, "save", late)


@pytest.mark.parametrize("break_it, check", [
    (None, None),
    (_the_seam_is_bypassed, "a"),
    (_the_full_check_accepts_anything, "d"),
    (_the_state_is_saved_a_height_late, "c"),
], ids=["sound", "the_seam_is_bypassed", "the_full_check_accepts_anything",
        "the_state_is_saved_a_height_late"])
def test_a_broken_sync_comes_out_not_correct(break_it, check, monkeypatch,
                                             capsys):
    bench_run = _bench_run()
    if break_it is not None:
        break_it(monkeypatch)
    rc = bench_run.main(["--workload", CELL, "--seed", "5000000113",
                         "--seconds", "0.3", "--trace", "0", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is (check is None), lines[-2]
    if check is not None:
        assert check in line["failures"]["by_check"], line["failures"]


def test_a_program_without_the_seams_counters_is_refused_at_load(monkeypatch,
                                                                  capsys):
    """The parent commit: the driver's file refuses to load there, and run.py
    exits 2 before it makes any data, traced or not."""
    from tendermint_tpu.utils import trace

    bench_run = _bench_run()
    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items() if k != "state.save"})
    for traced in ("0", "1"):
        rc = bench_run.main(["--workload", CELL, "--seed", "5000000114",
                             "--seconds", "0.3", "--trace", traced,
                             "--rehearse"])
        out = capsys.readouterr()
        assert rc == bench_run.EXIT_REFUSED
        assert "state.save" in out.err and not out.out.strip()


# --- the generator ---------------------------------------------------------------


def test_the_chain_is_a_function_of_the_seed(chain, tmp_path):
    ds, made, again = chain
    assert made.meta["cached"] is False and again.meta["cached"] is True
    assert again.raws == made.raws
    assert churnchain.content_digest(again) == churnchain.content_digest(made)
    # every signature and the last block's hash, for one seed: a change to
    # the generator, the signers or the program's encodings shows here
    assert churnchain.content_digest(made) == DIGEST_OF_SEED_50
    _ds, _cfg, other = _made(str(tmp_path), 51)
    assert churnchain.content_digest(other) != churnchain.content_digest(made)
    assert made.heights == 7 and len(made.raws) == 8
    assert made.txs_per_block == 0
    kinds = mixedchain.key_types(made)
    assert (kinds.count("ed25519"), kinds.count("sr25519")) == (21, 9)
    signed = made.sigs.any(axis=2)
    assert made.meta["signatures"] == {
        kind: int(signed[:, [k == kind for k in kinds]].sum())
        for kind in ("ed25519", "sr25519")}
    # the off-curve validator never signs; somebody is absent somewhere
    assert not signed[:, ds.off_idx].any()
    assert signed.sum() < 7 * 29


def test_the_set_is_the_mixed_replay_cells_key_for_key():
    """The same seed gives the same 1,000 keys in both configurations: the
    dataset's validator section is fastsync-1k-mixed's, and the generator
    derives a key from the seed, the key type and its number alone."""
    node, replay = (spec.Cell(CELL).config["dataset"],
                    spec.Cell("fastsync-1k-mixed.replay").config["dataset"])
    for key in ("validators", "voting_power"):
        assert node[key] == replay[key]
    assert node["validators"] == {"ed25519": 700, "sr25519": 300}


# --- the reference against the program -------------------------------------------


def test_the_reference_replays_what_the_program_made(chain):
    from tendermint_tpu.types.block import Block
    from tendermint_tpu.types.part_set import PartSet

    ds, made, _again = chain
    ref = _replay(made, at={2, 5})
    assert ref["refused"] is None and ref["applied"] == list(range(1, 8))
    assert ref["app_hash"] == made.final["app_hash"]
    assert ref["last_results_hash"] == made.final["last_results_hash"]
    assert ref["validators_hash"] == ds.vals.hash()
    assert [len(ref["prefixes"][h]) for h in ref["applied"]] == made.prefix_sigs
    signed = made.sigs.any(axis=2)
    for h in range(1, 7):
        assert ref["full_slots"][h] == [int(i) for i in signed[h - 1].nonzero()[0]]
    for h in ref["applied"]:
        block = Block.unmarshal(made.raws[h - 1])
        assert ref["headers"][h] == (block.data.hash(),
                                     block.header.last_results_hash,
                                     block.header.app_hash)
        psh = PartSet.from_data(made.raws[h - 1]).header()
        assert ref["part_set_headers"][h] == (psh.total, psh.hash)
        assert ref["txs"][h] == 0


@pytest.mark.parametrize("kind", ["ed25519", "sr25519"])
def test_simple_validator_is_the_programs(kind):
    from tendermint_tpu.crypto import ed25519, sr25519
    from tendermint_tpu.types.validator import Validator

    priv = (ed25519 if kind == "ed25519" else sr25519).gen_priv_key(b"\x07" * 32)
    val = Validator.new(priv.pub_key(), 12345)
    assert mixed_commit.simple_validator(kind, priv.pub_key().bytes(), 12345) \
        == val.bytes()


@pytest.mark.parametrize("mode", ["light", "full"])
def test_the_reference_decides_a_corrupted_pool_commit_as_the_program(
        chain, mode):
    """``correct.check_decisions``' corrupted commit of a mixed pool (a
    flipped bit, S >= L, a truncated signature, an off-curve key, one on an
    sr25519 lane and one on a nil vote): ``mixed_commit.decide`` names the
    slot the program's entry point names, and accepts the clean commit."""
    from tendermint_tpu.types.validator_set import ErrWrongSignature

    ds, _made_chain, _again = chain
    bad, corrupted = correct.corrupted_commit(ds, 50)
    validators = [(v.address, v.pub_key.type, v.pub_key.bytes(),
                   v.voting_power) for v in ds.vals.validators]

    def parsed(commit):
        from tendermint_tpu.types.block import Block, Header

        return valset_replay.parse_block(Block(
            header=Header(height=commit.height + 1),
            last_commit=commit).marshal())["last_commit"]

    def decide(commit):
        return mixed_commit.decide(ds.chain_id, validators, parsed(commit),
                                   commit.height, commit.block_id.hash,
                                   full=mode == "full")

    verify = (ds.vals.verify_commit if mode == "full"
              else ds.vals.verify_commit_light)
    with pytest.raises(ErrWrongSignature) as e:
        verify(ds.chain_id, bad.block_id, bad.height, bad)
    verdict, slots = decide(bad)
    assert verdict == ("wrong_signature", e.value.index)
    assert e.value.index in corrupted
    # each corrupted lane, alone: the reference's verdict on the lane is
    # check_decisions' own reference's
    for i in corrupted:
        assert correct.reference_lane(ds, bad, i) is False
    clean, _absent = datagen.presented(ds, 50, bad.height - 1, "check")
    verify(ds.chain_id, clean.block_id, clean.height, clean)
    verdict, slots = decide(clean)
    assert verdict is None
    present = [i for i, cs in enumerate(clean.signatures) if not cs.absent()]
    assert slots == (present if mode == "full" else ds.vals.commit_light_prefix(
        clean, ds.vals.total_voting_power() * 2 // 3))


def test_the_reference_refuses_the_structure_first(chain):
    ds, made, _again = chain
    validators = mixed_commit.ordered(
        (valset_replay.address(key), kind, key, power)
        for kind, key, power in _genesis(made))
    commit = valset_replay.parse_block(made.raws[3])["last_commit"]   # for 3
    ok = (made.chain_id, validators, commit, 3, made.block_ids[2].hash)
    for full in (False, True):
        assert mixed_commit.decide(*ok, full=full)[0] is None
        assert mixed_commit.decide(*ok[:3], 4, ok[4], full=full)[0] \
            == ("commit_height", None)
        assert mixed_commit.decide(*ok[:4], b"\x00" * 32, full=full)[0] \
            == ("commit_block_id", None)
        assert mixed_commit.decide(ok[0], validators[:-1], *ok[2:],
                                   full=full)[0] == ("commit_size", None)
    # too few votes for the block: everybody but seven validators absent
    few = dict(commit, slots=[
        s if i < 7 else {"flag": valset_replay.ABSENT, "address": b"",
                         "timestamp": b"", "signature": b""}
        for i, s in enumerate(commit["slots"])])
    for full in (False, True):
        assert mixed_commit.decide(*ok[:2], few, *ok[3:], full=full,
                                   verify=False)[0] == ("not_enough_power", None)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(spec.BENCH_DIR, "reference", "mixed_commit.py")) as f:
        source = f.read()
    assert "tendermint_tpu" not in source
    for mine in ("ed25519_ref", "sr25519_ref", "light_prefix", "block_replay"):
        assert mine in source


# --- the corrupted chains ----------------------------------------------------------


def _driver(seed, tmp_path, monkeypatch):
    """The cell's driver at rehearsal sizes, its data under ``tmp_path``."""
    cell = spec.Cell(CELL)
    cfg = _rehearsal_config()
    ds = datagen.load_or_generate("mixed-driver", cfg, seed,
                                  data_dir=str(tmp_path), workers=0)
    run = record.Run(cell=cell, seed=seed, seconds=0.1, traced=False,
                     rehearse=True)
    monkeypatch.setattr(mixedchain, "load_or_generate", functools.partial(
        mixedchain.load_or_generate, data_dir=str(tmp_path), workers=0))
    return cell.driver.Driver(run, ds, cell.traffic)


def test_a_flipped_bit_alone_never_reaches_the_full_check(tmp_path,
                                                          monkeypatch):
    """Why the second corruption signs block h+2's commit anew: with the
    flipped bit alone block h+1's bytes miss the part-set header the honest
    commit for h+1 signed, and the reference (like the program) refuses at
    the light check of h+1. The driver's chain reaches the full check."""
    driver = _driver(52, tmp_path, monkeypatch)
    ref = driver._reference(driver.chain.raws, ())
    cases = driver._corruptions(ref)
    assert set(cases) == set(CORRUPTIONS)
    case = cases["flipped sr25519 bit outside a light prefix, in a signed block"]
    h, slot = case["at"] - 1, case["slot"]
    assert driver.kinds[slot] == "sr25519"
    assert slot in ref["full_slots"][h] and slot not in ref["prefixes"][h]
    bad = driver._reference(case["raws"], {h}, case["hashes"])
    assert (bad["refused"], bad["refused_by"]) == (
        (h + 1, "wrong_signature", slot), "full")
    assert bad["applied"] == list(range(1, h + 1))
    # the flipped bit without the new commit: refused one check earlier
    alone = list(driver.chain.raws)
    alone[h] = case["raws"][h]
    hashes = [b.hash for b in driver.chain.block_ids]
    hashes[h] = case["hashes"][h]
    early = driver._reference(alone, {h}, hashes)
    assert (early["refused"], early["refused_by"]) == (
        (h + 1, "commit_block_id", None), "light")
    # the first corruption sits on an ed25519 lane inside the prefix
    case = cases["flipped ed25519 bit inside a light prefix"]
    assert driver.kinds[case["slot"]] == "ed25519"
    assert case["slot"] in ref["prefixes"][case["at"]]
    bad = driver._reference(case["raws"], {case["at"]}, case["hashes"])
    assert (bad["refused"], bad["refused_by"]) == (
        (case["at"], "wrong_signature", case["slot"]), "light")


# --- the readers -----------------------------------------------------------------


def _reader(name):
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     name + ".py"), "read").read


def _mixed_run():
    run = _synthetic_run([
        _span("apply.validate", 10.0, 0.030, last_commit="none"),
        _span("apply.validate", 11.0, 0.050, last_commit="pending",
              last_commit_s=0.012, sigs=980),
        # a new node's save of the genesis state is no applied height's
        _span("state.save", 9.9, 0.5, height=0, bytes=150_000, validators=1000),
        _span("state.save", 10.1, 0.040, height=1, bytes=150_000,
              validators=1000),
        _span("state.save", 11.1, 0.044, height=2, bytes=150_400,
              validators=1000),
        _span("commit.assemble", 10.0, 0.004, mode="light", sigs=667),
        _span("commit.assemble", 10.2, 0.004, mode="light", sigs=667),
        _span("commit.assemble", 10.3, 0.006, mode="full", sigs=980),
        _span("prep.host_verify", 10.4, 0.010, route="host_c", sigs=200,
              kind="sr25519"),
        _span("prep.host_verify", 10.4, 0.010, route="host_c", sigs=50,
              kind="ed25519"),
        _span("prep.launch", 10.5, 0.001, program="jit__sr_verify_chunk",
              route="pallas", sigs=294, lanes=4096, device=0),
        _span("prep.launch", 10.6, 0.001, program="jit__verify_chunk",
              route="pallas", sigs=1153, lanes=4096, device=0),
    ])
    run.notes = {"mixed": {"seam": {"dispatched": 126, "fresh": 125,
                                    "stale": 1}}}
    return run


def test_the_mixed_readers_on_synthetic_spans():
    run = _mixed_run()
    want = {
        "mixed_lastcommit_exposed_ms": 12.0 / 2,
        "mixed_speculative_fresh_share": 100.0 * 125 / 126,
        "mixed_state_save_ms": 84.0 / 2,
        "mixed_sr_host_route_share": 100.0 * 200 / 494,
    }
    assert set(want) == set(NEW)
    for name, value in want.items():
        assert _reader(name)(run) == pytest.approx(value), name


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_without_its_spans(name, monkeypatch):
    """Laid over the parent commit: no such span in the program, and no
    driver wrote the note; and on an untraced run of this program."""
    from tendermint_tpu.utils import trace

    run = _mixed_run()
    run.traced = False
    assert _reader(name)(run) is None
    run.traced = True
    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items() if k != "state.save"})
    run.notes = {}
    assert _reader(name)(run) is None


# --- what the cell lists ---------------------------------------------------------


def _bench():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_one_chip_of_config_4_on_the_mixed_sync_mix():
    cells = [w for w in _bench()["workloads"] if w["name"] == CELL]
    assert len(cells) == 1
    assert (cells[0]["config"], cells[0]["traffic"], cells[0]["chips"]) == (
        CONFIG, "mixed-sync", 1)
    assert 0 < len(cells[0]["why"]) <= 200
    traffic = spec.Cell(CELL).traffic
    assert (traffic["driver"], traffic["warmup_passes"]) == ("mixedsync", 1)


@pytest.mark.parametrize("name", NEW + APPENDED + ["catchup_blocks_per_s"])
def test_the_cell_reports_this_metric(name):
    bench = _bench()
    entries = [m for m in bench["per_layer"] + bench["end_to_end"]
               if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    assert CELL in entry["workloads"]
    if name in NEW:
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "catchup_blocks_per_s"
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                           name + ".py"))
    elif name != "catchup_blocks_per_s":
        # an accepted metric keeps every cell it had
        assert "hub-150-full.fastsync" in entry["workloads"] \
            or name == "catchup_host_prep_cpu_ms"
        assert entry["moves"] == "catchup_blocks_per_s"


def test_the_cell_lists_nothing_else():
    bench = _bench()
    assert len(bench["per_layer"]) <= PER_LAYER_LIMIT
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert listed == set(NEW) | set(APPENDED) | {"catchup_blocks_per_s"}
    assert not listed & set(LEFT_OUT)


def test_the_configuration_states_what_the_issue_asks():
    bench = _bench()
    entries = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(entries) == 1
    entry = entries[0]
    assert entry["reduced"] == ["heights"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    config = spec.Cell(CELL).config
    assert config["architecture"] is None
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "configs[3]" in entry["source"] and "config 4" in entry["source"]
    # no two deployments share a source or a file
    assert len({c["source"] for c in bench["configs"]}) == len(bench["configs"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    d = config["dataset"]
    assert (d["absent_share"], d["nil_share"]) == (0.02, 0.002)
    assert "pattern_seed" not in d      # drawn from --seed, as hub-10k-live
    assert (d["heights"], d["chained_blocks"], d["chain_heights"],
            d["txs_per_block"]) == (2, False, 65, 0)
    assert list(config["reduced"]) == ["heights"]
    assert [g[:3] for g in config["guarantees"]] == [f"({c})" for c in "abcdef"]
    for key in ("deployment", "device_state", "assumed"):
        assert config[key], key
    for key in ("voting_power", "key_split", "absent_share", "nil_share",
                "data", "app", "stores", "pool"):
        assert config["assumed"][key], key
    assert config["rehearse"] == {
        "validators": {"ed25519": 21, "sr25519": 9}, "chain_heights": 8,
        "absent_share": 0.1, "nil_share": 0.05}
    assert config["profile_decisions_max"] == spec.Cell(
        "fastsync-1k-mixed.replay").config["profile_decisions_max"]
    assert config["max_backlog_heights"] == spec.Cell(
        "hub-150-full.fastsync").config["max_backlog_heights"]
    assert config["reference_heights"] >= 4


DIGEST_OF_SEED_50 = (
    "267fe081e6190af5407a7b49f69d295f69a91a108772b1f5c73b901b942028c3")
