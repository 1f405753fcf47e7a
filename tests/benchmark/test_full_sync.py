"""PR 37: the ``hub-150-full.fastsync`` cell on the CPU, tiny: its rehearsal
traced and untraced (both corruptions rejected at the reference's height with
the heights below saved and indexed), syncs broken on purpose that come out
not correct, the refusal of a program without the seams, the generator as a
function of the seed, the plain reference against the program on seeded
chains (hashes, app hash, results hash, part-set headers, the results'
encoding), the read-back through new sqlite connections after the node is
stopped, the new per-layer readers, and what the cell lists."""

import functools
import hashlib
import json
import os
import shutil
import types

import pytest

from benchmark.drivers import churnchain, fullchain
from benchmark.harness import datagen, record, spec
from benchmark.reference import block_replay
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

CELL = "hub-150-full.fastsync"
NEW = ["full_part_set_ms", "full_block_save_ms", "full_post_commit_ms",
       "full_deliver_us_per_tx", "full_index_us_per_tx",
       "full_index_cpu_us_per_tx", "full_index_lag_ms",
       "full_backlog_max_heights", "full_body_share", "full_cpu_sync_share",
       "full_cpu_post_commit_share", "full_cpu_indexer_share",
       "full_cpu_process_share"]
APPENDED = ["catchup_apply_ms", "catchup_host_prep_ms", "catchup_queue_ms",
            "catchup_requests_per_launch", "catchup_kernel_us_per_sig",
            "catchup_device_idle_share", "catchup_lane_fill",
            "catchup_dispatch_ms", "catchup_head_wait_ms",
            "catchup_keyset_miss_share", "catchup_prep_keyset_ms",
            "catchup_dispatches_per_decision", "light_verify_kernel_roofline",
            "catchup_apply_validate_ms", "catchup_apply_exec_ms",
            "catchup_apply_update_state_ms", "catchup_apply_save_ms"]


def _rehearsal_config():
    cfg = dict(spec.Cell(CELL).config)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
    return cfg


def _made(tmp, seed):
    cfg = _rehearsal_config()
    ds = datagen.load_or_generate("full", cfg, seed, data_dir=tmp, workers=0)
    return ds, cfg, fullchain.load_or_generate("full", ds, cfg, seed,
                                               data_dir=tmp, workers=0)


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    """(dataset, chain, the same chain loaded again) of seed 37, rehearsal
    sizes: 24 validators, 8 appliable heights of 40 transactions."""
    tmp = str(tmp_path_factory.mktemp("full"))
    ds, cfg, made = _made(tmp, 37)
    again = fullchain.load_or_generate("full", ds, cfg, 37, data_dir=tmp,
                                       workers=0)
    return ds, made, again


def _replay(made, raws=None, verify_at=()):
    return block_replay.replay(
        made.chain_id,
        [(v.pub_key.bytes(), v.power) for v in made.genesis.validators],
        made.raws if raws is None else raws,
        [b.hash for b in made.block_ids], verify_at)


def _bench_run():
    return spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")


# --- the rehearsal ---------------------------------------------------------------


@pytest.mark.parametrize("traced", ["0", "1"], ids=["untraced", "traced"])
def test_rehearsal_prints_the_contracts_last_line(traced):
    out = _run(["--workload", CELL, "--seed", f"370000011{traced}",
                "--seconds", "1", "--trace", traced, "--rehearse"])
    line = _last_line(out)
    notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    got = line["metrics"]
    if traced == "0":
        assert set(got) == {"catchup_blocks_per_s", "setup_s"}
    else:
        # every reader that needs neither the device nor ten heights of one
        # pass (the census marks) reads on the CPU
        for name in NEW[:9] + ["catchup_apply_ms", "catchup_apply_exec_ms",
                               "catchup_apply_save_ms",
                               "catchup_dispatches_per_decision"]:
            assert name in got, name
        assert got["catchup_dispatches_per_decision"]["value"] == 1.0
        assert 0 < got["full_body_share"]["value"] < 100
        assert got["full_block_save_ms"]["value"] \
            < got["catchup_apply_ms"]["value"]
        assert got["full_index_cpu_us_per_tx"]["value"] \
            <= got["full_index_us_per_tx"]["value"]
        assert "catchup_blocks_per_s" not in got
    chain = notes["chain"]
    assert (chain["heights"], chain["txs_per_block"], chain["tx_bytes"]) \
        == (8, 40, 1024)
    full = notes["full"]
    assert full["passes"] >= 1 and len(full["index_lag_s"]) == full["passes"]
    assert full["backlog_max_heights"] <= spec.Cell(CELL).config[
        "max_backlog_heights"]
    counters = full["counters"]
    assert counters["post_commit_submitted"] == counters["post_commit_done"] == 8
    assert (counters["heights_indexed"], counters["txs_indexed"]) == (8, 320)
    assert full["pipeline"]["dispatched"] == 8 * full["passes"]
    # both corrupted chains: refused where the reference refuses them, the
    # heights below applied (and, inside check, saved and indexed: a pass
    # that failed its read-back would have made the run not correct)
    rejected = notes["rejected"]
    assert set(rejected) == {"flipped byte in a transaction",
                             "flipped bit in a light prefix"}
    flipped = rejected["flipped byte in a transaction"]
    assert flipped["reference"][1] == "commit_block_id"
    assert flipped["program"][:2] == [flipped["reference"][0], "ValueError"]
    signature = rejected["flipped bit in a light prefix"]
    assert signature["reference"][1] == "wrong_signature"
    assert signature["program"][:3] == [signature["reference"][0],
                                        "ErrWrongSignature",
                                        signature["reference"][2]]
    for r in rejected.values():
        assert r["applied"] == r["reference"][0] - 1 >= 1
        assert r["program"][3] == ["pA", "pB"]
    assert not os.path.exists(os.path.join(
        spec.BENCH_DIR, ".homes", f"{CELL}-370000011{traced}-rehearse"))


def _indexer_drops_a_transaction(monkeypatch):
    """Guarantee (d) broken: one transaction of height 2 never reaches the
    index."""
    from tendermint_tpu.state.txindex import TxIndexer

    real = TxIndexer.index

    def lossy(self, height, idx, tx, result):
        if (height, idx) != (2, 0):
            real(self, height, idx, tx, result)

    monkeypatch.setattr(TxIndexer, "index", lossy)


def _responses_are_not_saved(monkeypatch):
    """Guarantee (c) broken: the last height's ABCI responses are saved
    empty."""
    from tendermint_tpu.state.store import ABCIResponses, StateStore

    real = StateStore.save_abci_responses

    def empty(self, height, responses):
        real(self, height, ABCIResponses() if height == 8 else responses)

    monkeypatch.setattr(StateStore, "save_abci_responses", empty)


def _a_part_is_stored_short(monkeypatch):
    """Guarantee (c) broken: every block's first part is stored one byte
    short (its header still names the whole part set)."""
    from tendermint_tpu.store import block_store
    from tendermint_tpu.types.part_set import Part

    real = block_store._block_rows

    def short(block, part_set):
        first = part_set.parts[0]
        clipped = types.SimpleNamespace(
            parts=[Part(index=0, bytes_=first.bytes_[:-1], proof=first.proof),
                   *part_set.parts[1:]], header=part_set.header)
        return real(block, clipped)

    monkeypatch.setattr(block_store, "_block_rows", short)


@pytest.mark.parametrize("break_it, correct", [
    (None, True),
    (_indexer_drops_a_transaction, False),
    (_responses_are_not_saved, False),
    (_a_part_is_stored_short, False),
], ids=["sound", "indexer_drops_a_transaction", "responses_are_not_saved",
        "a_part_is_stored_short"])
def test_a_broken_sync_comes_out_not_correct(break_it, correct, monkeypatch,
                                             capsys):
    bench_run = _bench_run()
    if break_it is not None:
        break_it(monkeypatch)
    rc = bench_run.main(["--workload", CELL, "--seed", "3700000113",
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
    from tendermint_tpu.state.txindex import IndexerService
    from tendermint_tpu.utils import trace

    bench_run = _bench_run()

    def refused(needle):
        for traced in ("0", "1"):
            rc = bench_run.main(["--workload", CELL, "--seed", "3700000114",
                                 "--seconds", "0.3", "--trace", traced,
                                 "--rehearse"])
            out = capsys.readouterr()
            assert rc == bench_run.EXIT_REFUSED
            assert needle in out.err and not out.out.strip()

    with monkeypatch.context() as m:
        m.setattr(trace, "CANONICAL_SPANS", {
            k: v for k, v in trace.CANONICAL_SPANS.items()
            if k != "store.save_block"})
        refused("store.save_block")
    monkeypatch.delattr(IndexerService, "wait_indexed")
    refused("wait_indexed")


# --- the generator ---------------------------------------------------------------


def test_the_chain_is_a_function_of_the_seed(chain, tmp_path):
    ds, made, again = chain
    assert made.meta["cached"] is False and again.meta["cached"] is True
    assert again.raws == made.raws
    assert churnchain.content_digest(again) == churnchain.content_digest(made)
    _ds, _cfg, other = _made(str(tmp_path), 38)
    assert churnchain.content_digest(other) != churnchain.content_digest(made)
    assert made.heights == 8 and len(made.raws) == 9
    keys = set()
    for raw in made.raws:
        txs = block_replay.parse_body(raw)["txs"]
        assert len(txs) == 40
        for tx in txs:
            assert len(tx) == 1024 and tx[16:17] == b"="
            int(tx[:16], 16)
            keys.add(tx[:16])
    assert len(keys) == 9 * 40
    # the seed draws the transactions, the pattern seed who signs
    assert fullchain.block_txs(37, 3, 4, 64) == fullchain.block_txs(37, 3, 4, 64)
    assert fullchain.block_txs(37, 3, 4, 64) != fullchain.block_txs(38, 3, 4, 64)
    with pytest.raises(ValueError):
        fullchain.block_txs(37, 3, 4, 17)


def test_the_full_size_block_is_the_sources():
    """1,043 transactions of 1,024 bytes marshal to the 17 parts the issue
    states (no chain is signed for this: the body alone sets the count)."""
    txs = fullchain.block_txs(37, 1, 1043, 1024)
    data = sum(1 + 2 + len(tx) for tx in txs)       # tag, 2-byte length, tx
    assert data == 1043 * 1027 == 1_071_161
    assert -(-(data + 20_000) // block_replay.PART_SIZE) == 17
    d = spec.Cell(CELL).config["dataset"]
    assert (d["txs_per_block"], d["tx_bytes"], d["chain_heights"]) \
        == (1043, 1024, 41)


# --- the reference against the program -------------------------------------------


@pytest.mark.parametrize("seed", [37, 41, 43])
def test_the_reference_computes_what_the_program_computes(seed, tmp_path):
    """Hashes, app hash, results hash and part-set headers from block bytes
    alone, against the program's own on a chain the program made."""
    from tendermint_tpu.abci.types import ResponseDeliverTx, results_hash
    from tendermint_tpu.types.block import Block
    from tendermint_tpu.types.part_set import PartSet

    _ds, _cfg, made = _made(str(tmp_path), seed)
    ref = _replay(made, verify_at={3})
    assert ref["refused"] is None and ref["applied"] == list(range(1, 9))
    assert ref["app_hash"] == made.final["app_hash"]
    assert ref["last_results_hash"] == made.final["last_results_hash"]
    assert ref["delivered"] == 8 * 40 and len(ref["store"]) == 8 * 40
    for h in ref["applied"]:
        block = Block.unmarshal(made.raws[h - 1])
        assert ref["headers"][h] == (block.data.hash(),
                                     block.header.last_results_hash,
                                     block.header.app_hash)
        psh = PartSet.from_data(made.raws[h - 1]).header()
        assert ref["part_set_headers"][h] == (psh.total, psh.hash)
        assert made.block_ids[h - 1].part_set_header == psh
        for tx in block.data.txs[:3]:
            key, _, value = tx.partition(b"=")
            assert ref["store"][key] == value
    assert block_replay.results_hash([(0, b"", 0, 0)] * 40) \
        == results_hash([ResponseDeliverTx()] * 40)
    assert [len(ref["prefixes"][h]) for h in ref["applied"]] == made.prefix_sigs


@pytest.mark.parametrize("code, data, gas_wanted, gas_used", [
    (0, b"", 0, 0), (1, b"", 0, 0), (0, b"\x00answer", 7, 300),
    (4_000_000_000, b"x" * 200, 1 << 40, 1)])
def test_the_references_results_encoding_is_the_programs(code, data,
                                                         gas_wanted, gas_used):
    from tendermint_tpu.abci.types import ResponseDeliverTx

    assert block_replay.result_bytes(code, data, gas_wanted, gas_used) \
        == ResponseDeliverTx(code=code, data=data, log="not hashed",
                             gas_wanted=gas_wanted,
                             gas_used=gas_used).deterministic_marshal()


@pytest.mark.parametrize("n", [0, 1, 2, 3, 17, 40])
def test_the_references_trees_are_the_programs(n):
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.types.tx import txs_hash

    txs = fullchain.block_txs(5, 2, n, 48)
    assert block_replay.data_hash(txs) == txs_hash(txs)
    raw = b"".join(txs)
    header = PartSet.from_data(raw, 100).header()
    assert block_replay.part_set_header(raw, 100) == (header.total, header.hash)


def test_the_reference_refuses_what_was_not_signed(chain):
    _ds, made, _again = chain
    # a flipped byte in a transaction: the bytes miss the part-set header
    # the commit signed, and their own header's data_hash
    raws = list(made.raws)
    at = raws[3].index(block_replay.parse_body(raws[3])["txs"][5]) + 40
    raws[3] = raws[3][:at] + bytes([raws[3][at] ^ 1]) + raws[3][at + 1:]
    ref = _replay(made, raws)
    assert ref["refused"] == (4, "commit_block_id", None)
    assert ref["data_hash_differs"] is True and ref["applied"] == [1, 2, 3]
    # a header that names another app hash, consistently re-cut and re-signed
    # is out of a reference's reach; a wrong last_results_hash in the bytes
    # themselves is caught by the same first check
    assert _replay(made)["refused"] is None


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(spec.BENCH_DIR, "reference", "block_replay.py")) as f:
        source = f.read()
    assert "tendermint_tpu" not in source
    assert "import hashlib" in source


# --- the read-back through new connections ---------------------------------------


def _driver(seed, tmp_path, monkeypatch):
    """The cell's driver at rehearsal sizes, its data under ``tmp_path``."""
    cell = spec.Cell(CELL)
    cfg = _rehearsal_config()
    ds = datagen.load_or_generate("full-driver", cfg, seed,
                                  data_dir=str(tmp_path), workers=0)
    run = record.Run(cell=cell, seed=seed, seconds=0.1, traced=False,
                     rehearse=True)
    monkeypatch.setattr(fullchain, "load_or_generate", functools.partial(
        fullchain.load_or_generate, data_dir=str(tmp_path), workers=0))
    return cell.driver.Driver(run, ds, cell.traffic)


def test_a_stopped_nodes_files_answer_through_new_connections(tmp_path,
                                                              monkeypatch):
    """Guarantees (c) and (d): after Node.stop and close_stores the three
    sqlite files are opened again and hold what the reference computed; a
    row taken away afterwards is missed."""
    import sqlite3

    driver = _driver(3700000115, tmp_path, monkeypatch)
    try:
        driver.ref = driver._reference(driver.chain.raws, ())
        record_ = driver._pass(driver.chain.raws, lambda fn, _sigs: fn())
        assert record_.applied == 8 and record_.indexed
        data = os.path.join(record_.home, "data")
        assert {"blockstore.db", "state.db", "tx_index.db"} <= set(os.listdir(data))
        assert driver._differs(record_, driver.ref, 8) is None
        conn = sqlite3.connect(os.path.join(data, "tx_index.db"))
        tx = block_replay.parse_body(driver.chain.raws[7])["txs"][0]
        conn.execute("DELETE FROM kv WHERE k = ?",
                     (b"txr/" + hashlib.sha256(tx).digest(),))
        conn.commit()
        conn.close()
        assert "tx.height=8" in driver._differs(record_, driver.ref, 8)
    finally:
        shutil.rmtree(driver._home_prefix(), ignore_errors=True)


# --- the readers -----------------------------------------------------------------


def _reader(name):
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     name + ".py"), "read").read


def _full_run():
    def on(thread, span, cpu=None):
        return {**span, "thread": thread, "cpu_s": cpu}

    census = dict(wall_s=2.0, process_s=3.0, rest_s=0.2, lost=0,
                  sync_thread="MainThread",
                  threads={"MainThread": 1.0, "post-commit": 0.3,
                           "indexer": 0.8, "verify-service": 0.1})
    run = _synthetic_run([
        on("MainThread", _span("fastsync.apply", 10.0, 0.20)),
        on("MainThread", _span("fastsync.part_set", 10.0, 0.010, bytes=1 << 20,
                               parts=17)),
        on("MainThread", _span("fastsync.part_set", 11.0, 0.014, bytes=1 << 20,
                               parts=17)),
        on("MainThread", _span("abci.deliver_txs", 10.1, 0.030, n=1000)),
        on("MainThread", _span("abci.deliver_txs", 11.1, 0.050, n=1000)),
        on("MainThread", _span("store.save_block", 10.2, 0.016, rows=22)),
        on("MainThread", _span("state.save_responses", 10.3, 0.008)),
        on("MainThread", _span("block.data_hash", 10.4, 0.004, txs=1000)),
        # the same name on another thread is not the sync thread's time
        on("rpc", _span("block.data_hash", 10.4, 0.5, txs=1000)),
        on("MainThread", _span("apply.backlog_wait", 11.4, 0.068, backlog=2)),
        on("post-commit", _span("apply.post_commit", 10.5, 0.024, txs=1000,
                                events=1002)),
        on("indexer", _span("indexer.height", 10.5, 0.090, txs=1000, rows=3001),
           cpu=0.060),
        on("indexer", _span("indexer.height", 11.5, 0.110, txs=1000, rows=3001),
           cpu=0.080),
        on("MainThread", _span("fastsync.thread_cpu", 11.9, 0.0, **census)),
    ])
    run.notes = {"full": {"index_lag_s": [0.05, 0.07],
                          "backlog_max_heights": 2}}
    return run


def test_the_full_readers_on_synthetic_spans():
    run = _full_run()
    want = {
        "full_part_set_ms": 24.0 / 2,
        "full_block_save_ms": 16.0 / 2,
        "full_post_commit_ms": 24.0 / 2,
        "full_deliver_us_per_tx": 80_000.0 / 2000,
        "full_index_us_per_tx": 200_000.0 / 2000,
        "full_index_cpu_us_per_tx": 140_000.0 / 2000,
        "full_index_lag_ms": 60.0,
        "full_backlog_max_heights": 2,
        # 200 ms of body spans on the sync thread in 2 x 0.5 s of decisions
        "full_body_share": 100.0 * 0.200 / 1.0,
        "full_cpu_sync_share": 50.0,
        "full_cpu_post_commit_share": 15.0,
        "full_cpu_indexer_share": 40.0,
        "full_cpu_process_share": 150.0,
    }
    assert set(want) == set(NEW)
    for name, value in want.items():
        assert _reader(name)(run) == pytest.approx(value), name


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_without_its_spans(name, monkeypatch):
    """Laid over the parent commit: no such span in the program, and no
    driver wrote the note; and on an untraced run of this program."""
    from tendermint_tpu.utils import trace

    run = _full_run()
    run.traced = False
    assert _reader(name)(run) is None
    run.traced = True
    mine = ("fastsync.part_set", "fastsync.thread_cpu", "store.save_block",
            "events.publish_block", "indexer.height", "apply.backlog_wait",
            "state.save_responses", "block.data_hash", "mempool.update")
    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items() if k not in mine})
    run.notes = {}
    assert _reader(name)(run) is None


# --- what the cell lists ---------------------------------------------------------


def test_the_cell_lists_what_issue_37_says():
    """By membership and containment, never by position (D14): later PRs
    append cells, configurations and metrics after these."""
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cells) == 1
    cell = cells[0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hub-150-full", "full-sync", 1)
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert listed == set(NEW) | set(APPENDED) | {"catchup_blocks_per_s"}
    assert set(NEW) <= {m["name"] for m in bench["per_layer"]}
    for m in bench["per_layer"] + bench["end_to_end"]:
        if m["name"] in NEW:
            assert m["workloads"][0] == CELL
            assert m["moves"] == "catchup_blocks_per_s"
            assert m["layer"] == "block body"
        if m["name"] in APPENDED or m["name"] == "catchup_blocks_per_s":
            at = m["workloads"].index(CELL)
            assert m["workloads"][at - 1] == "hub-150-churn.fastsync"
    entries = [c for c in bench["configs"] if c["name"] == "hub-150-full"]
    assert len(entries) == 1
    entry = entries[0]
    assert entry["reduced"] == ["heights"]
    config, hub = spec.Cell(CELL).config, spec.Cell("hub-150.fastsync").config
    assert config["architecture"] is None
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    for key in ("validators", "voting_power", "absent_share", "nil_share",
                "pattern_seed"):
        assert config["dataset"][key] == hub["dataset"][key], key
    assert config["dataset"]["heights"] == 2
    assert list(config["reduced"]) == ["heights"]
    assert [g[:3] for g in config["guarantees"]] == [
        f"({c})" for c in "abcdefg"]
    for key in ("deployment", "device_state", "assumed"):
        assert config[key], key
    assert config["rehearse"] == {
        "validators": {"ed25519": 24, "sr25519": 0}, "chain_heights": 9,
        "txs_per_block": 40, "absent_share": 0.1, "nil_share": 0.05}
    traffic = spec.Cell(CELL).traffic
    assert (traffic["driver"], traffic["warmup_passes"],
            traffic["profile_skip"], traffic["profile_decisions"]) \
        == ("fullsync", 1, 5, 20)
