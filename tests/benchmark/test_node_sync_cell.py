"""PR 40: the ``hub-150-full-p2p.node-sync`` cell on the CPU, tiny: its
rehearsal traced and untraced (peers in processes of their own, the corrupted
and the silent pass, no child left behind), the refusal of a program without
the seams, the plain reference of the wire (it imports nothing of the program;
what it explains and what it does not), the seven ``wire_*`` readers on
synthetic spans and marks and on a program without them, and what the cell
lists: by membership and containment, never by position. PR 48: legs (g) and
(h) of its check over hand-made pass records, no process started (what each
still refuses, under its letter), and the result line's ``failures``."""

import json
import os
import re
import subprocess

import pytest

from benchmark.harness import spec, wire
from benchmark.reference import wire_sync
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

CELL = "hub-150-full-p2p.node-sync"
CONTROL = "hub-150-full.fastsync"
NEW = ["wire_block_recv_ms", "wire_pool_wait_share", "wire_cpu_recv_share",
       "wire_recv_cpu_us_per_packet", "wire_throttle_wait_ms",
       "wire_bytes_per_block", "wire_first_block_ms"]


def _reader(name):
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     name + ".py"), "read").read


def _bench_run():
    return spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")


def _peer_processes():
    out = subprocess.run(["ps", "-eo", "pid,args"], capture_output=True,
                         text=True).stdout
    return [line for line in out.splitlines() if "nodesync_peer.py" in line]


# --- the rehearsal ---------------------------------------------------------------


@pytest.mark.parametrize("traced", ["0", "1"], ids=["untraced", "traced"])
def test_rehearsal_prints_the_contracts_last_line_and_leaves_no_child(traced):
    seed = f"400000011{traced}"
    out = _run(["--workload", CELL, "--seed", seed, "--seconds", "1",
                "--trace", traced, "--rehearse"])
    line = _last_line(out)
    said = json.loads(out.stdout.strip().splitlines()[-2])
    notes = said["notes"]
    assert line["correct"] is True and line["failed"] == 0, said["failures"]
    assert list(line)[-1] == "failures"
    assert (line["failures"]["n"], line["failures"]["by_check"],
            line["failures"]["first"]) == (0, {}, [])
    compared = line["failures"]["compared"]
    assert line["attempted"] == 8 * said["passes"] > 0
    got = line["metrics"]
    if traced == "0":
        assert set(got) == {"catchup_blocks_per_s", "setup_s"}
    else:
        # the readers that need neither the device nor ten heights of one
        # pass (the census and the p2p.wire mark are written every ten)
        for name in ("wire_block_recv_ms", "wire_pool_wait_share",
                     "wire_first_block_ms", "catchup_apply_ms",
                     "full_block_save_ms", "full_index_lag_ms",
                     "full_body_share", "catchup_dispatches_per_decision"):
            assert name in got, name
        assert 0 <= got["wire_pool_wait_share"]["value"] <= 100
        assert got["wire_first_block_ms"]["value"] > 0
        assert "catchup_blocks_per_s" not in got
    chain = notes["chain"]
    assert (chain["heights"], chain["txs_per_block"], chain["tx_bytes"]) \
        == (8, 40, 1024)
    # two serving peers, processes of their own, holding the whole chain
    peers = notes["peers"]
    assert peers["ranges"] == [[1, 9], [1, 9]] and len(set(peers["pids"])) == 2
    assert os.getpid() not in peers["pids"]
    wire_notes = notes["wire"]
    assert all(len(row) == 2 for row in wire_notes["peers_cpu_s_a_pass"])
    assert all(pool == {"received": 9, "timed_out": 0, "peers_stopped": 0}
               for pool in wire_notes["pool"])
    assert notes["reference"]["wire"]["msgs"] == 9
    # (g): refused where the reference refuses it, and the pass completed
    flipped = notes["rejected"]["flipped byte in a transaction"]
    assert flipped["reference"][1] == "commit_block_id"
    assert flipped["program"][:2] == [flipped["reference"][0], "ValueError"]
    assert flipped["applied"] == 8
    assert set(flipped["program"][3]) <= set(flipped["scored"])
    assert compared["g_refused_height"] == [flipped["reference"][0]] * 2
    # (h): beside a clean pass from one peer alone; the silent peer's
    # requests were given up when the time-out says, BlockPool.timed_out
    # counts them, nobody else was stopped, the pass completed in its bound
    silent = notes["silent_peer"]
    assert silent["applied"] == 8
    assert silent["one_peer_pool"] == {"received": 9, "timed_out": 0,
                                       "peers_stopped": 0}
    assert silent["pool"]["timed_out"] == compared["h_timed_out"][0] \
        == compared["h_timed_out"][1] > 0
    assert compared["h_peers_stopped"] == [1, 1]
    waited, (least, most) = compared["h_given_up_after_s"]
    assert (least, most) == (14.75, 16.0) and least <= waited <= most
    assert compared["h_done_after_s"][0] <= compared["h_done_after_s"][1] \
        == pytest.approx(16.0 + 2 * silent["one_peer_s"])
    # nothing outlives the run: no peer process, no home directory
    assert not [p for p in _peer_processes()
                if any(str(pid) in p.split()[0] for pid in peers["pids"])]
    assert not os.path.exists(os.path.join(
        spec.BENCH_DIR, ".homes", f"{CELL}-{seed}-rehearse"))


def test_the_peers_die_with_a_benchmark_that_is_killed(tmp_path):
    """SIGKILL runs no atexit: the parent-death signal is what ends them."""
    import signal
    import sys
    import time

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TM_TPU_SKIP_WARMUP": "1",
           "PYTHONPATH": spec.ROOT}
    before = set(_peer_processes())
    proc = subprocess.Popen(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4000000120", "--seconds", "30",
         "--trace", "0", "--rehearse"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        cwd=spec.ROOT)
    try:
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline and \
                len(set(_peer_processes()) - before) < 2:
            assert proc.poll() is None, "the run ended before its peers came up"
            time.sleep(0.2)
        mine = set(_peer_processes()) - before
        assert len(mine) >= 2
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(30)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and set(_peer_processes()) & mine:
        time.sleep(0.1)
    assert not set(_peer_processes()) & mine
    import shutil

    shutil.rmtree(os.path.join(spec.BENCH_DIR, ".homes",
                               f"{CELL}-4000000120-rehearse"),
                  ignore_errors=True)


@pytest.mark.parametrize("seam", ["expire_requests", "wire_totals", "p2p.wire"])
def test_a_program_without_the_seam_is_refused_at_load(seam, monkeypatch, capsys):
    """The parent commit: the driver's file refuses to load there, and run.py
    exits 2 before it makes any data or starts any process."""
    from tendermint_tpu.blockchain import reactor
    from tendermint_tpu.p2p.switch import Switch
    from tendermint_tpu.utils import trace

    if seam == "expire_requests":
        monkeypatch.delattr(reactor.BlockPool, "expire_requests")
    elif seam == "wire_totals":
        monkeypatch.delattr(Switch, "wire_totals")
    else:
        monkeypatch.setattr(trace, "CANONICAL_SPANS", {
            k: v for k, v in trace.CANONICAL_SPANS.items() if k != "p2p.wire"})
    bench_run = _bench_run()
    before = _peer_processes()
    for traced in ("0", "1"):
        rc = bench_run.main(["--workload", CELL, "--seed", "4000000114",
                             "--seconds", "0.3", "--trace", traced,
                             "--rehearse"])
        out = capsys.readouterr()
        assert rc == bench_run.EXIT_REFUSED
        assert "node-sync mix needs a program" in out.err
        assert not out.out.strip()
    assert _peer_processes() == before


# --- legs (g) and (h) over hand-made pass records (PR 48) ---------------------------

REF = {"app_hash": b"\x01" * 8, "last_results_hash": b"\x02" * 32, "store": {},
       "txs": {h: 40 for h in range(1, 10)}}
BAD, HONEST = "bad0" * 10, "beef" * 10
READS_AS = {"refused": (5, "commit_block_id", None), "heights": [5],
            "completes": True, "data_hash_differs": True}


def _driver():
    """The cell's Driver with no chain, no peer and no file behind it: what
    its legs read of a pass is in the record they are handed."""
    from benchmark.harness import record

    cell = spec.Cell(CELL)
    driver = object.__new__(cell.driver.Driver)
    driver.run = record.Run(cell=cell, seed=48, seconds=1.0, traced=False,
                            rehearse=True)
    driver.p2p, driver.heights, driver.max_backlog = cell.config["p2p"], 8, 2
    driver._files_differ = lambda home, ref: None
    return driver, cell.driver.PassRecord


def _record(make, seconds, pool, *, applied=8, app_hash=REF["app_hash"],
            invalid=None, scored=(), silent=None):
    received, timed_out, stopped = pool
    return make(
        home="", applied=applied, t=(100.0, 100.0 + seconds),
        state={"height": applied, "app_hash": app_hash,
               "last_results_hash": REF["last_results_hash"]},
        app_sample={}, invalid=invalid, scored=list(scored), silent=silent,
        pipeline={"dispatched": 8, "discarded": 0, "in_flight": 0},
        counters={"post_commit_submitted": 8, "post_commit_done": 8,
                  "post_commit_backlog_max": 1, "backlog_waits": 0,
                  "heights_indexed": 8, "txs_indexed": 320,
                  "indexer_backlog_max": 1, "indexer_backlog_heights_max": 1},
        pool={"received": received, "timed_out": timed_out,
              "peers_stopped": stopped})


def _watched(open_requests, waited=15.01, asked_at=0.02):
    return {"stopped_at": 0.017, "after_blocks": 0, "asked_at": asked_at,
            "expired_at": asked_at + waited, "open_at_expiry": open_requests}


# ISSUE 48's table: what seven rehearsals of the parent noted of the silent
# pass (seconds, received, timed_out, peers_stopped). Five met nobody but the
# two peers they were given. In two the corrupted peer of leg (g), still
# alive, was dialled through PEX and served a bad block once the silent peer's
# requests were given up: both senders stopped, blocks taken twice. The
# repaired check makes no such pass; handed one, it says what is wrong with it
TALLIES = {"11": (15.14, 9, 9, 1), "101": (15.30, 9, 9, 1),
           "201": (15.41, 9, 9, 1), "202": (15.50, 9, 9, 1),
           "103": (15.13, 9, 4, 1), "203": (15.58, 15, 9, 3),
           "102": (24.57, 23, 9, 4)}


@pytest.mark.parametrize("seed", sorted(TALLIES))
def test_the_silent_leg_on_the_tallies_of_issue_48(seed):
    driver, make = _driver()
    seconds, *pool = TALLIES[seed]
    driver._hold_silent(_record(make, seconds, pool, silent=_watched(pool[1])),
                        0.25, REF)
    said = driver.run.failure_summary()
    if pool[2] == 1:
        assert said["n"] == 0 and said["by_check"] == {}, said
    else:
        assert set(said["by_check"]) == {"h"} and "peers were stopped" in \
            " ".join(said["first"])
        assert (said["by_check"]["h"] == 2) == (seconds > 16.5)
    assert said["compared"]["h_peers_stopped"] == [pool[2], 1]
    assert said["compared"]["h_done_after_s"] == [
        pytest.approx(seconds - 0.02), pytest.approx(16.5)]


@pytest.mark.parametrize("fault, words", [
    ("no request was ever timed out", "no request of the silent peer's"),
    ("re-asked after 60 s", "given up after 60.00 s"),
    ("re-asked after 5 s", "given up after 5.00 s"),
    ("timed_out counts other requests", "left 9 requests open"),
    ("the honest peer stopped too", "2 peers were stopped"),
    ("the pass ends late", "the pass ended 19.0 s after that request"),
    ("the pass ends short", "applied 7"),
    ("another app hash", "the app hash differs"),
])
def test_the_silent_leg_still_refuses(fault, words):
    driver, make = _driver()
    seconds, pool, silent, more = 15.2, [9, 9, 1], _watched(9), {}
    if fault == "no request was ever timed out":
        pool = [9, 0, 0]
        silent = {"stopped_at": 0.017, "after_blocks": 0}
    elif fault == "re-asked after 60 s":
        seconds, silent = 60.2, _watched(9, waited=60.0)
    elif fault == "re-asked after 5 s":
        seconds, silent = 5.2, _watched(9, waited=5.0)
    elif fault == "timed_out counts other requests":
        pool = [9, 4, 1]
    elif fault == "the honest peer stopped too":
        pool = [14, 9, 2]
    elif fault == "the pass ends late":
        seconds = 19.02
    elif fault == "the pass ends short":
        more = {"applied": 7}
    else:
        more = {"app_hash": b"\x09" * 8}
    driver._hold_silent(_record(make, seconds, pool, silent=silent, **more),
                        0.25, REF)
    said = driver.run.failure_summary()
    assert said["n"] >= 1 and set(said["by_check"]) == {"h"}, said
    assert words in " ".join(said["first"]), said["first"]


@pytest.mark.parametrize("fault", [
    None, "the program accepts the flipped byte", "refused at another height",
    "the sender is left unscored", "the sender is not stopped",
    "the reference reads the copy otherwise", "the pass ends short"])
def test_the_corrupted_leg_still_refuses(fault):
    driver, make = _driver()
    invalid = (5, "ValueError", None, [BAD],
               "second block's LastCommit is for a different block")
    scored, pool, want, more = [BAD], [14, 0, 1], READS_AS, {}
    if fault == "the program accepts the flipped byte":
        invalid, scored, pool = None, [], [9, 0, 0]
    elif fault == "refused at another height":
        invalid = (6, *invalid[1:])
    elif fault == "the sender is left unscored":
        scored = []
    elif fault == "the sender is not stopped":
        pool = [14, 0, 0]
    elif fault == "the reference reads the copy otherwise":
        want = {**READS_AS, "refused": None}
    elif fault == "the pass ends short":
        more = {"applied": 4}
    driver._hold_corrupted(
        _record(make, 0.4, pool, invalid=invalid, scored=scored, **more),
        want, 5, BAD, REF)
    said = driver.run.failure_summary()
    assert said["compared"]["g_refused_height"] == [
        None if invalid is None else invalid[0], 5]
    if fault is None:
        assert said["n"] == 0, said
    else:
        assert said["n"] == 1 and said["by_check"] == {"g": 1}, said


def test_a_clean_pass_fails_under_its_guarantees_letter():
    driver, make = _driver()
    driver.peers = []
    ref = {**REF, "wire": {"msgs": 9, "packets": 400, "bytes": 370_000,
                           "frames_least": 800}}
    backlog = _record(make, 0.2, [9, 0, 0])
    backlog.counters["indexer_backlog_heights_max"] = 3
    driver._hold_clean("pass 1", backlog, ref)
    twice = _record(make, 0.2, [10, 0, 0])
    twice.wire = {"channels": {"0x40": {}}}
    driver._hold_clean("pass 2", twice, ref)
    said = driver.run.failure_summary()
    assert said["by_check"] == {"e": 1, "f": 1}
    assert said["first"][0].startswith("pass 1: the backlog behind apply_block")
    assert "a clean pass takes 9 blocks, each once" in said["first"][1]


def test_every_run_listens_on_a_loopback_address_of_its_own():
    """Two runs on one host share no port: no two live process ids map to
    one address, and every address is a host address of 127.0.0.0/8."""
    import ipaddress

    from benchmark.drivers import nodesync_peer

    pids = list(range(1, 200_000)) + list(range(4_000_000, 4_194_305))
    hosts = {nodesync_peer.loopback_of(pid) for pid in pids}
    assert len(hosts) == len(pids) and "127.0.0.1" not in hosts
    for host in (nodesync_peer.loopback_of(pid) for pid in pids[::997]):
        octets = [int(o) for o in host.split(".")]
        assert ipaddress.ip_address(host).is_loopback
        assert octets[0] == 127 and 1 <= octets[3] <= 250
    cfg = nodesync_peer.local_config("/nowhere", "a@127.3.2.1:9",
                                     host="127.3.2.1")
    assert (cfg.p2p.laddr, cfg.rpc.laddr) == ("tcp://127.3.2.1:0",) * 2
    assert cfg.p2p.persistent_peers == "a@127.3.2.1:9"


def test_the_result_line_names_the_checks_that_failed():
    """``failures``: empty on a run that is correct (the rehearsals above
    assert it on a whole run), and on a run with failures injected their
    number, the count under each check's name (a failure appended without a
    name counts as ``other``) and the first three messages, cut."""
    from benchmark.harness import record

    run = record.Run(cell=None, seed=1, seconds=1.0, traced=False,
                     rehearse=True)
    assert run.failure_summary() == {"n": 0, "by_check": {}, "first": [],
                                     "compared": {}}
    run.fail("h", "a peer stopped after 0 blocks: " + "x" * 400)
    run.failures.append("no decision was attempted in the window")
    run.fail("compiled_in_window", "1 program(s) compiled")
    run.fail("h", "the pass beside a silent peer did not end")
    run.compare("h_peers_stopped", 3, 1)
    said = run.failure_summary()
    assert said["n"] == 4
    assert said["by_check"] == {"h": 2, "compiled_in_window": 1, "other": 1}
    assert len(said["first"]) == 3 and len(said["first"][0]) == 240
    assert said["first"][1] == "no decision was attempted in the window"
    assert said["compared"] == {"h_peers_stopped": [3, 1]}
    assert json.loads(json.dumps(said)) == said


# --- the plain reference of the wire ---------------------------------------------


def test_the_reference_imports_nothing_of_the_program():
    seen, todo = set(), ["wire_sync"]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        with open(os.path.join(spec.BENCH_DIR, "reference", name + ".py")) as f:
            source = f.read()
        assert "tendermint_tpu" not in re.sub(r'""".*?"""', "", source,
                                              flags=re.S), name
        for line in source.splitlines():
            m = re.match(r"\s*(?:from|import)\s+([\w.]+)(?:\s+import\s+(.*))?",
                         line)
            if not m:
                continue
            module = m.group(1)
            assert not module.startswith(("tendermint_tpu", "benchmark.drivers",
                                          "benchmark.harness")), line
            if module == "benchmark.reference":
                todo.extend(n.strip().split(" ")[0]
                            for n in m.group(2).split(","))
            elif module.startswith("benchmark.reference."):
                todo.append(module.rsplit(".", 1)[1])
    assert {"wire_sync", "block_replay", "valset_replay"} <= seen


RAWS = [b"x" * 41_359, b"y" * 41_360, b"z" * 1_070_555]


def _received(requests, responses, peers=((1, 3),), extra_bytes=0):
    want = wire_sync.a_pass(RAWS)
    base, height = peers[0]
    return {"msgs": want["msgs"] + requests + responses,
            "packets": want["packets"] + requests + responses,
            "bytes": want["bytes"] + requests * wire_sync.status_request_len()
            + responses * wire_sync.status_response_len(height, base)
            + extra_bytes}


@pytest.mark.parametrize("requests, responses", [(0, 0), (2, 4), (3, 2), (0, 7)])
def test_status_messages_explain_what_the_blocks_leave(requests, responses):
    peers = [(1, 3), (1, 3)]
    assert wire_sync.account(RAWS, _received(requests, responses), peers) == {
        "status_requests": requests, "status_responses": responses}


@pytest.mark.parametrize("broken", ["a byte more", "a block twice",
                                    "a packet more", "a peer without the tip",
                                    "two ranges"])
def test_what_no_number_of_status_messages_explains(broken):
    got, peers = _received(2, 4), [(1, 3), (1, 3)]
    if broken == "a byte more":
        got["bytes"] += 1
    elif broken == "a block twice":
        n = wire_sync.block_response_len(len(RAWS[0]))
        got = {"msgs": got["msgs"] + 1, "bytes": got["bytes"] + n,
               "packets": got["packets"] + wire_sync.packets(n)}
    elif broken == "a packet more":
        got["packets"] += 1
    elif broken == "a peer without the tip":
        peers = [(1, 2), (1, 2)]
    else:
        peers = [(1, 3), (2, 3)]
    assert wire_sync.account(RAWS, got, peers) is None


def test_a_pass_counts_messages_packets_frames_and_ranges():
    got = wire_sync.a_pass(RAWS)
    sizes = [wire_sync.block_response_len(len(raw)) for raw in RAWS]
    assert sizes == [41_359 + 8, 41_360 + 8, 1_070_555 + 8]
    assert got["heights"] == [(k + 1, n, -(-n // 1024))
                              for k, n in enumerate(sizes)]
    assert (got["msgs"], got["bytes"]) == (3, sum(sizes))
    assert got["packets"] == sum(-(-n // 1024) for n in sizes)
    # a full packet on the stream is 1,034 bytes: two sealed frames
    assert wire_sync.packet_len(1024, False) == 1034
    assert wire_sync.frames(1034) == 2 and wire_sync.frames(1024) == 1
    assert got["frames_least"] >= 2 * (got["packets"] - 3) + 3
    assert wire_sync.SEALED_FRAME == 1044
    assert wire_sync.serves(1, 41, 41) and not wire_sync.serves(5, 41, 4)
    assert not wire_sync.serves(1, 40, 41)


# --- the readers ----------------------------------------------------------------------


def _census(start, wall, recv, packets, blocked, on_0x40):
    return [
        _span("fastsync.thread_cpu", start, 0.0, wall_s=wall, process_s=wall,
              sync_thread="fastsync-pool", rest_s=0.0, lost=0,
              threads={"fastsync-pool": wall / 2, "mconn-recv": recv,
                       "mconn-send": 0.01, "indexer": wall / 4}),
        _span("p2p.wire", start, 0.0, packets_recv=packets, msgs_recv=12,
              recv_blocked_s=blocked, send_blocked_s=0.0, requested=3,
              pooled=2, channels={"0x40": {"bytes_recv": on_0x40},
                                  "0x20": {"bytes_recv": 99}})]


def _wire_run():
    run = _synthetic_run([
        _span("blockchain.recv_block", 10.1, 0.020, bytes=1_070_000, height=1),
        _span("blockchain.recv_block", 10.6, 0.030, bytes=1_070_000, height=2),
        _span("fastsync.pool_wait", 10.2, 0.25, height=1),
        _span("fastsync.pool_wait", 11.2, 0.15, height=2),
        _span("fastsync.pool_wait", 30.0, 9.0, height=2),   # outside any pass
        _span("fastsync.first_block", 10.3, 0.0, height=1, seconds=0.3),
        _span("fastsync.first_block", 11.3, 0.0, height=1, seconds=0.5),
        *_census(10.9, 4.0, 1.0, 10_000, 0.4, 10_700_000),
        *_census(11.9, 4.0, 0.6, 10_000, 0.0, 10_700_000),
    ], decisions=2)
    run.passes = [(10.0, 11.0, 1), (11.0, 12.0, 1)]
    return run


WANT = {"wire_block_recv_ms": 25.0,              # 50 ms over two decisions
        "wire_pool_wait_share": 20.0,            # 0.4 s of 2 s of passes
        "wire_cpu_recv_share": 20.0,             # 1.6 s of 8 s
        "wire_recv_cpu_us_per_packet": 80.0,     # 1.6 s over 20,000 packets
        "wire_throttle_wait_ms": 20.0,           # 0.4 s over 20 heights
        "wire_bytes_per_block": 1_070_000.0,     # 21.4 MB over 20 heights
        "wire_first_block_ms": 400.0}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_its_spans_and_marks(name):
    assert _reader(name)(_wire_run()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_without_them(name, monkeypatch):
    """An untraced run, a traced run of a program that has the names and
    wrote none of the marks, and the parent commit, which lacks them."""
    from tendermint_tpu.utils import trace

    run = _wire_run()
    run.traced = False
    assert _reader(name)(run) is None
    empty = _synthetic_run([], decisions=2)
    empty.passes = [(10.0, 12.0, 2)]
    assert _reader(name)(empty) in (None, 0.0)
    if name in ("wire_first_block_ms", "wire_cpu_recv_share",
                "wire_recv_cpu_us_per_packet", "wire_throttle_wait_ms",
                "wire_bytes_per_block"):
        assert _reader(name)(empty) is None      # a ratio over nothing
    mine = ("blockchain.recv_block", "fastsync.pool_wait",
            "fastsync.first_block", "p2p.wire", "fastsync.thread_cpu")
    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items() if k not in mine})
    assert _reader(name)(_wire_run()) is None


def test_the_readers_names_are_the_programs():
    from tendermint_tpu.utils import trace

    for name in ("blockchain.recv_block", "fastsync.pool_wait",
                 "fastsync.first_block", wire.WIRE, "fastsync.thread_cpu"):
        assert name in trace.CANONICAL_SPANS, name
    from tendermint_tpu.blockchain import reactor

    assert wire.CHANNEL == f"{reactor.BLOCKCHAIN_CHANNEL:#x}"


# --- what the cell lists ---------------------------------------------------------


def test_the_cell_lists_what_issue_40_says():
    """By membership and containment, never by position (D14): a later PR
    appends cells, configurations and metrics after these."""
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hub-150-full-p2p", "node-sync", 1)
    assert CONTROL in cell["why"] and len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    listed = {name for name, m in metrics.items()
              if CELL in m.get("workloads", [])}
    control = {name for name, m in metrics.items()
               if CONTROL in m.get("workloads", [])}
    assert "catchup_blocks_per_s" in listed
    assert set(NEW) <= listed and control <= listed
    assert listed == set(NEW) | control
    for name in NEW:
        m = metrics[name]
        assert CELL in m["workloads"] and CONTROL not in m["workloads"]
        assert (m["moves"], m["layer"]) == ("catchup_blocks_per_s", "wire")
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                           name + ".py"))
    for name in control:
        order = metrics[name]["workloads"]
        assert order.index(CONTROL) < order.index(CELL), name
    entry = {c["name"]: c for c in bench["configs"]}["hub-150-full-p2p"]
    assert entry["reduced"] == ["heights"] and len(entry["source"]) <= 200
    config, full = spec.Cell(CELL).config, spec.Cell(CONTROL).config
    assert config["architecture"] is None and config["source"] == entry["source"]
    # key for key, but a pass of 20 heights where the control's has 40
    assert config["dataset"] == {**full["dataset"], "chain_heights": 21}
    assert config["rehearse"] == full["rehearse"]
    assert config["max_backlog_heights"] == full["max_backlog_heights"]
    assert list(config["reduced"]) == ["heights"]
    assert config["guarantees"][:5] == full["guarantees"][:5]
    assert [g[:3] for g in config["guarantees"]] == [f"({c})" for c in "abcdefghi"]
    p2p = config["p2p"]
    assert (p2p["serving_peers"], p2p["send_rate"], p2p["recv_rate"],
            p2p["max_packet_msg_payload_size"], p2p["sealed_frame_bytes"]) == (
        2, 5_120_000, 5_120_000, 1024, 1044)
    from tendermint_tpu.blockchain import reactor
    from tendermint_tpu.config.config import Config

    assert (p2p["request_window"], p2p["peer_timeout_s"]) == (
        reactor.REQUEST_WINDOW, reactor.REQUEST_TIMEOUT_S)
    default = Config().p2p
    assert (default.send_rate, default.recv_rate,
            default.max_packet_msg_payload_size, default.pex) == (
        p2p["send_rate"], p2p["recv_rate"],
        p2p["max_packet_msg_payload_size"], p2p["pex"])
    traffic = spec.Cell(CELL).traffic
    assert traffic["driver"] == "nodesync" and traffic["warmup_passes"] == 1
