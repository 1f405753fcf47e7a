"""PR 40: the ``hub-150-full-p2p.node-sync`` cell on the CPU, tiny: its
rehearsal traced and untraced (peers in processes of their own, the corrupted
and the silent pass, no child left behind), the refusal of a program without
the seams, the plain reference of the wire (it imports nothing of the program;
what it explains and what it does not), the seven ``wire_*`` readers on
synthetic spans and marks and on a program without them, and what the cell
lists: by membership and containment, never by position."""

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
    # (h): a request timed out, the pass completed inside its bound
    silent = notes["silent_peer"]
    assert silent["pool"]["timed_out"] > 0 and silent["applied"] == 8
    assert silent["seconds"] <= silent["bound_s"]
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
