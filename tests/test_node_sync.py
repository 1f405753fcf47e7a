"""PR 40: a node catching up from real peers over the wire, at rehearsal sizes
(24 validators, 9 blocks of 40 transactions): ``Node``s over TCP loopback
with ``SecretConnection`` sync the chain and end where the plain references
say (app hash, every stored byte, the wire's byte count); a peer that serves a
corrupted block and one that stops answering do not stop the sync; the block
pool asks a timed-out height of another peer and does not punish an honest
late answer; ``Node.start()`` -> ``stop()`` leaks no thread, port or sqlite
handle; the connections count what they move without reading a clock."""

import collections
import os
import socket
import threading
import time
import types

import pytest

from benchmark.harness import datagen, record, spec
from benchmark.reference import block_replay, wire_sync
from tendermint_tpu.blockchain import reactor as bc
from tendermint_tpu.encoding import proto
from tendermint_tpu.p2p import connection as mconn
from tendermint_tpu.p2p.switch import _sum_counters, counters_since
from tendermint_tpu.utils.flowrate import Monitor

CELL = "hub-150-full-p2p.node-sync"


def _wait(cond, timeout, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# --- the chain, the serving nodes, the syncing node -----------------------------


def _driver(seed):
    """The benchmark's driver at rehearsal sizes, for what it makes: the
    chain, the tip's commit, a peer's home directory, the syncing node."""
    cell = spec.Cell(CELL)
    cfg = dict(cell.config)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
    ds = datagen.load_or_generate(cell.config_name + "-rehearse", cfg, seed)
    run = record.Run(cell=cell, seed=seed, seconds=1.0, traced=False,
                     rehearse=True)
    driver = cell.driver.Driver(run, ds, cell.traffic)
    driver.tip_commit = driver._tip_commit()
    return driver


def _serve(home):
    """An in-process serving node over a made home, as nodesync_peer.py
    starts one -> (node, what the driver needs of a peer)."""
    from benchmark.drivers import nodesync_peer
    from tendermint_tpu.node.node import Node, default_app

    node = Node(nodesync_peer.local_config(home), default_app("kvstore"))
    node.start()
    return node, types.SimpleNamespace(
        addr=node.p2p_addr(), id=node.node_key.id(), home=home,
        cpu_s=lambda: 0.0, range=(node.block_store.base, node.block_store.height))


@pytest.fixture(scope="module", params=[401, 2147484049])
def net(request, tmp_path_factory):
    """(driver, reference, two serving nodes' handles) of one seed."""
    driver = _driver(request.param)
    tmp = str(tmp_path_factory.mktemp("homes"))
    homes = [os.path.join(tmp, f"peer-{i}") for i in range(2)]
    driver._make_home(homes[0], driver.chain.raws)
    driver._make_home(homes[1], driver.chain.raws, state_from=homes[0])
    started = [_serve(home) for home in homes]
    driver.peers = [handle for _node, handle in started]
    ref = driver._reference(driver.chain.raws, ())
    yield driver, ref, started
    for node, _handle in started:
        node.stop()
        node.close_stores()


def _sync(driver, peers):
    """One pass of the driver through Node.start() -> its record."""
    return driver._pass(peers, lambda fn, _sigs: fn())


def test_two_nodes_over_tcp_sync_the_chain(net):
    driver, ref, _started = net
    record_ = _sync(driver, driver.peers)
    assert record_.applied == driver.heights == 8
    # the references' app hash and results hash, every stored byte of every
    # height from reopened files, the index
    assert driver._differs(record_, ref) is None
    # every block once on 0x40, plus whole status messages; 1,044-byte frames
    assert driver._wire_differs(record_, ref) is None
    got = record_.wire["channels"]["0x40"]
    want = wire_sync.a_pass(driver.chain.raws)
    assert got["msgs_recv"] == want["msgs"] + sum(record_.status_msgs.values())
    assert got["bytes_recv"] > want["bytes"] == sum(
        wire_sync.block_response_len(len(raw)) for raw in driver.chain.raws)
    assert record_.wire["sealed_bytes_recv"] == 1044 * record_.wire["frames_recv"]
    assert record_.pool == {"received": 9, "timed_out": 0, "peers_stopped": 0}


def test_a_wrong_byte_count_is_not_explained(net):
    driver, ref, _started = net
    record_ = _sync(driver, driver.peers)
    assert driver._wire_differs(record_, ref) is None
    record_.wire["channels"]["0x40"]["bytes_recv"] += 1
    assert "explains" in driver._wire_differs(record_, ref)


def test_a_peer_that_serves_a_corrupted_block_does_not_stop_the_sync(
        net, tmp_path):
    driver, ref, started = net
    honest = driver.peers[0]
    made = {}

    def copy(bad_id):
        made["raws"], made["at"] = driver._corrupted_chain(bad_id, honest.id)
        return made["raws"]

    home = str(tmp_path / "bad")
    driver._make_home(home, copy, state_from=honest.home)
    node, bad = _serve(home)
    try:
        want = wire_sync.corrupted(
            driver.ds.chain_id, driver._genesis_keys(), driver.chain.raws,
            made["raws"], [b.hash for b in driver.chain.block_ids])
        assert want["refused"][:2] == (made["at"], "commit_block_id")
        assert want["heights"] == [made["at"]] and want["completes"]
        record_ = _sync(driver, [bad, honest])
        assert record_.invalid is not None, "the bad block was never served"
        assert record_.invalid[0] == made["at"]
        assert record_.invalid[1] == "ValueError"
        assert bad.id in record_.invalid[3] and bad.id in record_.scored
        assert set(record_.invalid[3]) <= set(record_.scored)
        assert record_.pool["peers_stopped"] >= len(record_.invalid[3])
        # and the pass ended at the last height with the reference's state,
        # every stored byte the clean chain's: through the honest peer
        assert record_.applied == driver.heights
        assert driver._differs(record_, ref) is None
    finally:
        node.stop()
        node.close_stores()


def test_a_peer_that_stops_answering_does_not_stop_the_sync(net, monkeypatch):
    driver, ref, started = net
    monkeypatch.setattr(bc, "REQUEST_TIMEOUT_S", 1.0)   # the test's, not the program's
    silent_node, silent = started[1][0], driver.peers[1]
    answered = []
    receive = silent_node.bc_reactor.receive

    def deaf_after_one(ch_id, peer, msg_bytes):
        if 1 in proto.fields(msg_bytes):
            answered.append(1)
            if len(answered) > 1:
                return None
        return receive(ch_id, peer, msg_bytes)

    monkeypatch.setattr(silent_node.bc_reactor, "receive", deaf_after_one)
    t0 = time.monotonic()
    record_ = _sync(driver, [silent, driver.peers[0]])
    assert record_.applied == driver.heights
    assert record_.pool["timed_out"] > 0 and record_.pool["peers_stopped"] >= 1
    assert not record_.scored            # silence is the network's, not a lie
    assert time.monotonic() - t0 < 30
    assert driver._differs(record_, ref) is None


def test_start_and_stop_three_times_leaks_nothing(net):
    driver, _ref, _started = net

    def census():
        names = collections.Counter(
            t.name.split("-")[0] + "-" + t.name.split("-")[1]
            if t.name.count("-") > 1 else t.name
            for t in threading.enumerate())
        fds = []
        for fd in os.listdir("/proc/self/fd"):
            try:
                fds.append(os.readlink(f"/proc/self/fd/{fd}"))
            except OSError:
                pass
        return names, collections.Counter(
            "socket" if f.startswith("socket:") else f for f in fds)

    _sync(driver, driver.peers)          # what the first node of a process leaves
    time.sleep(0.5)
    threads0, fds0 = census()
    for _ in range(3):
        assert _sync(driver, driver.peers).applied == driver.heights
    assert _wait(lambda: not census()[0] - threads0, 10), census()[0] - threads0
    assert _wait(lambda: not census()[1] - fds0, 10), census()[1] - fds0


def test_a_stopped_switch_takes_no_peer_and_frees_its_port(net):
    """A dial or an accept in flight when stop() ran adds no peer, and the
    listener is gone at once: nothing waits for one more peer to dial."""
    driver, _ref, _started = net
    node, _home = driver._node(driver.peers)
    node.start()
    assert _wait(lambda: len(node.switch.peers) == 2, 10)
    host, port = node.transport.node_info.listen_addr.split("://")[1].split(":")
    node.stop()
    node.close_stores()
    assert not node.switch.peers
    with pytest.raises(OSError):
        socket.create_connection((host, int(port)), timeout=1.0)
    assert node.switch.dial_peer(driver.peers[0].addr) is None
    assert not node.switch.peers


# --- the pool ---------------------------------------------------------------------


def _block(height):
    return types.SimpleNamespace(header=types.SimpleNamespace(height=height))


def _pool(peers=("a", "b"), top=40):
    pool = bc.BlockPool(1)
    for pid in peers:
        pool.set_peer_range(pid, 1, top)
    return pool


def test_heights_fall_on_the_peers_in_the_order_of_their_ids():
    pool = bc.BlockPool(1)
    pool.set_peer_range("b", 1, 40)      # reported first
    pool.set_peer_range("a", 1, 40)
    asked = dict(pool.wanted_requests())
    assert len(asked) == bc.REQUEST_WINDOW
    assert all(pid == ("a", "b")[h % 2] for h, pid in asked.items())
    assert pool.wanted_requests() == []  # nothing is asked twice
    assert pool.sizes() == (bc.REQUEST_WINDOW, 0)


def test_a_timed_out_height_is_asked_of_another_peer():
    pool = _pool()
    asked = dict(pool.wanted_requests())
    now = time.monotonic()
    for h, pid in asked.items():
        if pid == "a":
            pool.add_block("a", _block(h))
    assert pool.expire_requests(now + bc.REQUEST_TIMEOUT_S - 1) == []
    assert pool.expire_requests(now + bc.REQUEST_TIMEOUT_S + 1) == ["b"]
    mine = [h for h, pid in asked.items() if pid == "b"]
    assert pool.timed_out == len(mine) and "b" not in pool.peers
    again = dict(pool.wanted_requests())
    assert sorted(again) == mine and set(again.values()) == {"a"}
    for h in again:
        pool.add_block("a", _block(h))           # answered: nothing is open
    assert pool.expire_requests(now + 10 * bc.REQUEST_TIMEOUT_S) == []


def test_an_honest_late_answer_is_not_punished():
    pool = _pool()
    asked = dict(pool.wanted_requests())
    late = next(h for h, pid in asked.items() if pid == "b")
    pool.expire_requests(time.monotonic() + bc.REQUEST_TIMEOUT_S + 1)
    pool.wanted_requests()
    pool.add_block("a", _block(late))
    before = (pool.received, dict(pool.blocks))
    pool.add_block("b", _block(late))            # b wakes up and answers
    assert (pool.received, pool.blocks) == before
    assert pool.blocks[late][1] == "a" and not pool.refused
    # one that answers a height nobody holds yet is taken like any other
    other = next(h for h in asked if h not in pool.blocks)
    pool.add_block("b", _block(other))
    assert pool.blocks[other][1] == "b"


def test_a_refused_height_is_asked_of_the_other_peer_while_there_is_one():
    pool = _pool()
    asked = dict(pool.wanted_requests())
    for h, pid in asked.items():
        pool.add_block(pid, _block(h))
    first, second = asked[4], asked[5]
    assert (pool.redo_request(4), pool.redo_request(5)) == (first, second)
    again = dict(pool.wanted_requests())
    assert again[4] != first and again[5] != second
    # with the other peer gone, the one that was refused is asked again
    pool.remove_peer(again[4])
    assert dict(pool.wanted_requests())[4] == first
    # what is remembered goes with the height
    pool.height = 4
    pool.blocks[4] = (_block(4), first)
    pool.pop_request()
    assert 4 not in pool.refused


# --- what the connections count ---------------------------------------------------


class _Plain:
    """A secret connection's shape over a socket, unsealed."""

    def __init__(self, sock):
        self._sock = sock

    def write(self, data):
        self._sock.sendall(data)

    def read(self, n):
        return self._sock.recv(n)

    def close(self):
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._sock.close()


@pytest.mark.parametrize("size", [0, 1, 1024, 1025, 1_070_000])
def test_a_connection_counts_packets_messages_and_bytes(size):
    a, b = socket.socketpair()
    got = []
    done = threading.Event()
    desc = [mconn.ChannelDescriptor(0x40, recv_message_capacity=2_000_000)]
    sender = mconn.MConnection(_Plain(a), desc, lambda ch, msg: None,
                               send_rate=0, recv_rate=0)
    receiver = mconn.MConnection(
        _Plain(b), desc, lambda ch, msg: (got.append(msg), done.set()),
        send_rate=0, recv_rate=0)
    sender.start()
    receiver.start()
    try:
        msg = os.urandom(size)
        assert sender.send(0x40, msg)
        assert done.wait(20) and got == [msg]
        want = wire_sync.packets(size)
        sent, received = sender.wire_counters(), receiver.wire_counters()
        assert (sent["packets_sent"], sent["msgs_sent"], sent["bytes_sent"]) \
            == (want, 1, size)
        assert (received["packets_recv"], received["msgs_recv"],
                received["bytes_recv"]) == (want, 1, size)
        assert received["channels"]["0x40"] == dict(zip(
            mconn.WIRE_KEYS, (0, 0, 0, want, 1, size)))
        assert sent["recv_blocked_s"] == received["send_blocked_s"] == 0.0
    finally:
        sender.stop()
        receiver.stop()


def test_sealed_frames_are_1044_bytes_and_counted():
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.p2p.secret_connection import SecretConnection

    a, b = socket.socketpair()
    ends = [None, None]

    def shake(i, sock, seed):
        ends[i] = SecretConnection(sock, ed25519.gen_priv_key(bytes([seed]) * 32))

    threads = [threading.Thread(target=shake, args=(0, a, 7)),
               threading.Thread(target=shake, args=(1, b, 9))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    left, right = ends
    before = (left.frames_sent, right.frames_recv)
    assert before[0] == before[1] == 1           # the handshake's one frame
    left.write(os.urandom(1034))                 # a full packet: two frames
    got = b""
    while len(got) < 1034:
        got += right.read(4096)
    assert (left.frames_sent, right.frames_recv) == (3, 3)
    assert wire_sync.frames(1034) == 2
    assert left.sealed_bytes_sent == right.sealed_bytes_recv == 3 * 1044
    left.close()
    right.close()


def test_the_limiter_counts_the_time_it_slept_in_whole_periods():
    monitor = Monitor(sample_period_s=0.01)
    assert monitor.limit(100, 10_000, block=True) == 100 and monitor.blocked_s == 0
    monitor.update(100)                          # the head start is spent
    assert monitor.limit(100, 10_000, block=True) >= 1
    assert monitor.blocked_s == pytest.approx(0.01)
    assert Monitor().limit(100, 0) == 100        # unlimited never sleeps


def test_a_stopped_peers_counts_stay_in_the_switchs_totals():
    total = {}
    _sum_counters(total, {"packets_recv": 3, "channels": {"0x40": {"bytes_recv": 7}}})
    _sum_counters(total, {"packets_recv": 2, "channels": {"0x40": {"bytes_recv": 1},
                                                          "0x20": {"bytes_recv": 5}}})
    assert total == {"packets_recv": 5, "channels": {"0x40": {"bytes_recv": 8},
                                                     "0x20": {"bytes_recv": 5}}}
    assert counters_since(total, {"packets_recv": 4, "channels": {
        "0x40": {"bytes_recv": 8}}}) == {
        "packets_recv": 1, "channels": {"0x40": {"bytes_recv": 0},
                                        "0x20": {"bytes_recv": 5}}}


def test_the_references_count_what_the_program_encodes():
    raw = os.urandom(70_000)
    block = types.SimpleNamespace(marshal=lambda: raw)
    assert wire_sync.block_response_len(len(raw)) == len(bc.msg_block_response(block))
    assert wire_sync.status_request_len() == len(bc.msg_status_request())
    for height, base in ((41, 1), (0, 0), (9, 1), (300, 128)):
        assert wire_sync.status_response_len(height, base) == len(
            bc.msg_status_response(height, base))
        assert wire_sync.block_request_len(height) == len(
            bc.msg_block_request(height))
    for chunk, eof in ((1024, False), (1024, True), (7, True), (0, True)):
        pm = (proto.Writer().varint(1, 0x40).bool(2, eof)
              .bytes(3, b"x" * chunk).out())
        packet = proto.Writer().message(3, pm, always=True).out()
        assert wire_sync.packet_len(chunk, eof) == len(proto.delimited(packet))
    assert block_replay.PART_SIZE == 65536
