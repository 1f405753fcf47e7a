"""Overload-resilience plane (docs/OVERLOAD.md): peer misbehavior scoring
with escalating disconnect/ban sanctions, ingress rate limiting (recv-side
flow control + per-channel message ceilings), priority load shedding, the
broadcast_tx admission gate, and the nemesis `flood` action.

Quick tier: scoreboard/ban-lifecycle units (simulated clock), shed-queue
and rate-limiter units, the recv-throttle regression, mempool-flood
scoring (gossip/recv threads survive a full mempool), ban refusal at the
dial AND accept seams, the RPC admission gate, and a 2-node in-process
flood smoke — a flooding low-power validator is banned while the majority
keeps committing.

Slow tier: the 4-node mesh scenario from the acceptance criteria — one
peer floods invalid-signature votes (nemesis flood action) + oversized
txs; the flooder is banned on the honest nodes (metric increments, redial
refused, post-ban traffic never reaches the drain) and the honest 3/4
keep committing. Failures print the TMTPU_* repro line.
"""

import os
import queue as _stdqueue
import socket as _socket
import threading
import time
import urllib.request

import pytest

from test_nemesis import (  # the in-process socketpair mesh helpers
    _PlainConn,
    _link,
    _stop_all,
    _wait,
    repro,
)

from tendermint_tpu.config.config import test_config as make_test_config
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.p2p.key import NodeKey
from tendermint_tpu.privval.file_pv import MockPV
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.utils import faults, lockwitness, nemesis, peerscore, trace

SEED = 2027
VOTE_CH = 0x22


@pytest.fixture(autouse=True)
def _clean_chaos():
    faults.configure([], seed=SEED)
    nemesis.clear()
    yield
    nemesis.clear()
    nemesis.PLANE.on_heal.clear()
    faults.clear()


def _board(clock, **kw):
    defaults = dict(halflife_s=100.0, disconnect_score=20.0, ban_score=40.0,
                    ban_duration_s=10.0, ban_max_duration_s=35.0)
    defaults.update(kw)
    return peerscore.PeerScoreBoard(peerscore.ScoreConfig(**defaults),
                                    clock=clock)


# ---------------------------------------------------------------------------
# Scoreboard units (simulated time)
# ---------------------------------------------------------------------------


def test_score_decay_over_simulated_time():
    t = [0.0]
    b = _board(lambda: t[0])
    b.record("p1", "invalid_signature")  # 8 points
    assert b.score("p1") == pytest.approx(8.0)
    t[0] = 100.0  # one half-life
    assert b.score("p1") == pytest.approx(4.0)
    t[0] = 300.0  # three half-lives
    assert b.score("p1") == pytest.approx(1.0)
    # an unknown offense scores 1 point; unattributed reports score no one
    assert b.record("p2", "???") == peerscore.SANCTION_NONE
    assert b.score("p2") == pytest.approx(1.0)
    assert b.record("", "invalid_signature") == peerscore.SANCTION_NONE
    # fully-decayed entries are pruned from the books (anti-DoS hygiene)
    t[0] = 5000.0
    assert b.snapshot()["scores"] == {}


def test_disconnect_fires_at_and_above_threshold():
    t = [0.0]
    b = _board(lambda: t[0])
    hits = []
    b.on_disconnect.append(lambda pid, reason: hits.append(pid))
    b.record("p1", "bad_message")  # 10 < 20: no sanction yet
    assert not hits
    assert b.record("p1", "bad_message") == peerscore.SANCTION_DISCONNECT
    assert hits == ["p1"]
    # EVERY further offense above the threshold re-fires: a redialing
    # peer pacing its score inside [disconnect, ban) must not misbehave
    # sanction-free
    assert b.record("p1", "checktx_reject") == peerscore.SANCTION_DISCONNECT
    assert hits == ["p1", "p1"]


def test_ban_expiry_and_reoffense_backoff():
    t = [0.0]
    b = _board(lambda: t[0])
    banned = []
    b.on_ban.append(lambda pid, until: banned.append((pid, until)))
    for _ in range(4):  # 4 x 10 crosses ban_score 40
        b.record("p1", "bad_message")
    assert b.is_banned("p1") and banned and banned[0][1] == pytest.approx(10.0)
    assert b.score("p1") == 0.0  # ban resets the score
    t[0] = 9.9
    assert b.is_banned("p1")
    t[0] = 10.1  # expiry is lazy but exact
    assert not b.is_banned("p1")
    # re-offense: duration doubles (10 -> 20)
    for _ in range(4):
        b.record("p1", "bad_message")
    assert b.is_banned("p1") and banned[1][1] == pytest.approx(t[0] + 20.0)
    t[0] += 20.1
    # third offense: 40 would exceed the cap -> clamped at 35
    for _ in range(4):
        b.record("p1", "bad_message")
    assert banned[2][1] == pytest.approx(t[0] + 35.0)
    d = b.describe()
    assert d["ban_counts"]["p1"] == 3 and d["bans_total"] == 3
    assert d["offenses"]["p1:bad_message"] == 12


def test_describe_and_snapshot_shapes():
    t = [0.0]
    b = _board(lambda: t[0])
    b.record("px", "invalid_signature")
    b.count_shed("vote")
    b.count_rate_limited("px", "0x22")
    d = b.describe()
    assert d["scores"]["px"] == pytest.approx(8.0)
    assert d["shed"] == {"vote": 1} and d["rate_limited"] == {"px:0x22": 1}
    assert d["config"]["ban_score"] == 40.0
    s = b.snapshot()
    assert s["bans_total"] == 0 and s["rate_limited"] == {("px", "0x22"): 1}


def test_honest_overload_rates_never_sanction():
    """The review-hardened tuning: offenses an HONEST peer emits
    continuously while WE are overloaded (full mempool, app rejects)
    must never cross the default disconnect threshold at honest gossip
    rates — equilibrium = points * rate * halflife/ln2."""
    t = [0.0]
    b = peerscore.PeerScoreBoard(clock=lambda: t[0])  # default config
    # 10 tx/s into a full/rejecting mempool for 10 simulated minutes
    for i in range(6000):
        t[0] = i * 0.1
        off = "mempool_full" if i % 2 else "checktx_reject"
        assert b.record("honest01", off) == peerscore.SANCTION_NONE
    assert b.score("honest01") < b.config.disconnect_score
    # ...while a 500/s flood of the same offense still bans in seconds
    t2 = [0.0]
    b2 = peerscore.PeerScoreBoard(clock=lambda: t2[0])
    sanction = None
    for i in range(10000):
        t2[0] = i * 0.002
        sanction = b2.record("flooder", "mempool_full")
        if sanction == peerscore.SANCTION_BAN:
            break
    assert sanction == peerscore.SANCTION_BAN and t2[0] < 15.0


# ---------------------------------------------------------------------------
# Shed queue + rate limiter units
# ---------------------------------------------------------------------------


def test_shed_queue_priorities_and_fifo():
    shed = []
    q = peerscore.ShedQueue(maxsize=3, on_shed=shed.append)
    assert q.put("s0", priority=peerscore.PRIO_STALE, channel="vote")
    assert q.put("f0", priority=peerscore.PRIO_FUTURE, channel="block_part")
    assert q.put("l0", priority=peerscore.PRIO_LIVE, channel="vote")
    # full: a live arrival evicts the oldest lowest class (the stale one)
    assert q.put("l1", priority=peerscore.PRIO_LIVE, channel="vote")
    # full of future+live: another stale arrival sheds itself
    assert not q.put("s1", priority=peerscore.PRIO_STALE, channel="vote")
    # equal-lowest arrival (future vs future) sheds the arrival, not the queue
    assert not q.put("f1", priority=peerscore.PRIO_FUTURE, channel="block_part")
    # control items are always admitted, even over capacity
    q.put(None)
    assert q.qsize() == 4
    # admitted items drain in arrival order
    assert [q.get_nowait() for _ in range(4)] == ["f0", "l0", "l1", None]
    with pytest.raises(_stdqueue.Empty):
        q.get_nowait()
    assert q.shed_counts == {"vote": 2, "block_part": 1}
    assert shed == ["vote", "vote", "block_part"]


def test_shed_queue_get_timeout_and_unbounded():
    q = peerscore.ShedQueue(maxsize=0)  # unbounded: never sheds
    for i in range(50):
        assert q.put(i, priority=peerscore.PRIO_STALE, channel="vote")
    assert q.qsize() == 50 and not q.shed_counts
    q2 = peerscore.ShedQueue(maxsize=10)
    t0 = time.monotonic()
    with pytest.raises(_stdqueue.Empty):
        q2.get(timeout=0.05)
    assert time.monotonic() - t0 >= 0.04


def test_rate_spec_and_token_bucket():
    rates = peerscore.parse_rate_spec("0x22:5, 0x30:100")
    assert rates == {0x22: 5.0, 0x30: 100.0}
    for bad in ("0x22", "0x22:0", "0x22:-1"):
        with pytest.raises(ValueError):
            peerscore.parse_rate_spec(bad)
    t = [0.0]
    rl = peerscore.ChannelRateLimiter({1: 5.0}, clock=lambda: t[0])
    assert sum(rl.allow(1) for _ in range(20)) == 5  # the 1s burst
    t[0] = 0.4  # 2 tokens refill
    assert sum(rl.allow(1) for _ in range(20)) == 2
    assert all(rl.allow(9) for _ in range(100))  # unconfigured: unlimited
    # fractional rates must accumulate to a deliverable token, not
    # silently blackhole the channel (burst cap is >= one message)
    rl2 = peerscore.ChannelRateLimiter({2: 0.5}, clock=lambda: t[0])
    assert rl2.allow(2) and not rl2.allow(2)
    t[0] += 2.0  # 0.5/s * 2s = 1 token
    assert rl2.allow(2) and not rl2.allow(2)


# ---------------------------------------------------------------------------
# MConnection: recv throttle regression + per-channel ceilings
# ---------------------------------------------------------------------------


def _mconn_pair(recv_rate=5_120_000, msg_rates=None, on_rate_limited=None):
    from tendermint_tpu.p2p.connection import ChannelDescriptor, MConnection

    sa, sb = _socket.socketpair()
    received = []
    a = MConnection(_PlainConn(sa), [ChannelDescriptor(id=1)],
                    on_receive=lambda *x: None, local_id="aaaa",
                    remote_id="bbbb")
    b = MConnection(_PlainConn(sb), [ChannelDescriptor(id=1)],
                    on_receive=lambda ch, msg: received.append((ch, msg)),
                    local_id="bbbb", remote_id="aaaa", recv_rate=recv_rate,
                    msg_rates=msg_rates, on_rate_limited=on_rate_limited)
    a.start()
    b.start()
    return a, b, received


def test_recv_rate_throttles_a_fast_sender():
    """ISSUE 5 satellite 1: recv_monitor.limit is actually wired — a
    sender pushing ~64 KB against a 64 KB/s recv_rate must be held to
    roughly the configured rate (was: recv_monitor constructed but
    limit() never called; the flood arrived as fast as TCP allowed)."""
    payload = os.urandom(8 * 1024)
    a, b, received = _mconn_pair(recv_rate=64_000)
    try:
        t0 = time.monotonic()
        for _ in range(8):
            assert a.send(1, payload)
        assert _wait(lambda: len(received) == 8, 15, 0.01), \
            f"only {len(received)}/8 messages arrived"
        elapsed = time.monotonic() - t0
        # ~65 KB of frames at 64 KB/s ≈ 1s; the monitor's first sample
        # window grants a head start, so assert a generous lower bound
        # (unthrottled, the same transfer completes in < 50 ms)
        assert elapsed > 0.4, f"recv side not throttled: {elapsed:.3f}s"
        assert received[0][1] == payload
    finally:
        a.stop()
        b.stop()


def test_per_channel_message_ceiling_scores_not_processes():
    limited = []
    a, b, received = _mconn_pair(msg_rates={1: 3.0},
                                 on_rate_limited=limited.append)
    try:
        for i in range(12):
            assert a.send(1, b"m%d" % i)
        _wait(lambda: len(received) + len(limited) >= 12, 10, 0.01)
        # the 1s burst admits ~3 (+ trickle refill); the rest are reported
        # to the scoring callback instead of the reactor
        assert 3 <= len(received) <= 6, received
        assert len(limited) >= 6 and set(limited) == {1}
        # admitted messages kept arrival order
        assert [m for _, m in received] == [b"m%d" % i
                                            for i in range(len(received))]
    finally:
        a.stop()
        b.stop()


# ---------------------------------------------------------------------------
# Mempool gossip scoring (satellite 2)
# ---------------------------------------------------------------------------


class _FakeSwitchWithBoard:
    def __init__(self, clock=time.monotonic):
        self.scoreboard = peerscore.PeerScoreBoard(clock=clock)


class _FakePeer:
    def __init__(self, pid):
        self.id = pid


def test_full_mempool_scores_peer_and_never_kills_gossip_thread():
    from tendermint_tpu.abci.types import Application
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.mempool.reactor import MempoolReactor, msg_txs

    mp = Mempool(Application(), max_txs=1, max_tx_bytes=64)
    r = MempoolReactor(mp, broadcast=False)
    r.switch = _FakeSwitchWithBoard()
    board = r.switch.scoreboard
    peer = _FakePeer("flooder01")
    r.receive(0x30, peer, msg_txs([b"tx-one"]))  # fills the pool
    assert mp.size() == 1 and board.score("flooder01") == 0.0
    # a flood into the full pool: scored, swallowed, thread alive
    for i in range(30):
        r.receive(0x30, peer, msg_txs([b"tx-flood-%d" % i]))
    assert board.score("flooder01") > 0
    assert board.describe()["offenses"]["flooder01:mempool_full"] == 30
    # oversized tx: its own (heavier) offense
    r.receive(0x30, peer, msg_txs([b"x" * 100]))
    assert board.describe()["offenses"]["flooder01:tx_too_large"] == 1
    # an app blowing up mid-CheckTx must not propagate into the recv
    # thread — and must NOT score the peer (it is OUR failure; scoring it
    # would ban every honest gossiper during an ABCI app outage)
    mp.flush()  # make room so the tx reaches the app at all
    before = board.score("flooder01")

    def boom(req):
        raise RuntimeError("app crashed")
    mp.app.check_tx = boom
    r.receive(0x30, peer, msg_txs([b"tx-late"]))
    assert board.score("flooder01") <= before
    assert "flooder01:checktx_reject" not in board.describe()["offenses"]


# ---------------------------------------------------------------------------
# Ban enforcement seams: dial side, accept side, reconnect loop
# ---------------------------------------------------------------------------


def test_dial_refused_for_banned_peer_without_touching_transport():
    from tendermint_tpu.p2p import switch as sw
    from tendermint_tpu.p2p.node_info import NodeInfo

    nk = NodeKey(ed25519.gen_priv_key(b"\x61" * 32))
    t = sw.Transport(nk, NodeInfo(node_id=nk.id(), network="x", moniker="m"))
    s = sw.Switch(t)
    dialed = []

    def fake_dial(addr):
        dialed.append(addr)
        raise OSError("stub transport")

    t.dial = fake_dial
    s.scoreboard.ban("badpeer")
    assert s.dial_peer("badpeer@127.0.0.1:1") is None
    assert not dialed  # refused BEFORE the transport opened a socket
    s.scoreboard.unban("badpeer")
    assert s.dial_peer("badpeer@127.0.0.1:1") is None  # stub dial fails
    assert dialed  # ...but the transport was consulted once unbanned


def test_reconnect_pass_skips_banned_persistent_peer():
    from tendermint_tpu.p2p import switch as sw

    t = [0.0]
    s = sw.Switch.__new__(sw.Switch)
    s.peers = {}
    s.logger = None
    s.scoreboard = _board(lambda: t[0], ban_duration_s=10.0)
    s._persistent_addrs = ["peerX@127.0.0.1:1"]
    s._reconnect_attempts = {}
    s._reconnect_next_try = {}
    dials = []
    s.dial_peer = lambda addr, persistent=False: dials.append(addr) or None
    s.scoreboard.ban("peerX")
    s._reconnect_pass(s._reconnect_attempts, s._reconnect_next_try)
    assert not dials and not s._reconnect_attempts  # no backoff burned
    t[0] = 10.1  # ban expired: retried immediately on the next pass
    s._reconnect_pass(s._reconnect_attempts, s._reconnect_next_try)
    assert dials == ["peerX@127.0.0.1:1"]


def test_transport_upgrade_seam_checks_bans_and_scores_evil_handshake():
    from tendermint_tpu.p2p import switch as sw
    from tendermint_tpu.p2p.node_info import NodeInfo

    nk = NodeKey(ed25519.gen_priv_key(b"\x62" * 32))
    t = sw.Transport(nk, NodeInfo(node_id=nk.id(), network="x", moniker="m"))
    s = sw.Switch(t)
    # the switch wires both hooks at construction (bound methods compare
    # by ==, not identity)
    assert t.ban_checker == s.scoreboard.is_banned
    s.scoreboard.ban("bannedX")
    assert t.ban_checker("bannedX") and not t.ban_checker("cleanY")
    t.on_evil_handshake("liar-authenticated-id")
    # real-clock board: allow for decay between record and read (a loaded
    # test box can stall seconds between the two)
    pts = peerscore.OFFENSE_POINTS["evil_handshake"]
    assert 0.5 * pts < s.scoreboard.score("liar-authenticated-id") <= pts


# ---------------------------------------------------------------------------
# Consensus drain attribution (the batched bitmap seam)
# ---------------------------------------------------------------------------


def test_vote_drain_bitmap_attributes_invalid_lanes_to_peers():
    from tendermint_tpu.consensus.state_machine import ConsensusState, MsgInfo

    cs = ConsensusState.__new__(ConsensusState)
    cs.logger = None
    cs.tracer = trace.Tracer()
    cs.scoreboard = peerscore.PeerScoreBoard()
    cs.on_work_done = []
    applied = []
    cs._try_add_vote = lambda vote, peer_id, verified=False: applied.append(
        (peer_id, verified)) or True

    class _VM:
        vote = object()

    msgs = [MsgInfo(_VM(), "honest01"), MsgInfo(_VM(), "forger02"),
            MsgInfo(_VM(), "honest03")]
    cs._apply_vote_results(msgs, {0: True, 1: False, 2: True})
    # the FAILED lane scored its delivering peer; verified lanes did not
    # (real-clock board: allow for decay between record and read)
    pts = peerscore.OFFENSE_POINTS["invalid_signature"]
    assert 0.5 * pts < cs.scoreboard.score("forger02") <= pts
    assert cs.scoreboard.score("honest01") == 0.0
    assert [p for p, _ in applied] == ["honest01", "honest03"]


def test_serial_vote_path_scores_typed_invalid_signature():
    from tendermint_tpu.consensus.state_machine import (
        ConsensusState,
        MsgInfo,
        VoteMessage,
    )
    from tendermint_tpu.types.vote import ErrVoteInvalidSignature

    cs = ConsensusState.__new__(ConsensusState)
    cs.logger = None
    cs.scoreboard = peerscore.PeerScoreBoard()
    cs.on_work_done = []

    def raise_invalid(vote, peer_id, verified=False):
        raise ErrVoteInvalidSignature("invalid signature")

    cs._try_add_vote = raise_invalid
    cs._handle_msg(MsgInfo(VoteMessage(object()), "forger02"))  # must not raise
    assert cs.scoreboard.score("forger02") > 0


# ---------------------------------------------------------------------------
# RPC: admission gate + unsafe_peers route
# ---------------------------------------------------------------------------


class _RpcCfg:
    class rpc:
        unsafe = True
        max_broadcast_tx_inflight = 1


class _RpcEnv:
    def __init__(self, node):
        self.node = node


def test_broadcast_tx_admission_gate_typed_overload():
    from tendermint_tpu.rpc import core as rpc_core

    gate_open = threading.Event()
    entered = threading.Event()

    class _MP:
        def check_tx(self, raw):
            entered.set()
            gate_open.wait(5)

            class _Res:
                code, data, log, codespace = 0, b"", "", ""
            return _Res()

    class _Node:
        config = _RpcCfg()
        mempool = _MP()
        switch = None

    import base64 as _b64mod

    def tx(s):
        return _b64mod.b64encode(s).decode()

    env = _RpcEnv(_Node())
    results = []
    th = threading.Thread(
        target=lambda: results.append(
            rpc_core.broadcast_tx_sync(env, tx(b"a"))),
        daemon=True)
    th.start()
    assert entered.wait(5)
    # slot 1 is held inside CheckTx: the second request is refused with the
    # TYPED overload error, not queued
    with pytest.raises(rpc_core.ErrOverloaded, match="overloaded"):
        rpc_core.broadcast_tx_sync(env, tx(b"b"))
    gate_open.set()
    th.join(5)
    assert results and results[0]["code"] == 0
    # the slot was released: the next call passes
    gate_open.set()
    assert rpc_core.broadcast_tx_sync(env, tx(b"c"))["code"] == 0
    # limit 0 disables the gate entirely
    env.node.config.rpc.max_broadcast_tx_inflight = 0
    env.node._rpc_tx_gate = None
    assert rpc_core.broadcast_tx_sync(env, tx(b"d"))["code"] == 0


def test_unsafe_peers_route_view_and_manual_ban():
    from tendermint_tpu.rpc import core as rpc_core

    class _Switch:
        scoreboard = peerscore.PeerScoreBoard()

    class _Node:
        config = _RpcCfg()
        switch = _Switch()

    env = _RpcEnv(_Node())
    env.node.switch.scoreboard.record("p1", "invalid_signature")
    out = rpc_core.unsafe_peers(env)
    assert 4.0 < out["scores"]["p1"] <= 8.0  # real clock: decay tolerated
    out = rpc_core.unsafe_peers(env, ban="p9", duration=60)
    assert "p9" in out["banned"] and out["bans_total"] == 1
    out = rpc_core.unsafe_peers(env, unban="p9")
    assert "p9" not in out["banned"]
    with pytest.raises(ValueError):
        rpc_core.unsafe_peers(env, ban="")
    env.node.config.rpc.unsafe = False
    try:
        with pytest.raises(ValueError, match="unsafe"):
            rpc_core.unsafe_peers(env)
    finally:
        env.node.config.rpc.unsafe = True


# ---------------------------------------------------------------------------
# Nemesis flood action units
# ---------------------------------------------------------------------------


def test_flood_grammar_and_site_scoping():
    r = nemesis.LinkRule.parse("aa>*:flood~4")
    assert r.action == "flood" and r.param == 4.0
    nemesis.add_link(r)
    assert nemesis.outcome("p2p.send", "aa1", "zz1") == "flood"
    # send-side only: the receiving end of the same plane must not
    # re-amplify the corrupted copies
    assert nemesis.outcome("p2p.recv", "zz1", "aa1") == "pass"
    with pytest.raises(faults.FaultError):
        nemesis.outcome("p2p.dial", "aa1", "zz1")
    assert any(l.startswith("aa>*:flood") for l in
               nemesis.PLANE.describe()["links"])


def test_flood_payloads_seeded_and_corrupting():
    faults.configure([], seed=123)
    nemesis.add_link("aa>bb:flood~6")
    msg = bytes(range(200))
    p1 = nemesis.PLANE.flood_payloads("aa1", "bb1", VOTE_CH, msg)
    assert len(p1) == 6
    # even copies: same length, one byte flipped near the tail; odd
    # copies: padded (the unparseable/oversized class)
    for i, c in enumerate(p1):
        assert c != msg
        if i % 2 == 0:
            assert len(c) == len(msg)
            diff = [j for j in range(len(msg)) if c[j] != msg[j]]
            assert len(diff) == 1 and diff[0] >= len(msg) - 24
        else:
            assert len(c) == len(msg) + nemesis.FLOOD_PAD_BYTES
            assert c[:len(msg)] == msg
    # deterministic replay from the seed
    nemesis.PLANE.reset_counters()
    assert nemesis.PLANE.flood_payloads("aa1", "bb1", VOTE_CH, msg) == p1
    # a different seed produces a different schedule
    faults.configure([], seed=124)
    nemesis.PLANE.reset_counters()
    assert nemesis.PLANE.flood_payloads("aa1", "bb1", VOTE_CH, msg) != p1


# ---------------------------------------------------------------------------
# In-process flood scenarios
# ---------------------------------------------------------------------------


def _mk_weighted_genesis(powers):
    privs = [ed25519.gen_priv_key(bytes([80 + i]) * 32)
             for i in range(len(powers))]
    genesis = GenesisDoc(
        chain_id="overload-chain",
        genesis_time=Time(1700004000, 0),
        validators=[GenesisValidator(b"", p.pub_key(), w)
                    for p, w in zip(privs, powers)],
    )
    return genesis, privs


def _mk_node(tmp_path, i, genesis, priv, metrics=False, tweak=None):
    from tendermint_tpu.node.node import Node

    cfg = make_test_config()
    cfg.set_root(str(tmp_path / f"node{i}"))
    os.makedirs(cfg.base.root_dir, exist_ok=True)
    cfg.base.fast_sync_mode = False
    cfg.p2p.laddr = ""  # peered via socketpairs (no `cryptography` dep)
    cfg.rpc.laddr = ""
    cfg.consensus.wal_path = os.path.join(cfg.base.root_dir, "cs.wal")
    if metrics:
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
    if tweak is not None:
        tweak(cfg, i)
    node_key = NodeKey(ed25519.gen_priv_key(bytes([140 + i]) * 32))
    return Node(cfg, genesis=genesis, priv_validator=MockPV(priv),
                node_key=node_key)


def _relink_until(a, b, stop, timeout=60):
    """Keep relinking a<->b (the redial-and-repeat loop a real flooder
    runs) until ``stop()`` or the link is REFUSED (ban). Returns True if
    a refusal was observed."""
    from tendermint_tpu.p2p.switch import P2PError

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if stop():
            return True
        bid = b.node_key.id()
        if bid not in a.switch.peers:
            a.switch.stop_peer_by_id(bid, "relink")
            b.switch.stop_peer_by_id(a.node_key.id(), "relink")
            try:
                _link(a, b)
            except P2PError:
                return True  # refused: the ban seam closed the loop
            except Exception:  # noqa: BLE001 - teardown still in flight
                pass
        time.sleep(0.05)
    return stop()


def test_flood_smoke_single_node_flooding_peer_banned_no_stall(tmp_path):
    """ISSUE 5 satellite 5, the quick-tier flood smoke: a 1-power
    validator floods its 10-power peer through the nemesis flood action
    (every outbound message amplified with seeded corrupted copies —
    invalid-signature votes and unparseable junk). The victim must score
    the flooder to a ban, refuse its redials, and keep committing.

    Runs under the lock-order witness (utils/lockwitness.py): the flood
    drives the scoreboard/shed/rate-limit locks hard against the p2p and
    consensus locks, and the exit assert proves the acquisition order
    stays acyclic even on the overload paths."""
    genesis, privs = _mk_weighted_genesis([10, 1])
    with lockwitness.witness() as w:
        nodes = [_mk_node(tmp_path, i, genesis, privs[i]) for i in range(2)]
        ids = [n.node_key.id() for n in nodes]
        desc = f"link={ids[1]}>*:flood~8"
        _run_flood_smoke(nodes, ids, desc)
    assert w.acquires > 0 and len(w.edges) > 0


def _run_flood_smoke(nodes, ids, desc):
    try:
        with repro("flood smoke", desc):
            for n in nodes:
                n.start()
            _link(nodes[0], nodes[1])
            assert _wait(lambda: nodes[0].block_store.height >= 2, 30, 0.1), \
                "no initial progress"

            nemesis.add_link(f"{ids[1]}>*:flood~8")
            board = nodes[0].switch.scoreboard
            assert _relink_until(nodes[0], nodes[1],
                                 lambda: board.is_banned(ids[1]), 60), \
                f"flooder never banned; board={board.describe()}"
            assert board.is_banned(ids[1])
            # the drain attributed at least part of the flood to invalid
            # signatures out of the batched bitmap
            offenses = board.describe()["offenses"]
            assert any(k.startswith(f"{ids[1]}:") for k in offenses), offenses

            # redial refused at the switch seam without touching a socket
            assert nodes[0].switch.dial_peer(f"{ids[1]}@127.0.0.1:1") is None
            # ...and the in-process accept seam refuses a fresh link
            from tendermint_tpu.p2p.switch import P2PError

            with pytest.raises(P2PError, match="banned"):
                sa, sb = _socket.socketpair()
                try:
                    nodes[0].switch._add_peer(
                        _PlainConn(sa), nodes[1].transport.node_info,
                        outbound=False)
                finally:
                    sb.close()

            # no commit stall: the 10/11-power node keeps deciding alone
            h = nodes[0].block_store.height
            assert _wait(lambda: nodes[0].block_store.height >= h + 2,
                         30, 0.1), "victim stalled after banning the flooder"
    finally:
        _stop_all(nodes)


@pytest.mark.slow
def test_four_node_mesh_flooder_banned_majority_live(tmp_path):
    """Acceptance scenario: 4-node mesh, node3 floods invalid-signature
    votes (nemesis flood action) and oversized txs (its max_tx_bytes
    exceeds the honest nodes'); the flooder is banned on the honest nodes
    (ban metric increments, redial refused, post-ban traffic never
    reaches the drain) while the honest 3/4 majority keeps committing
    within the liveness bound. Deterministic under TMTPU_FAULT_SEED."""
    def tweak(cfg, i):
        # honest nodes reject txs over 256B; the flooder accepts (and
        # gossips) bigger ones — its tx gossip is oversized BY CONFIG at
        # every honest receiver, the second scoring feed of the scenario
        cfg.mempool.max_tx_bytes = 4096 if i == 3 else 256

    genesis, privs = _mk_weighted_genesis([10, 10, 10, 10])
    nodes = [_mk_node(tmp_path, i, genesis, privs[i], metrics=(i == 0),
                      tweak=tweak) for i in range(4)]
    ids = [n.node_key.id() for n in nodes]
    desc = f"link={ids[3]}>*:flood~8#{VOTE_CH:#x}"
    try:
        with repro("4-node flood ban", desc):
            for n in nodes:
                n.start()
            for i in range(4):
                for j in range(i):
                    _link(nodes[i], nodes[j])
            assert _wait(lambda: min(n.block_store.height
                                     for n in nodes) >= 2, 60, 0.1), \
                "no initial progress"

            # the flood: node3's VOTE-channel traffic is amplified with
            # corrupted copies (scoped with #0x22 so the scenario pins the
            # drain-bitmap attribution path, not the easier unparseable-
            # junk teardowns); plus a legitimately-submitted oversized tx
            # that every honest mempool rejects as too large
            nemesis.add_link(f"{ids[3]}>*:flood~8#{VOTE_CH:#x}")
            nodes[3].mempool.check_tx(b"oversized=" + b"x" * 1000)

            boards = [nodes[i].switch.scoreboard for i in range(3)]
            for i in range(3):
                assert _relink_until(nodes[i], nodes[3],
                                     lambda i=i: boards[i].is_banned(ids[3]),
                                     90), \
                    f"node{i} never banned the flooder: {boards[i].describe()}"
            # invalid-signature lanes out of the batched drain bitmap were
            # attributed to the flooder on at least one honest node
            assert any(
                b.describe()["offenses"].get(f"{ids[3]}:invalid_signature", 0)
                > 0 for b in boards), [b.describe()["offenses"]
                                       for b in boards]

            # post-ban: the flooder is torn down everywhere and its redial
            # is refused — its traffic can never reach the drain again
            from tendermint_tpu.p2p.switch import P2PError

            for i in range(3):
                assert ids[3] not in nodes[i].switch.peers
                assert nodes[i].switch.dial_peer(
                    f"{ids[3]}@127.0.0.1:1") is None
            with pytest.raises(P2PError, match="banned"):
                sa, sb = _socket.socketpair()
                try:
                    nodes[0].switch._add_peer(
                        _PlainConn(sa), nodes[3].transport.node_info,
                        outbound=False)
                finally:
                    sb.close()

            # the honest 3/4 keep committing within the liveness bound
            h = max(n.block_store.height for n in nodes[:3])
            assert _wait(lambda: min(n.block_store.height
                                     for n in nodes[:3]) >= h + 2, 60, 0.1), \
                ("honest majority stalled after banning the flooder: "
                 f"{[n.block_store.height for n in nodes]}")

            # ban metric incremented on node0's /metrics (sampler tick)
            def banned_metric():
                url = f"http://{nodes[0].metrics_server.addr}/metrics"
                body = urllib.request.urlopen(url, timeout=5).read().decode()
                line = next(l for l in body.splitlines()
                            if l.startswith("tendermint_p2p_peers_banned_total"))
                return float(line.rsplit(" ", 1)[1])
            assert _wait(lambda: banned_metric() >= 1.0, 15, 0.3), \
                "peers_banned_total never incremented on /metrics"
    finally:
        _stop_all(nodes)
