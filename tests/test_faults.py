"""Tier-1 smoke + unit tests for the deterministic fault-injection layer
(tendermint_tpu/utils/faults.py) and the device circuit breaker
(tendermint_tpu/ops/breaker.py).

Quick-tier by design (ISSUE satellite: the chaos layer must never silently
rot): one injected WAL torn-write and one injected device failure run on
every `-m 'not slow'` pass. The subprocess crash-recovery matrix and the
real-kernel breaker re-probe live in tests/test_fault_matrix.py (slow)."""

import io
import os
import time

import numpy as np
import pytest

from tendermint_tpu.utils import faults


class SimulatedCrash(Exception):
    """Stands in for os._exit so in-process tests observe the crash."""


@pytest.fixture(autouse=True)
def _clean_faults():
    old_crash = faults.REGISTRY.crash_fn
    yield
    faults.clear()
    faults.REGISTRY.crash_fn = old_crash
    # never leak an open circuit into later tests, even on assert failure
    import sys

    for mod in ("tendermint_tpu.ops.ed25519_batch",
                "tendermint_tpu.ops.sr25519_batch"):
        m = sys.modules.get(mod)
        if m is not None:
            m.BREAKER.reset()


def _raise_sim():
    raise SimulatedCrash()


# ---------------------------------------------------------------------------
# Registry: grammar, triggers, determinism
# ---------------------------------------------------------------------------


def test_rule_grammar():
    r = faults.Rule.parse("wal.write:torn@12")
    assert (r.site, r.action, r.nth, r.times) == ("wal.write", "torn", 12, 1)
    r = faults.Rule.parse("ops.ed25519.device:raise%0.5x2")
    assert (r.prob, r.times) == (0.5, 2)
    r = faults.Rule.parse("p2p.send:delay~0.02")
    assert r.param == 0.02 and r.nth is None and r.prob is None
    for bad in ("", "siteonly", "a.site:frobnicate", "a.site:raise@x"):
        with pytest.raises(ValueError):
            faults.Rule.parse(bad)


def test_nth_trigger_fires_exactly_once():
    faults.configure(["a.site:raise@3"], seed=1)
    fired = []
    for _ in range(6):
        try:
            faults.fire("a.site")
            fired.append(False)
        except faults.FaultInjected:
            fired.append(True)
    assert fired == [False, False, True, False, False, False]


def test_times_widens_nth():
    faults.configure(["a.site:raise@2x2"], seed=1)
    fired = []
    for _ in range(5):
        try:
            faults.fire("a.site")
            fired.append(False)
        except faults.FaultInjected:
            fired.append(True)
    assert fired == [False, True, True, False, False]


def test_prob_decisions_replay_from_seed():
    faults.configure(["b.site:drop%0.4"], seed=42)
    seq1 = [faults.maybe_drop("b.site") for _ in range(100)]
    assert any(seq1) and not all(seq1)
    faults.reset(seed=42)
    assert [faults.maybe_drop("b.site") for _ in range(100)] == seq1
    faults.reset(seed=43)
    assert [faults.maybe_drop("b.site") for _ in range(100)] != seq1


def test_per_site_counters_are_interleaving_independent():
    """The decision for hit k of a site depends only on (seed, site, k):
    interleaving another site's hits between them must not change it."""
    faults.configure(["x.site:drop%0.5", "y.site:drop%0.5"], seed=9)
    seq_x = [faults.maybe_drop("x.site") for _ in range(50)]
    faults.reset()
    inter = []
    for _ in range(50):
        faults.maybe_drop("y.site")
        inter.append(faults.maybe_drop("x.site"))
        faults.maybe_drop("y.site")
    assert inter == seq_x


def test_env_install(monkeypatch):
    monkeypatch.setenv("TMTPU_FAULTS", "c.site:raise@1")
    monkeypatch.setenv("TMTPU_FAULT_SEED", "77")
    faults.install_from_env()
    assert faults.REGISTRY.seed == 77
    with pytest.raises(faults.FaultInjected):
        faults.fire("c.site")
    faults.fire("c.site")  # exhausted


def test_disconnect_action_raises_fault_disconnect():
    faults.configure(["p2p.recv:disconnect@1"], seed=0)
    with pytest.raises(faults.FaultDisconnect):
        faults.maybe_drop("p2p.recv")


def test_env_install_keeps_programmatic_rules(monkeypatch):
    """Node.start() reloads the env config; with NOTHING in the env it must
    not wipe a schedule installed in-process via configure()."""
    monkeypatch.delenv("TMTPU_FAULTS", raising=False)
    faults.configure(["wal.fsync:raise@1"], seed=4)
    faults.install_from_env()
    with pytest.raises(faults.FaultInjected):
        faults.fire("wal.fsync")
    # an explicit env spec wins over the programmatic one
    monkeypatch.setenv("TMTPU_FAULTS", "abci.call:raise@1")
    faults.install_from_env()
    faults.fire("wal.fsync")  # old rule gone
    with pytest.raises(faults.FaultInjected):
        faults.fire("abci.call")


def test_p2p_send_disconnect_tears_down_connection():
    """A p2p.send:disconnect rule must behave like a transport error (peer
    teardown via on_error), never an exception into the sending thread."""
    from tendermint_tpu.p2p.connection import ChannelDescriptor, MConnection

    class _Conn:
        closed = False

        def close(self):
            self.closed = True

    errors = []
    conn = _Conn()
    mc = MConnection(conn, [ChannelDescriptor(id=1)],
                     on_receive=lambda *a: None,
                     on_error=errors.append)
    mc._running = True  # armed without spawning the socket threads
    faults.configure(["p2p.send:disconnect@1"], seed=0)
    assert mc.send(1, b"gossip") is False  # no exception escapes
    assert errors and isinstance(errors[0], faults.FaultDisconnect)
    assert conn.closed and not mc._running


def test_canonical_sites_registered():
    assert set(faults.CANONICAL_SITES) <= set(faults.sites())


def test_mismatched_action_fails_loudly():
    """A rule whose action the site cannot apply (torn at an fsync site,
    drop at a call site) must raise, not silently burn its trigger."""
    faults.configure(["wal.fsync:torn@1", "abci.call:drop@1"], seed=0)
    with pytest.raises(faults.FaultError):
        faults.fire("wal.fsync")
    with pytest.raises(faults.FaultError):
        faults.fire("abci.call")
    faults.configure(["p2p.recv:torn@1"], seed=0)
    with pytest.raises(faults.FaultError):
        faults.maybe_drop("p2p.recv")


def test_legacy_fail_index_counter(monkeypatch):
    faults.REGISTRY.crash_fn = _raise_sim
    monkeypatch.setenv("TMTPU_FAIL_INDEX", "2")
    monkeypatch.setattr(faults, "_legacy_counter", 0)
    faults.fail_point()
    faults.fail_point()
    with pytest.raises(SimulatedCrash):
        faults.fail_point()


# ---------------------------------------------------------------------------
# WAL torn-write smoke (the tier-1 injected WAL fault)
# ---------------------------------------------------------------------------


def _write_until_crash(wal_dir, spec, n=10, seed=11):
    from tendermint_tpu.consensus.wal import WAL, WALMessageBlob

    faults.REGISTRY.crash_fn = _raise_sim
    faults.configure([spec], seed=seed)
    w = WAL(wal_dir)
    n_ok = 0
    try:
        for i in range(n):
            w.write_sync(WALMessageBlob(kind="k", payload=b"p%d" % i), time_ns=i)
            n_ok += 1
    except SimulatedCrash:
        pass
    finally:
        w._head.close()  # simulate process death: no flush of buffers
    return n_ok


@pytest.mark.parametrize("action,expect_ok", [("torn", 4), ("partial", 4)])
def test_wal_torn_write_crash_and_repair(tmp_path, action, expect_ok):
    """A torn/partial frame left by a mid-append crash is truncated by the
    reopen repair; replay yields exactly the valid prefix and appends work."""
    from tendermint_tpu.consensus.wal import WAL, EndHeightMessage

    d = str(tmp_path / action)
    n_ok = _write_until_crash(d, f"wal.write:{action}@5")
    assert n_ok == 4
    # the crash left a damaged tail on disk
    chunk = os.path.join(d, "wal.000000")
    size = os.path.getsize(chunk)
    faults.clear()
    w2 = WAL(d)  # repair runs here
    msgs = [tm.msg for tm, _ in w2.iter_messages()]
    assert len(msgs) == expect_ok
    assert os.path.getsize(chunk) <= size  # torn tail truncated away
    w2.write_sync(EndHeightMessage(3), time_ns=99)
    msgs = [tm.msg for tm, _ in w2.iter_messages()]
    assert len(msgs) == expect_ok + 1 and isinstance(msgs[-1], EndHeightMessage)
    w2.close()


@pytest.mark.parametrize("action", ["torn", "partial"])
def test_wal_torn_write_tears_the_nth_frame_of_a_drain(tmp_path, action):
    """ISSUE 41: a drain's frames are handed to the file in one write, but
    ``wal.write:torn@12`` still tears the twelfth FRAME: while a rule is
    armed the drain goes frame by frame, the eleven before it already with
    the file; with nothing armed it is one write."""
    from tendermint_tpu.consensus.wal import WAL, WALMessageBlob, _valid_frames

    d = str(tmp_path / action)
    blobs = [("vote", b"vote-%02d" % i * 9, "peer%d" % (i % 3))
             for i in range(20)]
    faults.REGISTRY.crash_fn = _raise_sim
    faults.configure([f"wal.write:{action}@12"], seed=7)
    w = WAL(d)
    with pytest.raises(SimulatedCrash):
        w.write_blobs(blobs, time_ns=5)
    w._head.close()  # process death
    assert faults.snapshot()[0]["wal.write"] == 12
    chunk = os.path.join(d, "wal.000000")
    with open(chunk, "rb") as f:
        data = f.read()
    ends = [end for _pos, end, _t, _m in _valid_frames(data)]
    assert len(ends) == 11
    torn = len(data) - ends[-1]      # a cut twelfth frame, nothing after it
    frame = ends[-1] - ends[-2]      # (the frames differ only in a digit)
    assert (8 <= torn < frame) if action == "torn" else (1 <= torn < 8)

    faults.configure(["wal.write:torn@1000"])   # armed, not reached
    w2 = WAL(d)                      # the repair keeps the eleven
    assert os.path.getsize(chunk) == ends[-1]
    assert w2.write_blobs(blobs[11:], time_ns=6) == 9    # a frame a write
    faults.clear()
    assert w2.write_blobs(blobs, time_ns=7) == 1         # one write a drain
    w2.close()
    got = [tm.msg for tm, _ in WAL(d).iter_messages()]
    assert got == [WALMessageBlob(*b) for b in blobs + blobs]


def test_wal_torn_cut_point_replays_from_seed(tmp_path):
    faults.REGISTRY.crash_fn = _raise_sim
    cuts = []
    for run in ("a", "b"):
        d = str(tmp_path / run)
        _write_until_crash(d, "wal.write:torn@3", seed=123)
        cuts.append(os.path.getsize(os.path.join(d, "wal.000000")))
    assert cuts[0] == cuts[1]


# ---------------------------------------------------------------------------
# Device-failure smoke (the tier-1 injected device fault + circuit breaker)
# ---------------------------------------------------------------------------


def _ed_items(n_valid=4, n_bad=1):
    from tendermint_tpu.crypto import ed25519 as ref

    priv = ref.gen_priv_key(b"\x11" * 32)
    pub = priv.pub_key().data
    items = [(pub, b"m%d" % i, ref.sign(priv.data, b"m%d" % i))
             for i in range(n_valid)]
    items += [(pub, b"bad%d" % i, b"\x00" * 64) for i in range(n_bad)]
    return items, [True] * n_valid + [False] * n_bad


def test_device_failure_falls_back_and_recloses(monkeypatch, caplog):
    """Injected device-dispatch failure: the batch is re-verified on the
    host within the same dispatch, the circuit opens -- loudly: one
    error-level log line carrying the exception and its traceback -- and
    after the cooldown the background probe re-closes it; the next batch
    takes the device route again (stubbed here -- the real-kernel twin of
    this test is slow-tier, tests/test_fault_matrix.py)."""
    import logging

    from tendermint_tpu.ops import ed25519_batch as edb

    caplog.set_level(logging.ERROR, logger="tendermint_tpu.ops.breaker")

    monkeypatch.setenv("TM_TPU_HOST_CROSSOVER", "0")  # force the device route
    monkeypatch.setenv("TM_TPU_BREAKER_COOLDOWN_S", "0.05")
    items, expect = _ed_items()
    edb.BREAKER.reset()
    # a stub from the start: a degraded batch that lands after the cooldown
    # must not launch the real probe (an XLA compile) under this test
    device_back = []
    monkeypatch.setattr(edb.BREAKER, "probe", lambda: bool(device_back))
    faults.configure(["ops.ed25519.device:raise@1"], seed=3)

    # same-dispatch fallback: correct bitmap despite the device failure
    assert edb.verify_batch(items).tolist() == expect
    assert edb.BREAKER.is_open and edb.BREAKER.trips >= 1
    assert isinstance(edb.BREAKER.last_error, faults.FaultInjected)

    # while open: host fallback keeps verifying (the consensus guarantee)
    assert edb.verify_batch(items).tolist() == expect
    # the trip was logged once (not once per degraded batch), with the
    # exception a reader needs to tell a dead link from a compiler refusal
    trips = [r for r in caplog.records
             if r.name == "tendermint_tpu.ops.breaker"]
    assert len(trips) == 1 and trips[0].levelno == logging.ERROR
    assert "ed25519-device" in trips[0].getMessage()
    assert "FaultInjected" in trips[0].getMessage()
    assert trips[0].exc_info and trips[0].exc_info[1] is edb.BREAKER.last_error

    # after cooldown the background probe re-closes the circuit
    device_back.append(True)
    time.sleep(0.1)
    edb.verify_batch(items)  # allow() kicks the probe
    deadline = time.monotonic() + 10
    while edb.BREAKER.is_open and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not edb.BREAKER.is_open

    # closed again: the device route runs (stub proves the route, no XLA)
    calls = []

    def stub(items_, n, multichip):
        calls.append(n)
        return None, lambda _: np.asarray(expect)

    monkeypatch.setattr(edb, "_dispatch_device", stub)
    assert edb.verify_batch(items).tolist() == expect
    assert calls == [len(items)]
    assert not edb.BREAKER.is_open


def test_sr25519_device_failure_falls_back(monkeypatch):
    from tendermint_tpu.crypto import sr25519 as srref
    from tendermint_tpu.ops import sr25519_batch as srb

    monkeypatch.setenv("TM_TPU_HOST_CROSSOVER", "0")
    priv = srref.gen_priv_key(b"\x22" * 32)
    pub = priv.pub_key().data
    items = [(pub, b"sr0", srref.sign(priv.data, b"sr0")),
             (pub, b"bad", b"\x00" * 64)]
    srb.BREAKER.reset()
    faults.configure(["ops.sr25519.device:raise@1"], seed=5)
    assert list(srb.verify_batch(items)) == [True, False]
    assert srb.BREAKER.is_open
    srb.BREAKER.reset()


def test_failed_warmup_is_recorded_and_logged(monkeypatch, caplog):
    """warmup() never kills a node, but its outcome is readable
    (crypto_batch.WARMUP) and a failure is logged at error level -- both
    when a step raises and when verify_batch only degraded to the host."""
    import logging

    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.ops import ed25519_batch as edb

    monkeypatch.delenv("TM_TPU_SKIP_WARMUP", raising=False)
    caplog.set_level(logging.ERROR, logger="tendermint_tpu.crypto.batch")

    def boom():
        raise RuntimeError("no calibration today")

    monkeypatch.setattr(cbatch, "WARMUP", cbatch.WarmupStatus())
    monkeypatch.setattr(edb, "calibrate_host_crossover", boom)
    assert cbatch.warmup(background=False) is None
    assert cbatch.WARMUP.state == "failed"
    assert "no calibration today" in str(cbatch.WARMUP.error)
    assert cbatch.warmup(background=False) is None  # once per process
    logged = [r for r in caplog.records if r.levelno == logging.ERROR]
    assert len(logged) == 1 and "no calibration today" in logged[0].getMessage()
    assert logged[0].exc_info[1] is cbatch.WARMUP.error

    # the device route fails, the breaker answers from the host: verify_batch
    # returns normally, and the warm-up must still not report success
    caplog.clear()
    monkeypatch.setattr(cbatch, "WARMUP", cbatch.WarmupStatus())
    monkeypatch.setattr(edb, "calibrate_host_crossover", lambda: 0)
    edb.BREAKER.reset()
    faults.configure(["ops.ed25519.device:raise"], seed=1)
    t = cbatch.warmup()
    assert cbatch.WARMUP.join(30) and not t.is_alive()
    assert cbatch.WARMUP.state == "failed"
    assert isinstance(cbatch.WARMUP.error.__cause__, faults.FaultInjected)
    assert any("fell back to the host" in r.getMessage()
               for r in caplog.records
               if r.name == "tendermint_tpu.crypto.batch")


# ---------------------------------------------------------------------------
# Persistent-peer reconnect backoff
# ---------------------------------------------------------------------------


def test_reconnect_backoff_huge_attempt_does_not_overflow():
    """2.0**1024 overflows a float; a peer down for hours must not kill
    the reconnect thread via OverflowError."""
    from tendermint_tpu.p2p import switch as sw

    for k in (1023, 1024, 10_000_000):
        d = sw.reconnect_backoff_s(k)
        assert sw.RECONNECT_MAX_S <= d <= sw.RECONNECT_MAX_S * (
            1.0 + sw.RECONNECT_JITTER)


def test_reconnect_backoff_schedule():
    import random

    from tendermint_tpu.p2p import switch as sw

    rng = random.Random(7)
    prev_base = 0.0
    for k in range(8):
        base = min(sw.RECONNECT_BASE_S * 2.0 ** k, sw.RECONNECT_MAX_S)
        for _ in range(20):
            d = sw.reconnect_backoff_s(k, rng)
            assert base <= d <= base * (1.0 + sw.RECONNECT_JITTER) + 1e-9
        assert base >= prev_base  # monotone until the cap
        prev_base = base
    assert min(sw.RECONNECT_BASE_S * 2.0 ** 10, sw.RECONNECT_MAX_S) \
        == sw.RECONNECT_MAX_S
