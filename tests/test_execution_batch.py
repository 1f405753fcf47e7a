"""ISSUE 17: the batched execution plane (docs/EXECUTION.md).

Batched-vs-serial DeliverTx equivalence (order alignment, results_hash,
app hashes over a full chain), the DeliverTxBatch wire/transport seam
with its structural-probe fallback, the serial-equivalence contract
(fault injection degrades pre-dispatch; real batch errors propagate),
the commit->apply overlap handle with its stale-input discard, the
post-commit worker's FIFO ordering and crash shield, and the plane's
spans/metrics.
"""

from __future__ import annotations

import pytest

from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.state.execution import (
    BlockExecutor,
    PostCommitWorker,
    deliver_block_txs,
)
from tendermint_tpu.state.state import make_genesis_state
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.store.db import MemDB
from tendermint_tpu.types.block import Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, PRECOMMIT_TYPE, Vote
from tendermint_tpu.utils import faults


class LedgerApp(abci.Application):
    """Appends every delivered tx to a ledger; rejects b'bad*'. The batch
    override rides the base-class serial shim, so `delivered` is the
    per-tx observation sequence either way — any double-apply or
    reordering shows up as a ledger mismatch."""

    def __init__(self):
        self.delivered: list[bytes] = []
        self.batch_calls = 0

    def deliver_tx(self, req: abci.RequestDeliverTx) -> abci.ResponseDeliverTx:
        self.delivered.append(bytes(req.tx))
        if req.tx.startswith(b"bad"):
            return abci.ResponseDeliverTx(code=1, log="rejected")
        return abci.ResponseDeliverTx(code=0, data=bytes(req.tx[::-1]))

    def deliver_tx_batch(self, req: abci.RequestDeliverTxBatch) -> abci.ResponseDeliverTxBatch:
        self.batch_calls += 1
        return super().deliver_tx_batch(req)


class SerialOnlyApp:
    """Duck-typed app WITHOUT deliver_tx_batch (pre-batch stubs)."""

    def __init__(self):
        self.delivered: list[bytes] = []

    def deliver_tx(self, req):
        self.delivered.append(bytes(req.tx))
        return abci.ResponseDeliverTx(code=0, data=bytes(req.tx))


MIX = [b"a-ok", b"bad-1", b"", b"c-ok", b"bad-2", b"d" * 40]


# ---------------------------------------------------------------------------
# deliver_block_txs == the serial loop
# ---------------------------------------------------------------------------


def test_deliver_block_txs_matches_serial():
    batched_app, serial_app = LedgerApp(), LedgerApp()
    batched = deliver_block_txs(batched_app, MIX)
    serial = [serial_app.deliver_tx(abci.RequestDeliverTx(tx=t)) for t in MIX]
    assert batched == serial  # order-aligned, field-identical
    assert batched_app.delivered == serial_app.delivered == MIX
    assert batched_app.batch_calls == 1
    # the deterministic subset feeding LastResultsHash is bit-identical
    assert abci.results_hash(batched) == abci.results_hash(serial)


def test_deliver_block_txs_chunks_at_max_batch(monkeypatch):
    monkeypatch.setenv("TMTPU_DELIVER_MAX_BATCH", "2")
    app = LedgerApp()
    out = deliver_block_txs(app, MIX)
    assert app.batch_calls == 3  # 6 txs / cap 2
    assert [r.code for r in out] == [0, 1, 0, 0, 1, 0]
    assert app.delivered == MIX


def test_deliver_disabled_env_restores_serial(monkeypatch):
    monkeypatch.setenv("TMTPU_DELIVER", "0")
    app = LedgerApp()
    out = deliver_block_txs(app, MIX)
    assert app.batch_calls == 0
    assert [r.code for r in out] == [0, 1, 0, 0, 1, 0]


def test_deliver_block_txs_serial_for_batchless_app():
    app = SerialOnlyApp()
    out = deliver_block_txs(app, [b"x", b"y"])
    assert app.delivered == [b"x", b"y"]
    assert [r.data for r in out] == [b"x", b"y"]


def test_deliver_block_txs_empty_is_empty():
    app = LedgerApp()
    assert deliver_block_txs(app, []) == []
    assert app.batch_calls == 0  # no dispatch, no probe


# ---------------------------------------------------------------------------
# the serial-equivalence contract (docs/EXECUTION.md)
# ---------------------------------------------------------------------------


def test_fault_injection_degrades_chunk_to_serial(monkeypatch):
    """`abci.deliver_batch` fires BEFORE dispatch: the hit chunk runs the
    serial loop — each tx applied exactly once, responses unchanged."""
    monkeypatch.setenv("TMTPU_DELIVER_MAX_BATCH", "2")
    faults.configure(["abci.deliver_batch:raise@2"], seed=7)
    try:
        app = LedgerApp()
        out = deliver_block_txs(app, MIX)
    finally:
        faults.clear()
    assert app.delivered == MIX  # exactly once each, in order
    assert app.batch_calls == 2  # chunk 2 of 3 went serial
    ref = [LedgerApp().deliver_tx(abci.RequestDeliverTx(tx=t)) for t in MIX]
    assert out == ref


def test_fault_injection_every_chunk_still_serial_equivalent():
    faults.configure(["abci.deliver_batch:raise"], seed=7)
    try:
        app = LedgerApp()
        out = deliver_block_txs(app, MIX)
    finally:
        faults.clear()
    assert app.batch_calls == 0
    assert [r.code for r in out] == [0, 1, 0, 0, 1, 0]


def test_app_exception_mid_batch_propagates_not_redone():
    """A genuine app error during a real batch must PROPAGATE with the
    prefix applied — the serial loop's failure shape — never be silently
    redone serially (that would double-apply the prefix)."""

    class BlowsUpAt3(LedgerApp):
        def deliver_tx(self, req):
            if len(self.delivered) == 2:
                raise RuntimeError("app blew up")
            return super().deliver_tx(req)

    app = BlowsUpAt3()
    with pytest.raises(RuntimeError, match="app blew up"):
        deliver_block_txs(app, MIX)
    assert app.delivered == MIX[:2]  # prefix ran once; nothing redone


# ---------------------------------------------------------------------------
# ABCI transport seam: wire codec, socket probe, local client
# ---------------------------------------------------------------------------


def test_wire_codec_deliver_tx_batch_round_trip():
    from tendermint_tpu.abci import wire

    req = abci.RequestDeliverTxBatch(txs=[b"a", b"", b"ccc"])
    kind, back = wire.decode_request(wire.encode_request("deliver_tx_batch", req))
    assert kind == "deliver_tx_batch" and back == req
    # the empty support probe must survive the round trip too
    kind, back = wire.decode_request(
        wire.encode_request("deliver_tx_batch", abci.RequestDeliverTxBatch()))
    assert kind == "deliver_tx_batch" and back == abci.RequestDeliverTxBatch()
    resp = abci.ResponseDeliverTxBatch(responses=[
        abci.ResponseDeliverTx(code=0, data=b"d", gas_used=3),
        abci.ResponseDeliverTx(code=9, log="no", codespace="app"),
    ])
    kind, back = wire.decode_response(wire.encode_response("deliver_tx_batch", resp))
    assert kind == "deliver_tx_batch" and back == resp
    kind, back = wire.decode_response(
        wire.encode_response("deliver_tx_batch", abci.ResponseDeliverTxBatch()))
    assert back == abci.ResponseDeliverTxBatch()


def test_socket_transport_deliver_batch_and_fallback():
    from tendermint_tpu.abci.client import ABCISocketClient
    from tendermint_tpu.abci.server import ABCIServer

    app = LedgerApp()
    server = ABCIServer(app, "tcp://127.0.0.1:0")
    server.start()
    try:
        cli = ABCISocketClient(server.addr)
        assert cli._batch_delivertx is None  # unprobed
        out = cli.deliver_tx_batch(abci.RequestDeliverTxBatch(
            txs=[b"ok-1", b"bad-x", b"ok-2"]))
        assert cli._batch_delivertx is True
        assert app.batch_calls == 2  # empty probe + the real batch
        assert [r.code for r in out.responses] == [0, 1, 0]
        assert app.delivered == [b"ok-1", b"bad-x", b"ok-2"]
        # pre-batch-server degradation: serial per-tx loop, same responses
        cli._batch_delivertx = False
        out2 = cli.deliver_tx_batch(abci.RequestDeliverTxBatch(
            txs=[b"ok-3", b"bad-y"]))
        assert [r.code for r in out2.responses] == [0, 1]
        assert app.batch_calls == 2  # untouched
        cli.close()
    finally:
        server.stop()


def test_socket_app_exception_does_not_disable_deliver_batching():
    """An app blow-up during a REAL batch is an exception response: it
    must propagate (the prefix executed — exactly the serial failure
    shape) WITHOUT pinning the client to the serial loop, and without
    any serial redo of the failed chunk."""
    from tendermint_tpu.abci.client import ABCISocketClient
    from tendermint_tpu.abci.server import ABCIServer
    from tendermint_tpu.abci.wire import ABCIRemoteError

    class FlakyApp(LedgerApp):
        def __init__(self):
            super().__init__()
            self.fail_once = True

        def deliver_tx_batch(self, req):
            # req.txs guard: the client's empty support probe must not
            # count as the transient failure under test
            if req.txs and self.fail_once:
                self.fail_once = False
                self.delivered.append(bytes(req.txs[0]))  # prefix ran
                raise RuntimeError("transient app failure")
            return super().deliver_tx_batch(req)

    app = FlakyApp()
    server = ABCIServer(app, "tcp://127.0.0.1:0")
    server.start()
    try:
        cli = ABCISocketClient(server.addr)
        with pytest.raises(ABCIRemoteError, match="transient"):
            cli.deliver_tx_batch(abci.RequestDeliverTxBatch(txs=[b"ok-1"]))
        assert cli._batch_delivertx  # one blip must not cost batching
        assert app.delivered == [b"ok-1"]  # prefix applied ONCE, no redo
        out = cli.deliver_tx_batch(abci.RequestDeliverTxBatch(txs=[b"ok-2"]))
        assert [r.code for r in out.responses] == [0]
        cli.close()
    finally:
        server.stop()


def test_local_client_exposes_deliver_tx_batch():
    from tendermint_tpu.abci.proxy import local_app_conns

    conns = local_app_conns(LedgerApp())
    out = conns.consensus.deliver_tx_batch(abci.RequestDeliverTxBatch(
        txs=[b"ok-l", b"bad-l"]))
    assert [r.code for r in out.responses] == [0, 1]


# ---------------------------------------------------------------------------
# full-chain equivalence through BlockExecutor + the overlap handle
# ---------------------------------------------------------------------------


def _genesis(n_vals=2, chain_id="exec-batch-chain"):
    privs = [ed25519.gen_priv_key(bytes([60 + i]) * 32) for i in range(n_vals)]
    gvals = [GenesisValidator(b"", p.pub_key(), 10) for p in privs]
    gd = GenesisDoc(chain_id=chain_id, validators=gvals,
                    genesis_time=Time(1700000000, 0))
    gd.validate_and_complete()
    return gd, privs


def _commit_for(state, block, privs):
    bid = BlockID(hash=block.hash(),
                  part_set_header=PartSet.from_data(block.marshal()).header())
    sigs = []
    by_addr = {p.pub_key().address(): p for p in privs}
    for val in state.validators.validators:
        priv = by_addr[val.address]
        v = Vote(type=PRECOMMIT_TYPE, height=block.header.height, round=0,
                 block_id=bid, timestamp=block.header.time.add_ns(1_000_000),
                 validator_address=val.address,
                 validator_index=state.validators.get_by_address(val.address)[0])
        v.signature = priv.sign(v.sign_bytes(state.chain_id))
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address, v.timestamp,
                              v.signature))
    return bid, Commit(height=block.header.height, round=0, block_id=bid,
                       signatures=sigs)


def _run_chain(privs, gd, n_blocks=3, speculate=False):
    """Drive n blocks through BlockExecutor + kvstore; returns the
    per-height (app_hash, last_results_hash) trail."""
    state = make_genesis_state(gd)
    app = KVStoreApplication()
    store = StateStore(MemDB())
    store.save(state)
    bx = BlockExecutor(store, app)
    trail = []
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    for h in range(1, n_blocks + 1):
        txs = [b"k%d-%d=v%d" % (h, i, i) for i in range(4 * h)]
        proposer = state.validators.get_proposer()
        block = state.make_block(h, txs, last_commit, [], proposer.address)
        bid, commit = _commit_for(state, block, privs)
        cp = bx.dispatch_commit_verify(state, block) if speculate else None
        state, _ = bx.apply_block(state, bid, block, commit_pending=cp)
        trail.append((state.app_hash, state.last_results_hash))
        last_commit = commit
    return trail


def test_chain_batched_equals_serial_app_hashes(monkeypatch):
    gd, privs = _genesis()
    batched = _run_chain(privs, gd)
    monkeypatch.setenv("TMTPU_DELIVER", "0")
    serial = _run_chain(privs, gd)
    assert batched == serial  # app_hash AND LastResultsHash per height


def test_chain_with_overlap_handle_equals_plain(monkeypatch):
    """dispatch_commit_verify threaded through apply_block resolves to
    the same accept decisions and hashes as the synchronous verify."""
    gd, privs = _genesis()
    plain = _run_chain(privs, gd)
    overlapped = _run_chain(privs, gd, speculate=True)
    assert overlapped == plain


def test_chain_batched_equals_serial_under_fault_injection(monkeypatch):
    gd, privs = _genesis()
    serial_ref = _run_chain(privs, gd)
    faults.configure(["abci.deliver_batch:raise%0.5"], seed=11)
    try:
        injected = _run_chain(privs, gd)
    finally:
        faults.clear()
    assert injected == serial_ref


def test_stale_overlap_handle_is_discarded():
    """A handle whose dispatch-time inputs drifted must NOT be consumed:
    fresh_for returns None and the apply falls back to the sync verify."""
    gd, privs = _genesis()
    state = make_genesis_state(gd)
    app = KVStoreApplication()
    store = StateStore(MemDB())
    store.save(state)
    bx = BlockExecutor(store, app)
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    block1 = state.make_block(1, [b"a=1"], last_commit,
                              [], state.validators.get_proposer().address)
    bid1, commit1 = _commit_for(state, block1, privs)
    assert bx.dispatch_commit_verify(state, block1) is None  # initial height
    state, _ = bx.apply_block(state, bid1, block1)

    block2 = state.make_block(2, [b"b=2"], commit1,
                              [], state.validators.get_proposer().address)
    cp = bx.dispatch_commit_verify(state, block2)
    assert cp is not None
    assert cp.fresh_for(state, block2) is cp.pending
    # height drift and valset drift both kill the handle
    assert cp.fresh_for(state, block1) is None
    stale = type(cp)(pending=cp.pending, height=cp.height,
                     last_block_id=cp.last_block_id, vals_hash=b"\x00" * 32)
    assert stale.fresh_for(state, block2) is None
    # the apply still succeeds with a stale handle (sync fallback)
    bid2, _ = _commit_for(state, block2, privs)
    state, _ = bx.apply_block(state, bid2, block2, commit_pending=stale)
    assert state.last_block_height == 2


# ---------------------------------------------------------------------------
# the post-commit worker
# ---------------------------------------------------------------------------


class _RecordingBus:
    """Event-bus duck type recording publish order across heights."""

    def __init__(self):
        self.events: list[tuple[str, int]] = []

    def publish_event_new_block(self, ev):
        self.events.append(("block", ev.block.header.height))

    def publish_event_new_block_header(self, ev):
        self.events.append(("header", ev.header.height))

    def publish_event_new_evidence(self, ev):
        self.events.append(("evidence", ev.height))

    def publish_event_tx(self, ev):
        self.events.append(("tx", ev.height))

    def publish_event_validator_set_updates(self, ev):
        self.events.append(("valset", -1))


def test_post_commit_events_fifo_across_heights():
    """apply_block returns once state is saved; events still publish in
    height order (h fully before h+1) and flush_post_commit drains."""
    gd, privs = _genesis()
    state = make_genesis_state(gd)
    app = KVStoreApplication()
    store = StateStore(MemDB())
    store.save(state)
    bus = _RecordingBus()
    bx = BlockExecutor(store, app, event_bus=bus)
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    for h in (1, 2, 3):
        block = state.make_block(h, [b"k%d=v" % h, b"j%d=w" % h], last_commit,
                                 [], state.validators.get_proposer().address)
        bid, last_commit = _commit_for(state, block, privs)
        state, _ = bx.apply_block(state, bid, block)
    assert bx.flush_post_commit(timeout_s=10.0)
    heights = [h for _, h in bus.events if h > 0]
    assert heights == sorted(heights)  # h's events strictly before h+1's
    per_height = [h for kind, h in bus.events if kind == "tx"]
    assert per_height == [1, 1, 2, 2, 3, 3]
    bx.stop()


def test_post_commit_worker_crash_shield_and_restart():
    ran = []
    w = PostCommitWorker()
    w.submit(lambda: 1 / 0)  # must not kill the worker
    w.submit(lambda: ran.append("a"))
    assert w.flush(timeout_s=5.0)
    assert ran == ["a"]
    w.stop()
    w.submit(lambda: ran.append("b"))  # restarts after stop
    assert w.flush(timeout_s=5.0)
    assert ran == ["a", "b"]
    w.stop()


def test_flush_without_any_submit_is_immediate():
    assert PostCommitWorker().flush(timeout_s=0.1)


# ---------------------------------------------------------------------------
# headless replay + handshake replay ride the same engine
# ---------------------------------------------------------------------------


def test_replay_ctx_batched_equals_serial_app_hash(monkeypatch):
    from tendermint_tpu.blockchain.pipeline import VerifyAheadPipeline
    from tendermint_tpu.blockchain.replay import ReplayCtx, make_chain
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    privs = [ed25519.gen_priv_key(bytes([70 + i]) * 32) for i in range(2)]
    vals = ValidatorSet(validators=[Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    privs = [by_addr[v.address] for v in vals.validators]  # signer order
    chain = make_chain("replay-chain", 5, vals, privs,
                       txs_for=lambda h: [b"r%d-%d=v" % (h, i) for i in range(3)])

    def run():
        ctx = ReplayCtx(vals, "replay-chain", app=KVStoreApplication())
        for b in chain:
            ctx.pool.add_block("good", b)
        pipe = VerifyAheadPipeline()
        while pipe.process_next(ctx):
            pass
        return ctx.applied, ctx.app_hash

    batched_applied, batched = run()
    assert batched_applied == [1, 2, 3, 4]  # n-1: last block has no child
    monkeypatch.setenv("TMTPU_DELIVER", "0")
    serial_applied, serial = run()
    assert serial_applied == batched_applied
    assert batched == serial


# ---------------------------------------------------------------------------
# satellites: txs_hash chash route, spans, metrics
# ---------------------------------------------------------------------------


def test_txs_hash_chash_route_matches_reference():
    from tendermint_tpu.crypto import merkle, tmhash
    from tendermint_tpu.types.tx import txs_hash

    txs = [b"tx-%d" % i * (i + 1) for i in range(9)] + [b""]
    ref = merkle.hash_from_byte_slices([tmhash.sum(t) for t in txs])
    assert txs_hash(txs) == ref  # chash route (when up) is bit-identical
    assert txs_hash(txs[:1]) == merkle.hash_from_byte_slices(
        [tmhash.sum(txs[0])])


def test_deliver_spans_are_canonical_and_recorded():
    from tendermint_tpu.utils import trace as tmtrace

    for name in ("abci.deliver_txs", "abci.deliver_batch", "apply.post_commit"):
        assert name in tmtrace.CANONICAL_SPANS
        assert name in tmtrace.MIRRORED_SPANS
    tracer = tmtrace.Tracer(name="deliver-test", enabled=True)
    try:
        with tracer.activate():
            deliver_block_txs(LedgerApp(), MIX)
    finally:
        tracer.disable()
    names = {s.name for s in tracer.dump()}
    assert {"abci.deliver_txs", "abci.deliver_batch"} <= names


def test_deliver_metrics_preseeded_and_counted():
    from tendermint_tpu.utils import metrics as tmmetrics

    nm = tmmetrics.NodeMetrics()
    text = nm.registry.expose()
    assert "tendermint_abci_deliver_batch_size_count 0" in text
    assert "tendermint_abci_deliver_tx_invalid_total 0.0" in text
    prev = tmmetrics.GLOBAL_NODE_METRICS
    tmmetrics.GLOBAL_NODE_METRICS = nm
    try:
        gd, privs = _genesis()
        state = make_genesis_state(gd)
        app = KVStoreApplication()
        store = StateStore(MemDB())
        store.save(state)
        bx = BlockExecutor(store, app)
        last_commit = Commit(height=0, round=0, block_id=BlockID(),
                             signatures=[])
        # two malformed validator txs: rejected by the app (code=1), so the
        # once-dead invalid accumulator now lands on the counter
        block = state.make_block(
            1, [b"ok=1", b"val:not-base64!x", b"val:also-bad"], last_commit,
            [], state.validators.get_proposer().address)
        bid, _ = _commit_for(state, block, privs)
        bx.apply_block(state, bid, block)
        nm2 = nm.registry.expose()
    finally:
        tmmetrics.GLOBAL_NODE_METRICS = prev
    assert "tendermint_abci_deliver_tx_invalid_total 2.0" in nm2
    assert "tendermint_abci_deliver_batch_size_count 0" not in nm2


# ---------------------------------------------------------------------------
# the backlog behind apply_block: counters, wait_indexed, the bound (PR 37)
# ---------------------------------------------------------------------------


def _indexed_executor():
    """A BlockExecutor over the kvstore whose events an IndexerService over
    MemDB indexes, wired as node/node.py wires them."""
    from tendermint_tpu.state.txindex import (BlockIndexer, IndexerService,
                                              TxIndexer)
    from tendermint_tpu.types.events import EventBus

    gd, privs = _genesis()
    state = make_genesis_state(gd)
    store = StateStore(MemDB())
    store.save(state)
    bus = EventBus()
    bx = BlockExecutor(store, KVStoreApplication(), event_bus=bus)
    idx_db = MemDB()
    txi = TxIndexer(idx_db)
    svc = IndexerService(txi, BlockIndexer(idx_db), bus)
    bx.follow_backlog(svc.backlog_heights)
    svc.on_indexed = bx.backlog_changed
    return bx, svc, state, privs


def _apply(bx, state, privs, heights, txs_per_block, last_commit=None,
           before_each=None):
    last_commit = last_commit or Commit(height=0, round=0, block_id=BlockID(),
                                        signatures=[])
    for h in heights:
        txs = [b"h%d-k%d=v" % (h, i) for i in range(txs_per_block)]
        block = state.make_block(h, txs, last_commit, [],
                                 state.validators.get_proposer().address)
        bid, last_commit = _commit_for(state, block, privs)
        if before_each is not None:
            before_each()
        state, _ = bx.apply_block(state, bid, block)
    return state, last_commit


def test_the_event_bus_says_how_many_subscriptions_it_queued_to():
    from tendermint_tpu.types import events

    bus = events.EventBus()
    assert bus.publish(events.EVENT_TX, object()) == 0
    bus.subscribe("a", "tm.event=Tx")
    bus.subscribe("b", "tm.event=Tx AND tx.height=3")
    bus.subscribe("c", "tm.event=NewBlock")
    data = events.EventDataTx(height=3, tx=b"k=v", index=0)
    assert bus.publish_event_tx(data) == 2
    assert bus.publish_event_tx(events.EventDataTx(height=4, tx=b"k=v")) == 1
    assert bus.publish_event_new_block(events.EventDataNewBlock()) == 1
    assert bus.publish_event_new_block_header(
        events.EventDataNewBlockHeader()) == 0


def test_the_post_commit_worker_counts_and_the_spans_say_what_was_published():
    from tendermint_tpu.utils import trace

    bx, svc, state, privs = _indexed_executor()
    svc.start()
    worker = bx.post_commit
    assert (worker.submitted, worker.done, worker.backlog_max) == (0, 0, 0)
    trace.dump(clear=True)
    trace.enable()
    try:
        _apply(bx, state, privs, (1, 2, 3), 5)
        assert bx.flush_post_commit(timeout_s=10.0)
        assert svc.wait_indexed(3, timeout_s=10.0)
    finally:
        trace.disable()
    spans = trace.dump(clear=True)
    assert (worker.submitted, worker.done) == (3, 3)
    assert 1 <= worker.backlog_max <= 2 and worker.backlog() == 0
    posted = [s for s in spans if s.name == "apply.post_commit"]
    assert [(s.tags["height"], s.tags["txs"], s.tags["events"])
            for s in posted] == [(1, 5, 7), (2, 5, 7), (3, 5, 7)]
    assert {s.thread for s in posted} == {"post-commit"}
    # the indexer's two subscriptions take the header and the five
    # transactions; nobody takes NewBlock
    published = [s for s in spans if s.name == "events.publish_block"]
    assert [s.tags["events"] for s in published] == [6, 6, 6]
    assert [s.parent_id for s in published] == [s.span_id for s in posted]
    indexed = [s for s in spans if s.name == "indexer.height"]
    assert [(s.tags["height"], s.tags["txs"]) for s in indexed] == [
        (1, 5), (2, 5), (3, 5)]
    for s in indexed:
        # a document and two postings a transaction (tx.height, app.creator)
        assert s.tags["rows"] == 15 and s.tags["bytes"] > 0
        assert s.thread == "indexer" and s.cpu_s <= s.duration_s + 1e-3
    delivered = [s for s in spans if s.name == "abci.deliver_txs"]
    assert [s.tags["n"] for s in delivered] == [5, 5, 5]
    svc.stop()
    bx.stop()


def test_a_failing_post_commit_task_still_counts_as_done():
    w = PostCommitWorker()
    w.submit(lambda: 1 / 0)
    w.submit(lambda: None)
    assert w.flush(timeout_s=5.0)
    assert (w.submitted, w.done, w.backlog()) == (2, 2, 0)
    w.stop()


def test_wait_indexed_and_the_indexers_counters():
    bx, svc, state, privs = _indexed_executor()
    # not started: nothing will ever be indexed, and nobody is kept waiting
    assert svc.wait_indexed(1, timeout_s=5.0) is False
    assert svc.backlog_heights() == 0
    svc.start()
    state, last_commit = _apply(bx, state, privs, (1, 2), 4)
    assert svc.wait_indexed(2, timeout_s=10.0) is True
    assert svc.wait_indexed(1, timeout_s=0.0) is True
    assert svc.wait_indexed(3, timeout_s=0.05) is False
    assert (svc.heights_indexed, svc.txs_indexed, svc.last_indexed_height) \
        == (2, 8, 2)
    assert 1 <= svc.backlog_max and svc.backlog_heights_max >= 1
    assert len(svc.tx_indexer.search("tx.height=2")) == 4
    assert svc.backlog_heights() == 0
    svc.stop()
    # stopped: a waiter is released at once
    assert svc.wait_indexed(9, timeout_s=5.0) is False
    bx.stop()


def test_apply_waits_at_the_bound_and_nothing_is_lost_when_the_indexer_is_slow():
    """The indexer is held at its first transaction. Two heights may pile up
    behind apply_block; the third apply waits (counted, and traced as
    apply.backlog_wait) until the indexer moves again, and afterwards every
    transaction of every height is in the index."""
    import threading

    from tendermint_tpu.state import execution
    from tendermint_tpu.utils import trace

    bx, svc, state, privs = _indexed_executor()
    gate = threading.Event()
    real = svc.tx_indexer.index

    def held(*a):
        assert gate.wait(30.0)
        real(*a)

    svc.tx_indexer.index = held
    svc.start()
    trace.dump(clear=True)
    trace.enable()
    try:
        state, last_commit = _apply(bx, state, privs, (1, 2), 6)
        assert bx.flush_post_commit(timeout_s=10.0)
        # height 1 in the indexer's hand, height 2's header behind it
        assert bx.backlog_heights() == execution.MAX_BACKLOG_HEIGHTS == 2
        assert bx.backlog_waits == 0
        done = []
        t = threading.Thread(target=lambda: done.append(_apply(
            bx, state, privs, (3, 4, 5, 6), 6, last_commit)))
        t.start()
        t.join(0.3)
        assert t.is_alive() and not done and bx.backlog_waits == 1
        assert bx.store.load().last_block_height == 2     # height 3 not begun
        gate.set()
        t.join(30.0)
        assert not t.is_alive() and done[0][0].last_block_height == 6
        assert svc.wait_indexed(6, timeout_s=30.0)
    finally:
        gate.set()
        trace.disable()
    waits = [s for s in trace.dump(clear=True) if s.name == "apply.backlog_wait"]
    assert bx.backlog_waits == len(waits) >= 1
    assert waits[0].duration_s >= 0.25
    for s in waits:
        assert s.tags["bound"] == 2 and s.tags["backlog"] >= 2
    assert bx.post_commit.backlog_max <= 2 and svc.backlog_heights_max <= 2
    assert (svc.heights_indexed, svc.txs_indexed) == (6, 36)
    for h in range(1, 7):
        assert len(svc.tx_indexer.search(f"tx.height={h}")) == 6
    assert bx.backlog_heights() == 0
    svc.stop()
    bx.stop()


def test_a_follower_that_is_gone_does_not_hold_apply():
    """An indexer stopped with heights still in its queues reports no
    backlog (it will never catch up), and an executor nobody follows counts
    its own tasks alone."""
    import time as _t

    bx, svc, state, privs = _indexed_executor()
    real = svc.tx_indexer.index
    svc.tx_indexer.index = lambda *a: (_t.sleep(0.05), real(*a))
    svc.start()
    state, last_commit = _apply(bx, state, privs, (1,), 3)
    svc.stop()
    assert svc.backlog_heights() == 0
    state, last_commit = _apply(bx, state, privs, (2, 3, 4, 5), 3, last_commit)
    assert bx.flush_post_commit(timeout_s=10.0)
    assert state.last_block_height == 5 and bx.backlog_heights() == 0
    bx.stop()


def test_mempool_update_is_a_span_of_the_apply():
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.utils import trace

    gd, privs = _genesis()
    state = make_genesis_state(gd)
    store = StateStore(MemDB())
    store.save(state)
    app = KVStoreApplication()
    bx = BlockExecutor(store, app, mempool=Mempool(app))
    trace.dump(clear=True)
    trace.enable()
    try:
        _apply(bx, state, privs, (1, 2), 3)
    finally:
        trace.disable()
    spans = trace.dump(clear=True)
    updates = [s for s in spans if s.name == "mempool.update"]
    assert [(s.tags["height"], s.tags["txs"]) for s in updates] == [(1, 3), (2, 3)]
    saves = {s.span_id for s in spans if s.name == "apply.save"}
    assert {s.parent_id for s in updates} <= saves
    bx.stop()


def test_node_stop_lets_the_index_catch_up_and_close_stores_closes(tmp_path):
    """Node.stop flushes the post-commit queue and then waits for the index
    (IndexerService.wait_indexed) before it detaches the indexer: every
    applied height is in the index afterwards. close_stores then closes the
    sqlite connections, and the files answer to new ones."""
    from tendermint_tpu.config.config import Config
    from tendermint_tpu.node.node import Node, default_app
    from tendermint_tpu.state.txindex import TxIndexer
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.store.db import new_db
    from tendermint_tpu.utils.log import NopLogger

    gd, privs = _genesis(n_vals=2, chain_id="stop-chain")
    node = Node(Config().set_root(str(tmp_path)), default_app("kvstore"), gd,
                logger=NopLogger())
    node.indexer_service.start()
    state = node.state_store.load()
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    for h in (1, 2, 3):
        txs = [b"s%d-%d=v" % (h, i) for i in range(20)]
        block = state.make_block(h, txs, last_commit, [],
                                 state.validators.get_proposer().address)
        bid, last_commit = _commit_for(state, block, privs)
        node.block_store.save_block(
            block, PartSet.from_data(block.marshal()), last_commit)
        state, _ = node.block_exec.apply_block(state, bid, block)
    node.stop()
    assert node.indexer_service.last_indexed_height == 3
    assert node.indexer_service.txs_indexed == 60
    node.close_stores()
    with pytest.raises(Exception):
        node.block_store.load_block_meta(1)        # its connection is closed
    blocks = new_db("sqlite", str(tmp_path / "data" / "blockstore.db"))
    index = new_db("sqlite", str(tmp_path / "data" / "tx_index.db"))
    try:
        assert BlockStore(blocks).height == 3
        assert len(TxIndexer(index).search("tx.height=3")) == 20
    finally:
        blocks.close()
        index.close()
