"""Node: the DI root wiring stores, ABCI app, mempool, consensus, and p2p
(reference: node/node.go:100,706,941).
"""

from __future__ import annotations

import os

from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.config.config import Config
from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.consensus.state_machine import ConsensusState
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.mempool.mempool import Mempool
from tendermint_tpu.mempool.reactor import MempoolReactor
from tendermint_tpu.p2p.key import NodeKey
from tendermint_tpu.p2p.node_info import NodeInfo
from tendermint_tpu.p2p.switch import Switch, Transport
from tendermint_tpu.privval.file_pv import FilePV
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import make_genesis_state
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.store.block_store import BlockStore
from tendermint_tpu.store.db import new_db
from tendermint_tpu.types.events import EventBus
from tendermint_tpu.types.genesis import GenesisDoc


def default_app(name: str):
    """App selection (reference: proxy/client.go:75 DefaultClientCreator):
    a known in-proc app name, or a tcp://|unix:// address of an out-of-process
    ABCI socket server."""
    if name.startswith(("tcp://", "unix://", "grpc://")):
        return name  # resolved to socket/grpc clients by abci.proxy.new_app_conns
    if name in ("kvstore", "persistent_kvstore"):
        # snapshot support for state-sync serving (the reference e2e app
        # takes snapshot_interval from its manifest; env keeps the CLI thin)
        interval = int(os.environ.get("TMTPU_KVSTORE_SNAPSHOT_INTERVAL", "0"))
        return KVStoreApplication(snapshot_interval=interval)
    if name == "counter":
        from tendermint_tpu.abci.counter import CounterApp

        return CounterApp()
    if name == "counter_serial":
        from tendermint_tpu.abci.counter import CounterApp

        return CounterApp(serial=True)
    if name == "noop":
        from tendermint_tpu.abci.types import Application

        return Application()
    raise ValueError(f"unknown proxy app {name!r}")


class Node:
    """reference: node/node.go:706 NewNode."""

    def __init__(self, config: Config, app=None, genesis: GenesisDoc | None = None,
                 priv_validator=None, node_key: NodeKey | None = None,
                 logger=None):
        self.config = config
        if logger is None:
            # real structured logger by default (reference: libs/log); tests
            # pass NopLogger or capture stderr
            from tendermint_tpu.utils.log import new_logger

            logger = new_logger(level=config.base.log_level,
                                fmt=config.base.log_format)
        self.logger = logger

        # DBs (reference: node/node.go:716,235 initDBs)
        backend = config.base.db_backend
        dbdir = config.db_dir()
        self.block_store = BlockStore(new_db(backend, os.path.join(dbdir, "blockstore.db")
                                             if backend != "memdb" else None))
        self.state_store = StateStore(new_db(backend, os.path.join(dbdir, "state.db")
                                             if backend != "memdb" else None))

        # genesis + state. The very first state load is guarded: a corrupt
        # state row is quarantined and rebuilt from the block store when
        # possible; otherwise the empty state routes this node into the
        # normal state-sync / fast-sync bootstrap (store/repair.py,
        # docs/DURABILITY.md) instead of refusing to boot.
        from tendermint_tpu.store.repair import StoreRepairer, recover_state

        self.genesis = genesis if genesis is not None else GenesisDoc.from_file(config.genesis_file())
        state = recover_state(self.state_store, self.block_store, logger,
                              statesync_enabled=config.statesync.enable)
        if state.is_empty():
            state = make_genesis_state(self.genesis)
            self.state_store.save(state)

        # self-healing storage plane: one repairer owns quarantine + the
        # repair queue; every store's detection hook routes into it
        self.store_repairer = StoreRepairer(
            block_store=self.block_store, state_store=self.state_store,
            chain_id=self.genesis.chain_id, logger=logger)
        self.block_store.on_corruption = self.store_repairer.note
        self.state_store.on_corruption = self.store_repairer.note

        # app: in-proc object or socket address -> 4-connection proxy
        # (reference: node/node.go:731 createAndStartProxyAppConns)
        from tendermint_tpu.abci.proxy import new_app_conns

        self.app = app if app is not None else default_app(config.base.proxy_app)
        self.proxy_app = new_app_conns(self.app)

        # ABCI handshake/replay (reference: node/node.go:777 doHandshake)
        from tendermint_tpu.consensus.replay import Handshaker

        self.event_bus = EventBus()
        handshaker = Handshaker(self.state_store, self.block_store, self.genesis)
        state = handshaker.handshake(state, self.proxy_app.consensus)

        # priv validator: remote signer socket, or local file PV
        # (reference: node/node.go:753 createAndStartPrivValidatorSocketClient)
        if priv_validator is None and config.base.priv_validator_laddr:
            from tendermint_tpu.privval.signer import (
                RetrySignerClient,
                SignerClient,
                SignerListenerEndpoint,
            )

            self.signer_endpoint = SignerListenerEndpoint(
                config.base.priv_validator_laddr)
            priv_validator = RetrySignerClient(
                SignerClient(self.signer_endpoint, self.genesis.chain_id))
        elif priv_validator is None and config.base.priv_validator_key_file:
            priv_validator = FilePV.load_or_generate(
                config.priv_validator_key_file(), config.priv_validator_state_file()
            )
        self.priv_validator = priv_validator

        # mempool
        self.mempool = Mempool(
            self.proxy_app.mempool,
            version=config.mempool.version,
            max_txs=config.mempool.size,
            max_txs_bytes=config.mempool.max_txs_bytes,
            cache_size=config.mempool.cache_size,
            max_tx_bytes=config.mempool.max_tx_bytes,
            keep_invalid_txs_in_cache=config.mempool.keep_invalid_txs_in_cache,
            recheck=config.mempool.recheck,
            ttl_duration_s=config.mempool.ttl_duration_s,
            ttl_num_blocks=config.mempool.ttl_num_blocks,
        )
        # admission filters from the current state (reference:
        # node.go:383,404 WithPreCheck/WithPostCheck; refreshed per block
        # by BlockExecutor._commit)
        from tendermint_tpu.state.tx_filter import tx_post_check, tx_pre_check

        self.mempool.pre_check = tx_pre_check(state)
        self.mempool.post_check = tx_post_check(state)

        # per-node time source (utils/clock.py, docs/NEMESIS.md): every
        # consensus/evidence wall-clock read goes through this object, so a
        # fabric skew action (`node.clock.set_skew(...)`) desynchronizes ONE
        # node of an in-process mesh. Born with the process default's skew
        # so TMTPU_CLOCK_SKEW_S also skews a subprocess testnet node.
        from tendermint_tpu.utils import clock as tmclock

        self.clock = tmclock.Clock(skew_s=tmclock.DEFAULT.skew_s)

        # evidence pool
        from tendermint_tpu.evidence.pool import EvidencePool

        self.evidence_pool = EvidencePool(new_db("memdb"), self.state_store,
                                          self.block_store, clock=self.clock)
        self.store_repairer.evidence_db = self.evidence_pool._db
        self.evidence_pool.on_corruption = self.store_repairer.note

        # block executor
        self.block_exec = BlockExecutor(
            self.state_store, self.proxy_app.consensus, mempool=self.mempool,
            evidence_pool=self.evidence_pool, event_bus=self.event_bus,
            block_store=self.block_store,
        )

        # consensus
        wal = WAL(config.wal_file()) if config.consensus.wal_path else None
        self.consensus = ConsensusState(
            config.consensus, state, self.block_exec, self.block_store,
            mempool=self.mempool, evidence_pool=self.evidence_pool,
            priv_validator=self.priv_validator, event_bus=self.event_bus, wal=wal,
            clock=self.clock,
        )
        if config.mempool.broadcast:
            self.mempool.enable_txs_available()

        # p2p
        self.node_key = node_key if node_key is not None else NodeKey.load_or_gen(
            config.node_key_file())
        node_info = NodeInfo(
            node_id=self.node_key.id(),
            network=self.genesis.chain_id,
            moniker=config.base.moniker,
        )
        self.transport = Transport(self.node_key, node_info,
                                   config.p2p.handshake_timeout_s,
                                   config.p2p.dial_timeout_s)
        # overload-resilience plane (utils/peerscore.py, docs/OVERLOAD.md):
        # per-node scoreboard + per-peer per-channel ingress ceilings
        from tendermint_tpu.utils import peerscore

        scoreboard = peerscore.PeerScoreBoard(
            peerscore.ScoreConfig.from_p2p_config(config.p2p), logger=logger)
        self.switch = Switch(self.transport, logger=logger,
                             max_inbound=config.p2p.max_num_inbound_peers,
                             max_outbound=config.p2p.max_num_outbound_peers,
                             send_rate=config.p2p.send_rate,
                             recv_rate=config.p2p.recv_rate,
                             scoreboard=scoreboard,
                             msg_rates=peerscore.parse_rate_spec(
                                 config.p2p.recv_msg_rate))
        # drain-bitmap invalid-signature attribution feeds the same board
        self.consensus.scoreboard = scoreboard

        # state sync runs only on a fresh node (reference: node.go:991
        # startStateSync is gated on state.LastBlockHeight == 0)
        self._statesync_active = (config.statesync.enable
                                  and state.last_block_height == 0)
        fast_sync = config.base.fast_sync_mode and len(self.genesis.validators) > 1
        wait_sync = fast_sync or self._statesync_active
        self.consensus_reactor = ConsensusReactor(self.consensus, wait_sync=wait_sync)
        self.mempool_reactor = MempoolReactor(self.mempool, broadcast=config.mempool.broadcast)

        from tendermint_tpu.evidence.reactor import EvidenceReactor
        from tendermint_tpu.statesync import StateSyncReactor, Syncer

        if config.fastsync.version == "v1":
            from tendermint_tpu.blockchain.v1 import BlockchainReactorV1 as _BCR
        elif config.fastsync.version == "v2":
            from tendermint_tpu.blockchain.v2 import BlockchainReactorV2 as _BCR
        else:
            from tendermint_tpu.blockchain.reactor import BlockchainReactor as _BCR
        self.bc_reactor = _BCR(
            state, self.block_exec, self.block_store, fast_sync,
            self.consensus_reactor)
        # BlockResponses feed the repairer's fetch waiters; the repairer's
        # own requests ride the same 0x40 wire protocol over this switch
        self.bc_reactor.repairer = self.store_repairer
        self.store_repairer.switch = self.switch
        self.evidence_reactor = EvidenceReactor(self.evidence_pool)
        syncer = None
        if self._statesync_active:
            syncer = Syncer(
                self.proxy_app.snapshot, self._make_state_provider(),
                chunk_request_timeout_s=config.statesync.chunk_request_timeout_s,
                chunk_fetchers=config.statesync.chunk_fetchers,
                logger=logger)
            # app reject_senders verdicts score the sending peer
            syncer.scoreboard = self.switch.scoreboard
        # Reactor is registered unconditionally: every node SERVES snapshots
        # from its app (reference: node.go:839 statesync.NewReactor).
        self.statesync_reactor = StateSyncReactor(self.proxy_app.snapshot, syncer)

        self.switch.add_reactor("MEMPOOL", self.mempool_reactor)
        self.switch.add_reactor("BLOCKCHAIN", self.bc_reactor)
        self.switch.add_reactor("CONSENSUS", self.consensus_reactor)
        self.switch.add_reactor("EVIDENCE", self.evidence_reactor)
        self.switch.add_reactor("STATESYNC", self.statesync_reactor)

        # tx/block indexer (reference: node/node.go:269-315 createAndStart
        # IndexerService)
        self.tx_indexer = None
        self.block_indexer = None
        self.indexer_service = None
        self.event_sink = None
        self._idx_db = None
        if config.tx_index.indexer == "kv":
            from tendermint_tpu.state.txindex import (
                BlockIndexer,
                IndexerService,
                TxIndexer,
            )

            idx_db = new_db(backend, os.path.join(dbdir, "tx_index.db")
                            if backend != "memdb" else None)
            self.tx_indexer = TxIndexer(idx_db)
            self.block_indexer = BlockIndexer(idx_db)
            self.tx_indexer.on_corruption = self.store_repairer.note
            self.block_indexer.on_corruption = self.store_repairer.note
            self.store_repairer.tx_indexer = self.tx_indexer
            self.store_repairer.block_indexer = self.block_indexer
            self.indexer_service = IndexerService(
                self.tx_indexer, self.block_indexer, self.event_bus, logger)
            self._idx_db = idx_db
        elif config.tx_index.indexer == "psql":
            # Write-only SQL sink (reference: node/node.go:282-299 "psql");
            # tx/block search RPCs report unsupported, as upstream.
            from tendermint_tpu.state.sql_sink import SqlEventSink, connect
            from tendermint_tpu.state.txindex import IndexerService

            if not config.tx_index.psql_conn:
                raise ValueError(
                    "the psql indexer requires tx_index.psql_conn")
            sink = SqlEventSink(connect(config.tx_index.psql_conn),
                                self.genesis.chain_id)
            self.event_sink = sink
            self.tx_indexer = sink.tx_indexer()
            self.block_indexer = sink.block_indexer()
            self.indexer_service = IndexerService(
                self.tx_indexer, self.block_indexer, self.event_bus, logger)

        if self.indexer_service is not None:
            # the heights the indexer has not caught up with count into the
            # backlog that holds apply_block back (docs/EXECUTION.md)
            self.block_exec.follow_backlog(self.indexer_service.backlog_heights)
            self.indexer_service.on_indexed = self.block_exec.backlog_changed

        # Prometheus metrics (reference: node/node.go:118-132 MetricsProvider)
        self.metrics = None
        self.metrics_server = None
        if config.instrumentation.prometheus:
            from tendermint_tpu.utils import metrics as tmmetrics

            self.metrics = tmmetrics.NodeMetrics(
                tmmetrics.Registry(config.instrumentation.namespace))
            tmmetrics.GLOBAL_NODE_METRICS = self.metrics

        # PEX + addrbook (reference: node/node.go:872-889
        # createAddrBookAndSetOnSwitch + createPEXReactorAndAddToSwitch)
        self.addr_book = None
        self.pex_reactor = None
        if config.p2p.pex:
            from tendermint_tpu.p2p.addrbook import AddrBook
            from tendermint_tpu.p2p.pex_reactor import PexReactor

            self.addr_book = AddrBook(
                config.base.resolve(config.p2p.addr_book_file),
                strict=config.p2p.addr_book_strict)
            self.pex_reactor = PexReactor(
                self.addr_book, seed_mode=config.p2p.seed_mode,
                seeds=config.p2p.seeds.split(",") if config.p2p.seeds else [],
                logger=logger)
            self.switch.add_reactor("PEX", self.pex_reactor)
            # a ban evicts the peer from the address book too: PEX must
            # not keep recommending (or redialing) a sanctioned identity
            self.switch.scoreboard.on_ban.append(
                lambda pid, until: self.addr_book.mark_bad(pid))

        # flight recorder (utils/trace.py, docs/OBSERVABILITY.md): one
        # instance-scoped Tracer per node — the module-global ring would
        # interleave spans from every node of an in-process mesh. Enabled
        # by TMTPU_TRACE=1 (ring size TMTPU_TRACE_CAP); the fabric/soak
        # harness and the unsafe_trace RPC route can flip it live.
        from tendermint_tpu.utils import trace as tmtrace

        self.tracer = tmtrace.Tracer(name=self.node_key.id()[:12],
                                     enabled=tmtrace.trace_enabled_from_env())
        self.consensus.tracer = self.tracer
        self.mempool.tracer = self.tracer
        self.switch.tracer = self.tracer
        self.bc_reactor.tracer = self.tracer
        self.store_repairer.tracer = self.tracer

        self.rpc_server = None
        self._tx_notify_thread = None

        # consensus stall watchdog (consensus/watchdog.py): a node stalled
        # behind a healed partition hands itself back to fast-sync catchup
        from tendermint_tpu.consensus.watchdog import ConsensusWatchdog

        self.watchdog = ConsensusWatchdog(
            config.consensus, self.block_store, self.consensus_reactor,
            self.bc_reactor, self.handoff_to_fastsync,
            metrics=self.metrics, logger=logger)

    def install_misbehavior(self, spec: str) -> None:
        """Maverick mode: make THIS node byzantine (reference:
        test/maverick/consensus/misbehavior.go, selected per node via the
        maverick binary's --misbehaviors flag; here via the TMTPU_BYZ /
        TMTPU_MISBEHAVIOR env vars so an e2e manifest can mark a real
        PROCESS byzantine).

        ``spec`` is a consensus/misbehavior.py behavior spec — a bare
        behavior name (``double_prevote``) or a height-windowed map
        (``equivocate~3-5+lunatic~7-``, docs/BYZANTINE.md). The installer
        swaps a double-sign-guarded FilePV for an unguarded signer with
        the SAME key (a byzantine actor ignores its own safety guard) and
        wires the per-slot consensus hooks."""
        from tendermint_tpu.consensus import misbehavior as mb

        mb.install(self, spec)

    # --- lifecycle (reference: node/node.go:941 OnStart) -------------------

    def start(self) -> None:
        # Chaos layer: (re)load TMTPU_FAULTS/TMTPU_FAULT_SEED so every node
        # process starts its fault-site hit counters from zero -- a crash
        # matrix run is then replayable from the env spec + seed alone.
        from tendermint_tpu.utils import faults, nemesis

        faults.install_from_env()
        nemesis.install_from_env()
        # AOT-warm the batch-verify kernel off the critical path so the first
        # real commit at a warm bucket size is a compile-cache hit
        # (reference has no analogue; XLA compilation is TPU-build-specific).
        # The genesis validators' key types decide which kernels: a chain
        # with sr25519 validators must not compile that kernel on its first
        # commit, on one chip as on several.
        from tendermint_tpu.crypto import batch as crypto_batch

        crypto_batch.warmup(key_types=tuple(sorted(
            {v.pub_key.type for v in self.genesis.validators})))
        if self.config.p2p.laddr:
            la = self.transport.listen(self.config.p2p.laddr)
            if self.addr_book is not None:
                from tendermint_tpu.p2p.addrbook import NetAddress

                hp = la.split("://", 1)[1]
                host, port = hp.rsplit(":", 1)
                self.addr_book.add_our_address(
                    NetAddress(self.node_key.id(), host, int(port)))
        # before the switch starts: its redial loop sleeps a second when it
        # finds no persistent peer on its first pass
        if self.config.p2p.persistent_peers:
            self.switch.add_persistent_peers(
                self.config.p2p.persistent_peers.split(","))
        self.switch.start()
        # boot-time integrity scrub (TMTPU_SCRUB_ON_START=0 opts out,
        # docs/DURABILITY.md), on a background thread: the full walk is
        # O(chain length) and must not serialize startup. Serving paths
        # stay safe meanwhile — every read is individually checked, so a
        # peer asking for a not-yet-scrubbed rotten row gets typed-missing
        # and the repair hook fires. Repairs drain on the repairer's
        # background worker once peers connect.
        from tendermint_tpu.store.scrub import scrub_on_start_enabled

        if scrub_on_start_enabled():
            import threading

            def _boot_scrub():
                try:
                    report = self.scrubber().scrub(
                        repairer=self.store_repairer, drain=False)
                    if report.corruptions and self.logger:
                        self.logger.error(
                            "startup scrub found corruption; repairs "
                            "scheduled", corrupt=len(report.corruptions),
                            checked=report.checked)
                except Exception as e:  # noqa: BLE001 - the scrub is
                    # advisory; a failed pass must not take the node down
                    if self.logger:
                        self.logger.error("startup scrub failed", err=e)

            threading.Thread(target=_boot_scrub, name="boot-scrub",
                             daemon=True).start()
        if self._statesync_active:
            import threading

            threading.Thread(target=self._run_state_sync, daemon=True).start()
        elif not self.consensus_reactor.wait_sync:
            self.consensus.start()
        else:
            self.bc_reactor.start_sync()
        self.watchdog.start()
        if self.mempool.txs_available() is not None:
            import threading

            def notify():
                ev = self.mempool.txs_available()
                try:
                    while self._running:
                        if ev.wait(timeout=0.2):
                            ev.clear()
                            self.consensus.handle_txs_available()
                except Exception as e:  # noqa: BLE001 - notifier death would
                    # silently stop empty-block-suppressed proposers
                    if self.logger:
                        self.logger.error("tx-available notifier crashed",
                                          err=e)

            self._running = True
            self._tx_notify_thread = threading.Thread(target=notify, daemon=True)
            self._tx_notify_thread.start()
        else:
            self._running = True
        # RPC
        if self.config.rpc.laddr:
            from tendermint_tpu.rpc.server import RPCServer

            self.rpc_server = RPCServer(self)
            self.rpc_server.start(self.config.rpc.laddr)
        if self.config.rpc.grpc_laddr:
            from tendermint_tpu.rpc.grpc_server import BroadcastAPIServer

            self.grpc_server = BroadcastAPIServer(self, self.config.rpc.grpc_laddr)
            self.grpc_server.start()
        # indexer + Prometheus (reference: node/node.go:964,1219)
        if self.indexer_service is not None:
            self.indexer_service.start()
        if self.metrics is not None:
            from tendermint_tpu.utils.metrics import MetricsServer

            self.metrics_server = MetricsServer(
                self.metrics.registry,
                self.config.instrumentation.prometheus_listen_addr)
            self.metrics_server.start()
            self._metrics_thread = __import__("threading").Thread(
                target=self._metrics_sampler, name="metrics-sampler", daemon=True)
            self._metrics_thread.start()

    def stop(self) -> None:
        self._running = False
        # release the flight recorder's module-wide ENABLED refcount: a
        # stopped node must not pin every later hot-path guard in this
        # process on the instrumented branch (fabric churn builds and
        # stops hundreds of nodes per session)
        self.tracer.disable()
        self.watchdog.stop()
        if self.rpc_server is not None:
            self.rpc_server.stop()
        if getattr(self, "grpc_server", None) is not None:
            self.grpc_server.stop()
        if self.metrics_server is not None:
            self.metrics_server.stop()
        self.consensus.stop()
        # drain queued post-commit event publishes (so indexers/subscribers
        # see every committed height), let the index catch up with what was
        # published, then detach the indexer and park the worker thread
        self.block_exec.flush_post_commit(timeout_s=5.0)
        if self.indexer_service is not None:
            if self.indexer_service.backlog_heights():
                self.indexer_service.wait_indexed(self.block_store.height,
                                                  timeout_s=5.0)
            self.indexer_service.stop()
        if self.event_sink is not None:
            self.event_sink.stop()
        self.block_exec.stop()
        self.switch.stop()
        # the removed peers' gossip routines read the block store: gone
        # before a caller may close the stores (close_stores)
        self.consensus_reactor.wait_gossip_ended(timeout_s=2.0)
        if getattr(self, "signer_endpoint", None) is not None:
            self.signer_endpoint.close()
        # release the ingest coalescer's executor thread (it holds strong
        # mempool/app refs; fabric churn would otherwise leak one parked
        # thread per stopped node, docs/INGEST.md)
        self.mempool._ingest.stop()
        self.proxy_app.stop()
        # A warm-up compile still in flight must finish before the process
        # may exit: the XLA runtime aborts at interpreter teardown under a
        # live compile. (Its outcome is in crypto_batch.WARMUP; a failure
        # was logged when it happened.)
        from tendermint_tpu.crypto import batch as crypto_batch

        if not crypto_batch.WARMUP.join(timeout=600.0) and self.logger:
            self.logger.error("kernel warm-up still running after 600 s")

    def close_stores(self) -> None:
        """Close the block, state and index stores' connections (sqlite
        folds its WAL back into the file). For a stopped node whose process
        lives on: stop() leaves the stores readable, and a process that
        exits needs neither."""
        for db in (self.block_store._db, self.state_store._db, self._idx_db):
            if db is not None:
                db.close()

    def abort(self) -> None:
        """Power-loss teardown (docs/SOAK.md crash actions): release this
        incarnation's threads and sockets WITHOUT the orderly flushes
        stop() performs — no consensus stop (whose WAL close is preceded by
        completing the in-flight transition), no post-commit drain, no
        indexer join, no sink/DB close — so the durable home is abandoned
        exactly as the crash instant left it and a rebooted incarnation
        must recover through handshake + WAL replay + fast-sync alone.

        In-process honesty note: the hosting interpreter survives, so
        bytes already buffered by the OS (and sqlite connections reaped by
        GC) persist — a strict SUPERSET of what a real power cut keeps.
        Sub-fsync damage (a torn WAL tail) is injected explicitly by the
        crash harness on the abandoned home (faults.tear_wal_tail)."""
        self._running = False
        self.tracer.disable()
        self.watchdog.stop()
        if self.rpc_server is not None:
            self.rpc_server.stop()
        if getattr(self, "grpc_server", None) is not None:
            self.grpc_server.stop()
        if self.metrics_server is not None:
            self.metrics_server.stop()
        # freeze consensus: pause() parks the receive routine and ticker
        # but leaves the WAL unclosed and any half-finalized round state
        # (e.g. a crash-site rule that aborted _finalize_commit) in place
        self.consensus.pause()
        if self.indexer_service is not None:
            # detach from the event bus without draining queued postings —
            # a crash loses exactly the not-yet-indexed tail
            self.indexer_service.stop()
        # park worker threads without flush_post_commit: queued event
        # publishes for already-applied heights are lost, as in a crash
        self.block_exec.stop()
        self.switch.stop()
        if getattr(self, "signer_endpoint", None) is not None:
            self.signer_endpoint.close()
        self.mempool._ingest.stop()
        self.proxy_app.stop()

    def _metrics_sampler(self) -> None:
        """Gauge sampling loop; histograms are fed at their call sites
        (reference wires metrics structs through constructors -- a sampler
        keeps the hot paths free of metric plumbing)."""
        import sys
        import time as _t

        from tendermint_tpu.utils import faults as _faults
        from tendermint_tpu.utils import nemesis as _nemesis

        m = self.metrics
        last_height = self.block_store.height
        last_height_t = _t.monotonic()
        # chaos counters are sampled as deltas against the layers' own
        # monotonic counts, so /metrics stays a true Prometheus counter
        last_site_hits: dict = {}
        last_fired: dict = {}
        last_nemesis_fired: dict = {}
        last_bans = 0
        last_shed: dict = {}
        last_rate_limited: dict = {}
        last_score_peers: set = set()
        # Counter series are permanent once created; cap the per-peer
        # label space so identity-minting churn cannot grow /metrics
        # without bound (overflow aggregates under peer="_overflow")
        rl_label_cap = 1024
        rl_labels_seen: set = set()

        def _rl_labels(k):
            peer = k[0][:16]
            if peer in rl_labels_seen or len(rl_labels_seen) < rl_label_cap:
                rl_labels_seen.add(peer)
                return {"peer": peer, "channel": k[1]}
            return {"peer": "_overflow", "channel": k[1]}

        def _pump_counter(counter, now_counts, last_counts, label_fn):
            for key, n in now_counts.items():
                delta = n - last_counts.get(key, 0)
                if delta > 0:
                    counter.add(delta, **label_fn(key))
            last_counts.clear()
            last_counts.update(now_counts)

        while self._running:
            try:
                h = self.block_store.height
                m.height.set(h)
                if h > last_height:
                    now = _t.monotonic()
                    m.block_interval_seconds.observe((now - last_height_t) / max(h - last_height, 1))
                    meta = self.block_store.load_block_meta(h)
                    if meta is not None:
                        m.num_txs.set(meta.num_txs)
                        m.total_txs.add(meta.num_txs)
                        m.block_size_bytes.set(meta.block_size)
                    last_height, last_height_t = h, now
                st = self.state_store.load()
                if st.validators is not None:
                    m.validators.set(st.validators.size())
                    m.validators_power.set(st.validators.total_voting_power())
                m.mempool_size.set(self.mempool.size())
                m.peers.set(len(self.switch.peers))
                m.rounds.set(getattr(self.consensus.rs, "round", 0))
                # chaos observability: fault-layer hit/fired counts and
                # nemesis link-plane firings, as counter deltas
                hits, fired = _faults.snapshot()
                _pump_counter(m.fault_site_hits, hits, last_site_hits,
                              lambda site: {"site": site})
                _pump_counter(m.faults_fired, fired, last_fired,
                              lambda k: {"site": k[0], "action": k[1]})
                _, nem_fired = _nemesis.PLANE.snapshot()
                _pump_counter(m.nemesis_fired, nem_fired, last_nemesis_fired,
                              lambda k: {"site": k[0], "action": k[1]})
                # overload-resilience plane: scores as live gauges, bans/
                # sheds/rate-limits as counter deltas (one board per node)
                board = self.switch.scoreboard.snapshot()
                score_peers = {pid[:16] for pid in board["scores"]}
                for pid, s in board["scores"].items():
                    m.peer_score.set(s, peer=pid[:16])
                for pid in last_score_peers - score_peers:
                    # banned/decayed-away peers: drop the series — a
                    # frozen pre-ban value misleads dashboards, and a
                    # zeroed-but-kept line per identity ever seen would
                    # grow /metrics cardinality without bound
                    m.peer_score.remove(peer=pid)
                last_score_peers = score_peers
                if board["bans_total"] > last_bans:
                    m.peers_banned.add(board["bans_total"] - last_bans)
                    last_bans = board["bans_total"]
                _pump_counter(m.shed, board["shed"], last_shed,
                              lambda ch: {"channel": ch})
                _pump_counter(m.rate_limited, board["rate_limited"],
                              last_rate_limited, _rl_labels)
                # device breaker state: only meaningful once a kernel
                # module is loaded; never force the import from a sampler
                for kernel in ("ed25519", "sr25519"):
                    kmod = sys.modules.get(f"tendermint_tpu.ops.{kernel}_batch")
                    if kmod is not None:
                        m.breaker_open.set(
                            1.0 if kmod.BREAKER.is_open else 0.0, kernel=kernel)
                        m.breaker_trips.set(kmod.BREAKER.trips, kernel=kernel)
            except Exception:  # noqa: BLE001 - sampling must never kill a node
                pass
            _t.sleep(0.25)

    # --- watchdog recovery -------------------------------------------------

    def handoff_to_fastsync(self) -> None:
        """Stall-watchdog recovery: pause the spinning consensus machine
        and re-enter fast-sync catchup — the block pool + verify-ahead
        pipeline pull the missing heights from peers' stored commits, then
        switch_to_consensus restarts consensus at the tip. No process
        restart, no WAL close; the consensus reactor's wait_sync latch
        keeps vote/proposal handling quiet while the pipeline owns the
        store."""
        self.consensus_reactor.wait_sync = True
        self.consensus.pause()
        self.consensus.rewind_for_catchup()
        self.bc_reactor.switch_to_fast_sync(self.state_store.load())

    # --- state sync --------------------------------------------------------

    def _make_state_provider(self):
        """Light-client state provider over the configured RPC servers
        (reference: node.go:648 startStateSync -> stateprovider.go:48)."""
        from tendermint_tpu.light.client import TrustOptions
        from tendermint_tpu.light.provider import HTTPProvider
        from tendermint_tpu.statesync import LightClientStateProvider

        cfg = self.config.statesync
        servers = [s for s in cfg.rpc_servers if s]
        if not servers:
            raise ValueError("state sync requires statesync.rpc_servers")
        if cfg.trust_height <= 0 or not cfg.trust_hash:
            raise ValueError("state sync requires statesync.trust_height and trust_hash")
        chain_id = self.genesis.chain_id
        providers = [HTTPProvider(chain_id, s) for s in servers]
        return LightClientStateProvider(
            chain_id,
            (self.genesis.consensus_params.version.app_version
             if self.genesis.consensus_params else 0),
            TrustOptions(period_s=cfg.trust_period_s, height=cfg.trust_height,
                         hash=bytes.fromhex(cfg.trust_hash)),
            providers[0], providers[1:],
            consensus_params=self.genesis.consensus_params,
            initial_height=self.genesis.initial_height,
            logger=self.logger,
        )

    def _run_state_sync(self) -> None:
        """Bootstrap from a snapshot, then hand off to fast sync (reference:
        node.go:991 startStateSync)."""
        cfg = self.config.statesync
        try:
            state, commit = self.statesync_reactor.sync(cfg.discovery_time_s)
        except Exception as e:  # noqa: BLE001
            if self.logger:
                self.logger.error("state sync failed", err=e)
            # Fall back to fast sync from genesis rather than hanging.
            self.bc_reactor.start_sync()
            return
        self.state_store.bootstrap(state)
        self.block_store.save_seen_commit(state.last_block_height, commit)
        # consensus picks the state up via the fast-sync -> consensus handoff
        # (ConsensusReactor.switch_to_consensus -> cs.update_to_state)
        self.bc_reactor.switch_to_fast_sync(state)

    # --- helpers -----------------------------------------------------------

    def scrubber(self):
        """A Scrubber over this node's full storage plane (startup pass +
        the ``unsafe_scrub`` RPC route; docs/DURABILITY.md)."""
        from tendermint_tpu.store.scrub import Scrubber

        idx_db = (self.tx_indexer._db
                  if getattr(self, "tx_indexer", None) is not None
                  and hasattr(self.tx_indexer, "_db") else None)
        return Scrubber(
            block_store=self.block_store, state_store=self.state_store,
            evidence_db=self.evidence_pool._db, txindex_db=idx_db,
            tracer=self.tracer)

    def p2p_addr(self) -> str:
        la = self.transport.node_info.listen_addr
        return f"{self.node_key.id()}@{la.split('://', 1)[1]}" if la else ""
