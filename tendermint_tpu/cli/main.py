"""Operator CLI (reference: cmd/tendermint/commands/): init, start, testnet,
show-node-id, show-validator, gen-validator, gen-node-key, unsafe-reset-all,
rollback, replay, version.

Usage: python -m tendermint_tpu.cli <command> [--home DIR] [options]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import time

from tendermint_tpu.config.config import Config, default_config


def _home(args) -> str:
    return os.path.abspath(args.home or os.environ.get("TMTPU_HOME", os.path.expanduser("~/.tendermint-tpu")))


def _ensure_dirs(root: str) -> None:
    for d in ("config", "data"):
        os.makedirs(os.path.join(root, d), exist_ok=True)


def _load_config(root: str) -> Config:
    cfg = default_config().set_root(root)
    toml_path = os.path.join(root, "config", "config.toml")
    if os.path.exists(toml_path):
        from tendermint_tpu.config.toml import load_toml_into

        load_toml_into(cfg, toml_path)
    cfg.base.root_dir = root
    return cfg


def cmd_init(args) -> int:
    """reference: cmd/tendermint/commands/init.go."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.p2p.key import NodeKey
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.ttime import Time

    root = _home(args)
    _ensure_dirs(root)
    cfg = default_config().set_root(root)

    pv = FilePV.load_or_generate(cfg.priv_validator_key_file(),
                                 cfg.priv_validator_state_file())
    NodeKey.load_or_gen(cfg.node_key_file())

    gen_file = cfg.genesis_file()
    if os.path.exists(gen_file):
        print(f"Found genesis file {gen_file}")
    else:
        chain_id = args.chain_id or f"test-chain-{os.urandom(3).hex()}"
        doc = GenesisDoc(
            chain_id=chain_id,
            genesis_time=Time.now(),
            validators=[GenesisValidator(b"", pv.get_pub_key(), 10)],
        )
        doc.validate_and_complete()
        doc.save_as(gen_file)
        print(f"Generated genesis file {gen_file}")

    from tendermint_tpu.config.toml import write_config_toml

    toml_path = os.path.join(root, "config", "config.toml")
    if not os.path.exists(toml_path):
        write_config_toml(cfg, toml_path)
        print(f"Generated config file {toml_path}")
    return 0


def cmd_start(args) -> int:
    """reference: cmd/tendermint/commands/run_node.go."""
    from tendermint_tpu.node.node import Node

    root = _home(args)
    cfg = _load_config(root)
    if args.proxy_app:
        cfg.base.proxy_app = args.proxy_app
    if args.p2p_laddr:
        cfg.p2p.laddr = args.p2p_laddr
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    if args.persistent_peers:
        cfg.p2p.persistent_peers = args.persistent_peers

    node = Node(cfg)
    mb = os.environ.get("TMTPU_BYZ") or os.environ.get("TMTPU_MISBEHAVIOR")
    if mb:
        # e2e byzantine node (reference: test/maverick); TMTPU_BYZ takes a
        # full height-windowed behavior spec (docs/BYZANTINE.md), the
        # legacy TMTPU_MISBEHAVIOR a bare behavior name; honest peers must
        # detect what is detectable and keep committing.
        node.install_misbehavior(mb)
    node.start()
    print(f"Started node {node.node_key.id()} p2p={node.transport.node_info.listen_addr}")

    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        node.stop()
    return 0


def cmd_show_node_id(args) -> int:
    from tendermint_tpu.p2p.key import NodeKey

    cfg = _load_config(_home(args))
    print(NodeKey.load(cfg.node_key_file()).id())
    return 0


def cmd_show_validator(args) -> int:
    import base64

    from tendermint_tpu.privval.file_pv import FilePV

    cfg = _load_config(_home(args))
    pv = FilePV.load(cfg.priv_validator_key_file(), cfg.priv_validator_state_file())
    pub = pv.get_pub_key()
    print(json.dumps({"type": "tendermint/PubKeyEd25519",
                      "value": base64.b64encode(pub.bytes()).decode()}))
    return 0


def cmd_gen_validator(args) -> int:
    import base64

    from tendermint_tpu.crypto import ed25519

    priv = ed25519.gen_priv_key()
    print(json.dumps({
        "address": priv.pub_key().address().hex().upper(),
        "pub_key": {"type": "tendermint/PubKeyEd25519",
                    "value": base64.b64encode(priv.pub_key().bytes()).decode()},
        "priv_key": {"type": "tendermint/PrivKeyEd25519",
                     "value": base64.b64encode(priv.bytes()).decode()},
    }, indent=2))
    return 0


def cmd_gen_node_key(args) -> int:
    from tendermint_tpu.p2p.key import NodeKey

    cfg = _load_config(_home(args))
    nk = NodeKey.load_or_gen(cfg.node_key_file())
    print(nk.id())
    return 0


def cmd_unsafe_reset_all(args) -> int:
    """reference: cmd/tendermint/commands/reset.go."""
    root = _home(args)
    data = os.path.join(root, "data")
    if os.path.isdir(data):
        shutil.rmtree(data)
        os.makedirs(data)
    # keep the validator key; reset sign state
    from tendermint_tpu.privval.file_pv import FilePV

    cfg = default_config().set_root(root)
    if os.path.exists(cfg.priv_validator_key_file()):
        pv = FilePV.load(cfg.priv_validator_key_file(), cfg.priv_validator_state_file())
        pv.last_sign_state.save()
    print(f"Reset {data}")
    return 0


def cmd_testnet(args) -> int:
    """Generate a v-node localnet layout (reference:
    cmd/tendermint/commands/testnet.go)."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.p2p.key import NodeKey
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.ttime import Time
    from tendermint_tpu.config.toml import write_config_toml

    out = os.path.abspath(args.output)
    n = args.v
    pvs = []
    node_keys = []
    for i in range(n):
        root = os.path.join(out, f"node{i}")
        _ensure_dirs(root)
        cfg = default_config().set_root(root)
        pvs.append(FilePV.load_or_generate(cfg.priv_validator_key_file(),
                                           cfg.priv_validator_state_file()))
        node_keys.append(NodeKey.load_or_gen(cfg.node_key_file()))

    doc = GenesisDoc(
        chain_id=args.chain_id or f"testnet-{os.urandom(3).hex()}",
        genesis_time=Time.now(),
        validators=[GenesisValidator(b"", pv.get_pub_key(), 1) for pv in pvs],
    )
    doc.validate_and_complete()

    peers = ",".join(
        f"{node_keys[i].id()}@127.0.0.1:{args.starting_port + 2 * i}" for i in range(n)
    )
    for i in range(n):
        root = os.path.join(out, f"node{i}")
        cfg = default_config().set_root(root)
        doc.save_as(cfg.genesis_file())
        cfg.p2p.laddr = f"tcp://127.0.0.1:{args.starting_port + 2 * i}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{args.starting_port + 2 * i + 1}"
        cfg.p2p.persistent_peers = peers
        write_config_toml(cfg, os.path.join(root, "config", "config.toml"))
    print(f"Successfully initialized {n} node directories in {out}")
    return 0


def cmd_rollback(args) -> int:
    """Undo one height (reference: cmd/tendermint/commands/rollback.go,
    state/rollback.go:112)."""
    from tendermint_tpu.state.rollback import rollback_state

    cfg = _load_config(_home(args))
    height, app_hash = rollback_state(cfg)
    print(f"Rolled back state to height {height} and hash {app_hash.hex().upper()}")
    return 0


def cmd_version(args) -> int:
    print("0.34.24-tpu")
    return 0


def cmd_light(args) -> int:
    """Light client daemon: track a chain over RPC with verified headers and
    serve verified light blocks (reference: cmd/tendermint/commands/light.go).
    """
    from tendermint_tpu.light import (
        Client,
        DBStore,
        HTTPProvider,
        TrustOptions,
    )
    from tendermint_tpu.store.db import new_db
    from tendermint_tpu.types.ttime import Time

    root = _home(args)
    _ensure_dirs(root)
    chain_id = args.chain_id
    primary = HTTPProvider(chain_id, args.primary)
    witnesses = [HTTPProvider(chain_id, w) for w in args.witnesses.split(",") if w]
    store = DBStore(new_db("sqlite", os.path.join(root, "data", "light.db")))
    if bool(args.trust_height) != bool(args.trust_hash):
        # Half an anchor is no anchor: silently falling back to TOFU would
        # discard the operator's pin (reference light.go requires both).
        print("error: --trusted-height and --trusted-hash must be given together",
              file=sys.stderr)
        return 1
    if args.trust_height and args.trust_hash:
        opts = TrustOptions(period_s=args.trust_period, height=args.trust_height,
                            hash=bytes.fromhex(args.trust_hash))
    else:
        # TOFU bootstrap from the primary's latest header
        lb = primary.light_block(0)
        opts = TrustOptions(period_s=args.trust_period, height=lb.height,
                            hash=lb.hash())
        print(f"Trusting height {lb.height} hash {lb.hash().hex().upper()} (TOFU)")
    from tendermint_tpu.light import SEQUENTIAL, SKIPPING

    client = Client(chain_id, opts, primary, witnesses, store,
                    verification_mode=SEQUENTIAL if args.sequential else SKIPPING,
                    max_clock_drift_s=120.0)
    print(f"Light client running against {args.primary} "
          f"(latest trusted: {client.latest_trusted.height})")
    proxy = None
    if args.laddr:
        from tendermint_tpu.light.proxy import LightProxy

        proxy = LightProxy(client, args.primary, args.laddr)
        proxy.start()
        print(f"Verifying proxy listening on {proxy.laddr}")
    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    while not stop:
        try:
            lb = client.update(Time.now())
            if lb is not None:
                print(f"verified height {lb.height} "
                      f"hash {lb.hash().hex().upper()[:16]}...")
        except Exception as e:  # noqa: BLE001
            print(f"update failed: {e}", file=sys.stderr)
        if args.once:
            break
        time.sleep(args.interval)
    if proxy is not None:
        proxy.stop()
    return 0


def cmd_signer_harness(args) -> int:
    """Operator tool: validate a remote signer deployment (reference:
    tools/tm-signer-harness, docs/tools/remote-signer-validation.md)."""
    from tendermint_tpu.privval.harness import run_harness, summary_json

    code = run_harness(args.addr, args.chain_id, home=args.home,
                       accept_timeout_s=args.accept_timeout)
    print(summary_json(code))
    return code


def cmd_replay(args) -> int:
    """Replay the block store through a fresh app and report the final state
    (reference: cmd/tendermint/commands/replay.go + consensus/replay_file.go).
    """
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.node.node import default_app
    from tendermint_tpu.abci.proxy import new_app_conns
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.store.db import new_db
    from tendermint_tpu.types.genesis import GenesisDoc

    cfg = _load_config(_home(args))
    dbdir = cfg.db_dir()
    block_store = BlockStore(new_db(cfg.base.db_backend,
                                    os.path.join(dbdir, "blockstore.db")))
    state_store = StateStore(new_db(cfg.base.db_backend,
                                    os.path.join(dbdir, "state.db")))
    genesis = GenesisDoc.from_file(cfg.genesis_file())
    state = state_store.load()
    proxy = new_app_conns(default_app(cfg.base.proxy_app))
    hs = Handshaker(state_store, block_store, genesis)
    new_state = hs.handshake(state, proxy.consensus)
    print(f"Replayed to height {new_state.last_block_height} "
          f"app_hash {new_state.app_hash.hex().upper()}")
    return 0


def cmd_reindex_event(args) -> int:
    """Rebuild the tx/block index from the block store + stored ABCI
    responses (reference: cmd/tendermint/commands/reindex_event.go)."""
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.state.txindex import BlockIndexer, TxIndexer
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.store.db import new_db

    cfg = _load_config(_home(args))
    dbdir = cfg.db_dir()
    block_store = BlockStore(new_db(cfg.base.db_backend,
                                    os.path.join(dbdir, "blockstore.db")))
    state_store = StateStore(new_db(cfg.base.db_backend,
                                    os.path.join(dbdir, "state.db")))
    idx_db = new_db(cfg.base.db_backend, os.path.join(dbdir, "tx_index.db"))
    txi, bi = TxIndexer(idx_db), BlockIndexer(idx_db)
    start = args.start_height or block_store.base
    end = args.end_height or block_store.height
    n_txs = 0
    skipped = []
    for h in range(start, end + 1):
        block = block_store.load_block(h)
        if block is None:
            continue
        try:
            resp = state_store.load_abci_responses(h)
        except Exception:  # noqa: BLE001 - pruned responses
            # Never index fabricated results (the reference aborts here);
            # skip the height and tell the operator.
            skipped.append(h)
            continue
        deliver = resp.deliver_txs
        for i, tx in enumerate(block.data.txs):
            if i >= len(deliver):
                break
            txi.index(h, i, tx, deliver[i])
            n_txs += 1
        bi.index(h, resp.begin_block.events if resp.begin_block else [],
                 resp.end_block.events if resp.end_block else [])
    print(f"Reindexed heights {start}..{end}: {n_txs} txs"
          + (f"; skipped {len(skipped)} heights with pruned ABCI responses"
             if skipped else ""))
    return 0


def cmd_compact(args) -> int:
    """Compact the sqlite databases (reference:
    cmd/tendermint/commands/compact.go for goleveldb)."""
    import sqlite3

    cfg = _load_config(_home(args))
    if cfg.base.db_backend != "sqlite":
        print(f"nothing to compact for backend {cfg.base.db_backend!r}")
        return 0
    for name in os.listdir(cfg.db_dir()):
        if not name.endswith(".db"):
            continue
        path = os.path.join(cfg.db_dir(), name)
        before = os.path.getsize(path)
        conn = sqlite3.connect(path)
        conn.execute("VACUUM")
        conn.close()
        print(f"compacted {name}: {before} -> {os.path.getsize(path)} bytes")
    return 0


def cmd_debug(args) -> int:
    """Dump node state for debugging (reference:
    cmd/tendermint/commands/debug/dump.go): config, stores summary, and
    (when the node is running) /status + /dump_consensus_state via RPC."""
    import urllib.request

    cfg = _load_config(_home(args))
    out_dir = args.output or os.path.join(_home(args), "debug")
    os.makedirs(out_dir, exist_ok=True)
    doc = {"home": _home(args), "db_backend": cfg.base.db_backend}
    try:
        from tendermint_tpu.store.block_store import BlockStore
        from tendermint_tpu.store.db import new_db

        bs = BlockStore(new_db(cfg.base.db_backend,
                               os.path.join(cfg.db_dir(), "blockstore.db")))
        doc["block_store"] = {"base": bs.base, "height": bs.height}
    except Exception as e:  # noqa: BLE001
        doc["block_store"] = {"error": str(e)}
    if args.rpc_laddr:
        base = "http://" + args.rpc_laddr.split("://", 1)[-1]
        for method in ("status", "dump_consensus_state", "net_info"):
            try:
                body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                                   "params": {}}).encode()
                with urllib.request.urlopen(urllib.request.Request(
                        base, data=body,
                        headers={"Content-Type": "application/json"}),
                        timeout=5) as r:
                    doc[method] = json.loads(r.read()).get("result")
            except Exception as e:  # noqa: BLE001
                doc[method] = {"error": str(e)}
    path = os.path.join(out_dir, "dump.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, default=str)
    print(f"wrote {path}")
    return 0


def cmd_probe_upnp(args) -> int:
    """Probe for a UPnP gateway (reference: cmd/tendermint/commands/
    probe_upnp.go)."""
    from tendermint_tpu.p2p import upnp

    try:
        out = upnp.probe(timeout_s=args.timeout)
    except upnp.UPnPError as e:
        print(f"Probe failed: {e}")
        return 1
    print(json.dumps(out, indent=2))
    return 0


def cmd_abci_server(args) -> int:
    """Run an example app behind an ABCI socket (reference:
    abci/cmd/abci-cli: kvstore and counter subcommands)."""
    from tendermint_tpu.abci.server import ABCIServer
    from tendermint_tpu.store.db import new_db

    if args.app == "counter":
        from tendermint_tpu.abci.counter import CounterApp

        if args.db or args.snapshot_interval:
            print("abci-server: --db/--snapshot-interval apply only to "
                  "kvstore", file=sys.stderr)
            return 1
        app = CounterApp(serial=args.serial)
    else:
        from tendermint_tpu.abci.kvstore import KVStoreApplication

        db = new_db("sqlite", args.db) if args.db else None
        app = KVStoreApplication(db, snapshot_interval=args.snapshot_interval)
    server = ABCIServer(app, args.address)
    server.start()
    print(f"ABCI {args.app} server listening on {server.addr}")
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        server.stop()
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tendermint-tpu")
    p.add_argument("--home", default=None, help="node home directory")
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("init", help="initialize a node")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("start", help="run the node")
    sp.add_argument("--proxy_app", default="")
    sp.add_argument("--p2p.laddr", dest="p2p_laddr", default="")
    sp.add_argument("--rpc.laddr", dest="rpc_laddr", default="")
    sp.add_argument("--p2p.persistent_peers", dest="persistent_peers", default="")
    sp.set_defaults(fn=cmd_start)

    for name, fn in (("show-node-id", cmd_show_node_id),
                     ("show-validator", cmd_show_validator),
                     ("gen-validator", cmd_gen_validator),
                     ("gen-node-key", cmd_gen_node_key),
                     ("unsafe-reset-all", cmd_unsafe_reset_all),
                     ("rollback", cmd_rollback),
                     ("version", cmd_version)):
        sp = sub.add_parser(name)
        sp.set_defaults(fn=fn)

    sp = sub.add_parser("testnet", help="generate a localnet")
    sp.add_argument("--v", type=int, default=4)
    sp.add_argument("--output", "-o", default="./mytestnet")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--starting-port", type=int, default=26656)
    sp.set_defaults(fn=cmd_testnet)

    sp = sub.add_parser("light", help="run a light client daemon")
    sp.add_argument("chain_id")
    sp.add_argument("--primary", "-p", required=True, help="primary RPC address")
    sp.add_argument("--witnesses", "-w", default="", help="comma-separated witness RPC addresses")
    sp.add_argument("--trusted-height", dest="trust_height", type=int, default=0)
    sp.add_argument("--trusted-hash", dest="trust_hash", default="")
    sp.add_argument("--trust-period", dest="trust_period", type=float,
                    default=168 * 3600.0)
    sp.add_argument("--interval", type=float, default=1.0)
    sp.add_argument("--once", action="store_true", help="single update then exit")
    sp.add_argument("--sequential", action="store_true",
                    help="sequential verification: every header between the "
                         "trusted one and the target, in batched windows "
                         "(default: skipping, i.e. bisection)")
    sp.add_argument("--laddr", default="",
                    help="serve a verifying RPC proxy on this address")
    sp.set_defaults(fn=cmd_light)

    sp = sub.add_parser(
        "signer-harness",
        help="validate a remote signer deployment (reference: "
             "tools/tm-signer-harness)")
    sp.add_argument("--addr", required=True,
                    help="listen address the remote signer dials, e.g. "
                         "tcp://127.0.0.1:26659")
    sp.add_argument("--chain-id", required=True)
    sp.add_argument("--accept-timeout", type=float, default=30.0)
    sp.set_defaults(fn=cmd_signer_harness)

    sp = sub.add_parser("replay", help="replay the block store through the app")
    sp.set_defaults(fn=cmd_replay)

    sp = sub.add_parser("reindex-event", help="rebuild the tx/block index")
    sp.add_argument("--start-height", type=int, default=0)
    sp.add_argument("--end-height", type=int, default=0)
    sp.set_defaults(fn=cmd_reindex_event)

    sp = sub.add_parser("compact", help="compact the node databases")
    sp.set_defaults(fn=cmd_compact)

    sp = sub.add_parser("debug", help="dump node state for debugging")
    sp.add_argument("--output", default="")
    sp.add_argument("--rpc-laddr", default="", help="running node RPC to query")
    sp.set_defaults(fn=cmd_debug)

    sp = sub.add_parser("probe-upnp", help="probe for a UPnP gateway")
    sp.add_argument("--timeout", type=float, default=3.0)
    sp.set_defaults(fn=cmd_probe_upnp)

    sp = sub.add_parser("abci-server", help="run an example app behind a socket")
    sp.add_argument("--address", default="tcp://127.0.0.1:26658")
    sp.add_argument("--app", default="kvstore", choices=["kvstore", "counter"])
    sp.add_argument("--serial", action="store_true",
                    help="counter: enforce serial nonces")
    sp.add_argument("--db", default="", help="sqlite path for persistence")
    sp.add_argument("--snapshot-interval", type=int, default=0)
    sp.set_defaults(fn=cmd_abci_server)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
