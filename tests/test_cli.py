"""Operator CLI: init a home dir, run a node from it, then drive the
maintenance commands (replay, reindex-event, compact, debug, light --once)
against the produced chain (reference: cmd/tendermint/commands/)."""

import json
import os
import time

from tendermint_tpu.cli.main import main as cli


def _wait(cond, timeout, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def test_cli_lifecycle(tmp_path, capsys):
    home = str(tmp_path / "home")
    assert cli(["--home", home, "init", "--chain-id", "cli-chain"]) == 0
    assert os.path.exists(f"{home}/config/genesis.json")
    assert cli(["--home", home, "show-node-id"]) == 0
    assert cli(["--home", home, "show-validator"]) == 0
    assert cli(["--home", home, "version"]) == 0
    capsys.readouterr()

    # run a real node from the CLI home (in-process; `start` blocks, so wire
    # the Node directly like cmd_start does)
    from tendermint_tpu.cli.main import _load_config
    from tendermint_tpu.node.node import Node

    cfg = _load_config(home)
    cfg.base.db_backend = "sqlite"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.wal_path = os.path.join(home, "data", "cs.wal")
    node = Node(cfg)
    node.start()
    try:
        node.mempool.check_tx(b"cli=works")
        assert _wait(lambda: node.block_store.height >= 3, 60)
        rpc_addr = node.rpc_server.laddr

        # light --once against the running node
        meta = node.block_store.load_block_meta(1)
        assert cli(["--home", str(tmp_path / "lighthome"), "light", "cli-chain",
                    "--primary", "http://" + rpc_addr.split("://", 1)[1],
                    "--trusted-height", "1",
                    "--trusted-hash", meta.block_id.hash.hex(),
                    "--trust-period", str(10 * 365 * 24 * 3600.0),
                    "--once"]) == 0
        out = capsys.readouterr().out
        assert "verified height" in out or "Light client running" in out

        # debug against the running node
        assert cli(["--home", home, "debug", "--rpc-laddr", rpc_addr,
                    "--output", str(tmp_path / "dbg")]) == 0
        doc = json.load(open(tmp_path / "dbg" / "dump.json"))
        assert int(doc["status"]["sync_info"]["latest_block_height"]) >= 1
        assert doc["block_store"]["height"] >= 1
    finally:
        node.stop()
    time.sleep(0.3)  # let sqlite handles settle

    # offline maintenance on the same home
    assert cli(["--home", home, "replay"]) == 0
    out = capsys.readouterr().out
    assert "Replayed to height" in out

    assert cli(["--home", home, "reindex-event"]) == 0
    out = capsys.readouterr().out
    assert "Reindexed heights" in out

    assert cli(["--home", home, "compact"]) == 0
    out = capsys.readouterr().out
    assert "compacted" in out

    assert cli(["--home", home, "rollback"]) == 0
    out = capsys.readouterr().out
    assert "Rolled back state to height" in out


def test_light_sequential_flag_reaches_the_client(tmp_path, monkeypatch):
    """`light --sequential` (reference cmd/tendermint/commands/light.go)
    builds the client in sequential mode; without it, skipping."""
    import pytest

    from tendermint_tpu import light

    built = []

    class Stop(Exception):
        pass

    def fake_client(*_a, **kw):
        built.append(kw["verification_mode"])
        raise Stop

    monkeypatch.setattr(light, "Client", fake_client)
    base = ["--home", str(tmp_path / "home"), "light", "some-chain",
            "-p", "http://127.0.0.1:1", "--trusted-height", "1",
            "--trusted-hash", "ab" * 32, "--once"]
    for extra in (["--sequential"], []):
        with pytest.raises(Stop):
            cli(base + extra)
    assert built == [light.SEQUENTIAL, light.SKIPPING]
