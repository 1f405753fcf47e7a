"""Manifest-driven multi-process e2e testnet runner (reference: test/e2e/
runner/: stages setup/start/load/perturb/wait/test/stop; perturbations in
runner/perturb.go).

Each node is a REAL OS process (`python -m tendermint_tpu.cli start`) with
durable sqlite stores and a WAL, connected over real TCP — the in-process
harness can't prove crash recovery or process isolation; this can. A
manifest describes the topology and a perturbation schedule:

    Manifest(validators=4, target_height=12, load_txs=20,
             perturbations=[Perturbation(node=3, action="kill",
                                         at_height=5, revive_after_s=2)])

Actions (reference runner/perturb.go): kill (SIGKILL + restart),
restart (SIGTERM + restart), pause (SIGSTOP/SIGCONT), disconnect (SIGSTOP
without revive until revive_after_s).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request
from dataclasses import dataclass, field


# --- load-aware progress waiting (r4 verdict item 6) ------------------------
#
# Wall-clock deadlines flake on the 1-core CI host: any concurrent load
# stretches every stage uniformly, and a fixed budget ends up measuring the
# contention, not the testnet. These waits are PROGRESS-based instead: they
# fail only when the progress metric (a height) stalls for an idle budget
# that is scaled live by measured host contention (the bench's
# spin-calibration trick: a fixed CPU loop's elapsed time is the load
# factor). A hard cap bounds total runtime against genuine hangs.

_SPIN_BASELINE: float | None = None


def _spin_ms() -> float:
    t0 = time.monotonic()
    x = 0
    for i in range(400_000):
        x += i
    return (time.monotonic() - t0) * 1e3


def calibrate_spin() -> float:
    """Record (or improve) the quiet-host spin baseline."""
    global _SPIN_BASELINE
    best = min(_spin_ms() for _ in range(3))
    if _SPIN_BASELINE is None or best < _SPIN_BASELINE:
        _SPIN_BASELINE = best
    return _SPIN_BASELINE


def load_factor() -> float:
    if _SPIN_BASELINE is None:
        calibrate_spin()
    return min(max(_spin_ms() / _SPIN_BASELINE, 1.0), 8.0)


def wait_progress(value_fn, done_fn, idle_budget_s: float, hard_cap_s: float,
                  what: str, tick=None, poll_s: float = 0.3) -> None:
    """Wait until done_fn(value) holds. value_fn returns a monotonic
    progress metric; the wait fails only if the metric stalls for
    idle_budget_s * load_factor(), or after hard_cap_s total."""
    best = value_fn()
    start = last_progress = time.monotonic()
    while True:
        if tick is not None:
            tick()
        if done_fn(best):
            return
        now = time.monotonic()
        factor = load_factor()
        idle = idle_budget_s * factor
        if now - last_progress > idle:
            raise TimeoutError(
                f"{what}: no progress for {now - last_progress:.0f}s "
                f"(budget {idle:.0f}s at load factor {factor:.1f}); "
                f"value={best}")
        if now - start > hard_cap_s:
            raise TimeoutError(f"{what}: hard cap {hard_cap_s:.0f}s "
                               f"exceeded; value={best}")
        time.sleep(poll_s)
        v = value_fn()
        if v > best:
            best = v
            last_progress = time.monotonic()


@dataclass
class Perturbation:
    node: int
    action: str  # kill | restart | pause | partition | heal
    at_height: int
    revive_after_s: float = 1.0
    # partition only: groups of node INDICES, e.g. [[0, 1], [2, 3]];
    # omitted -> isolate `node` from everyone else. Installed symmetrically
    # on every running node via the unsafe_nemesis RPC and healed at
    # revive_after_s (or by an explicit heal perturbation).
    groups: list = field(default_factory=list)


@dataclass
class PowerChange:
    """A voting-power change driven through the app's ``val:`` tx (ABCI
    EndBlock validator_updates -> state/execution.py update_state): change
    validator `node`'s power to `power` once the net reaches `at_height`.
    The update lands in the validator set two heights after the tx commits."""

    node: int
    power: int
    at_height: int


@dataclass
class Manifest:
    """reference: test/e2e/pkg/manifest.go (subset)."""

    validators: int = 4
    chain_id: str = ""
    target_height: int = 10
    load_txs: int = 10
    starting_port: int = 0  # 0 -> pick a free range
    perturbations: list[Perturbation] = field(default_factory=list)
    power_changes: list[PowerChange] = field(default_factory=list)
    # Node index to run byzantine (reference: maverick nodes in e2e
    # manifests, pkg/manifest.go Misbehaviors), -1 = none. The byzantine
    # node runs `misbehavior` — any consensus/misbehavior.py behavior spec
    # (docs/BYZANTINE.md), rolled by the generator's behavior dimension —
    # via TMTPU_MISBEHAVIOR; honest >2/3 must keep committing (and, for
    # the double-vote behaviors, produce DuplicateVoteEvidence).
    byzantine_node: int = -1
    misbehavior: str = "double_prevote"
    # Fast-sync version for all nodes (reference: manifest fast_sync key).
    fastsync_version: str = "v0"
    # Add a post-start state-sync joiner node (reference: statesync nodes).
    statesync_joiner: bool = False
    # Clock-skew dimension (docs/SOAK.md): run `skewed_node`'s process with
    # TMTPU_CLOCK_SKEW_S=clock_skew_s so its entire time plane — proposal
    # timestamps, timeout ticker, evidence aging — is offset from the rest
    # of the net. BFT time (weighted median) must absorb a sub-1/3 skewed
    # voice: honest >2/3 keep committing and header times stay monotonic.
    # -1 = no skewed node.
    skewed_node: int = -1
    clock_skew_s: float = 0.0
    # Light-serving dimension (docs/LIGHT.md): after the perturbation
    # matrix settles, run this many concurrent light clients behind one
    # LightGateway over the net's real RPC and cross-check every VERIFIED
    # answer against the chain's committed block id. Refusals are fine
    # (refuse-over-lie is the gateway contract); a hash mismatch fails
    # the run. 0 = no light-serving stage.
    light_clients: int = 0

    @staticmethod
    def from_file(path: str) -> "Manifest":
        with open(path) as f:
            doc = json.load(f)
        perts = [Perturbation(**p) for p in doc.pop("perturbations", [])]
        powers = [PowerChange(**p) for p in doc.pop("power_changes", [])]
        return Manifest(perturbations=perts, power_changes=powers, **doc)


def _free_port_base(n_ports: int) -> int:
    """A base port such that [base, base+n_ports) all bind right now."""
    import random
    import socket

    rng = random.Random(os.getpid())
    for _ in range(50):
        base = rng.randrange(20000, 60000 - n_ports)
        socks = []
        try:
            for off in range(n_ports):
                s = socket.socket()
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", base + off))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port range found")


class Runner:
    """reference: test/e2e/runner/main.go stage driver."""

    def __init__(self, manifest: Manifest, workdir: str, logger=None):
        self.m = manifest
        self.workdir = os.path.abspath(workdir)
        self.logger = logger
        self.procs: dict[int, subprocess.Popen | None] = {}
        self._paused: set[int] = set()
        if not self.m.starting_port:
            self.m.starting_port = _free_port_base(2 * (self.m.validators + 1))
        self.rpc_addrs = {
            i: f"http://127.0.0.1:{self.m.starting_port + 2 * i + 1}"
            for i in range(self.m.validators)
        }

    # --- stages -------------------------------------------------------------

    def setup(self) -> None:
        calibrate_spin()  # quiet-host baseline before the net loads the box
        from tendermint_tpu.cli.main import main as cli

        rc = cli(["testnet", "--v", str(self.m.validators),
                  "--output", self.workdir,
                  "--chain-id", self.m.chain_id or "e2e-chain",
                  "--starting-port", str(self.m.starting_port)])
        if rc != 0:
            raise RuntimeError("testnet setup failed")
        # default_config already uses the durable sqlite backend, so
        # kill/restart exercises real recovery; nothing to patch.
        from tendermint_tpu.config.config import default_config
        from tendermint_tpu.config.toml import (
            load_toml_into, write_config_toml)

        for i in range(self.m.validators):
            home = os.path.join(self.workdir, f"node{i}")
            path = os.path.join(home, "config", "config.toml")
            cfg = load_toml_into(default_config().set_root(home), path)
            cfg.fastsync.version = self.m.fastsync_version
            # localhost chaos harness: the partition/heal perturbations
            # drive each node's nemesis plane over the unsafe RPC route
            cfg.rpc.unsafe = True
            write_config_toml(cfg, path)

    def _spawn(self, i: int) -> subprocess.Popen:
        # JAX_PLATFORMS=cpu: a chip belongs to one process at a time, so N
        # node processes cannot share it (and this parent may hold it)
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "TM_TPU_DISABLE_BATCH": os.environ.get("TM_TPU_DISABLE_BATCH", ""),
               # serving nodes take app snapshots so late joiners can
               # state-sync in (reference e2e: snapshot_interval manifest key)
               "TMTPU_KVSTORE_SNAPSHOT_INTERVAL":
                   os.environ.get("TMTPU_KVSTORE_SNAPSHOT_INTERVAL", "4")}
        if i == self.m.byzantine_node:
            env["TMTPU_MISBEHAVIOR"] = self.m.misbehavior
        if i == self.m.skewed_node and self.m.clock_skew_s:
            env["TMTPU_CLOCK_SKEW_S"] = str(self.m.clock_skew_s)
        log = open(os.path.join(self.workdir, f"node{i}.log"), "ab")
        return subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu.cli",
             "--home", os.path.join(self.workdir, f"node{i}"), "start"],
            stdout=log, stderr=log, env=env)

    def start(self) -> None:
        for i in range(self.m.validators):
            self.procs[i] = self._spawn(i)

    def _load_targets(self) -> list[int]:
        """Round-robin universe for client traffic: every node with an RPC
        address, INCLUDING post-start joiners (a statesync-joined node that
        never receives client load is a dead weight the old
        `attempt % validators` cursor silently created)."""
        return sorted(self.rpc_addrs)

    def load(self) -> None:
        """Submit load_txs round-robin over the nodes' RPC (reference:
        runner/load.go)."""
        sent = 0
        attempt = 0
        deadline = time.monotonic() + 60
        while sent < self.m.load_txs and time.monotonic() < deadline:
            targets = self._load_targets()
            node = targets[attempt % len(targets)]
            attempt += 1
            if node in self._paused or self.procs.get(node) is None:
                if attempt % len(targets) == 0:
                    time.sleep(0.05)  # every node skipped: don't spin hot
                continue
            tx = b"e2e%d=v%d" % (sent, sent)
            try:
                self._rpc(node, "broadcast_tx_sync",
                          {"tx": __import__("base64").b64encode(tx).decode()})
                sent += 1
            except Exception:  # noqa: BLE001 - node may still be booting
                time.sleep(0.3)

    def load_report(self, window_s: float = 20.0) -> dict:
        """Timed load window -> throughput report (reference:
        test/loadtime/ + the QA tables in docs/qa/v034/README.md; the
        anchors there: 19.5 blocks/min, ~200-339 tx/s on 200 4-core
        droplets — this is a 1-core localnet, so the numbers are recorded
        for trend, not for parity with that hardware).

        Returns {window_s, blocks, blocks_per_min, txs_committed, tx_per_s,
        first_height, last_height}."""
        import base64

        start_h = self.max_height()
        deadline = time.monotonic() + window_s
        sent = 0
        attempt = 0  # round-robin cursor: advances even past dead/erroring
        while time.monotonic() < deadline:  # nodes, so one sick node can't
            targets = self._load_targets()  # pin the whole window
            node = targets[attempt % len(targets)]
            attempt += 1
            if node in self._paused or self.procs.get(node) is None:
                if attempt % len(targets) == 0:
                    time.sleep(0.05)  # every node skipped: don't spin hot
                continue
            tx = b"load%d=v%d" % (sent, sent)
            try:
                self._rpc(node, "broadcast_tx_sync",
                          {"tx": base64.b64encode(tx).decode()})
                sent += 1
            except Exception:  # noqa: BLE001
                time.sleep(0.2)
        end_h = self.max_height()
        txs = 0
        for h in range(start_h + 1, end_h + 1):
            try:
                b = self._rpc(0, "block", {"height": str(h)})
                txs += len(b["block"]["data"]["txs"] or [])
            except Exception:  # noqa: BLE001
                continue
        blocks = end_h - start_h
        return dict(window_s=window_s, blocks=blocks,
                    blocks_per_min=round(blocks * 60.0 / window_s, 1),
                    txs_sent=sent, txs_committed=txs,
                    tx_per_s=round(txs / window_s, 1),
                    first_height=start_h, last_height=end_h)

    def _progress_wait(self, value_fn, done_fn, idle_budget_s: float,
                       hard_cap_s: float, what: str, tick=None) -> None:
        wait_progress(value_fn, done_fn, idle_budget_s, hard_cap_s, what,
                      tick=tick)

    def perturb_and_wait(self, timeout_s: float = 180.0) -> None:
        """Run the perturbation schedule while waiting for target_height
        (reference: runner/perturb.go + wait.go). timeout_s is the IDLE
        budget basis: the wait fails on a height stall of timeout_s/3
        (load-scaled), or a hard cap of 4x timeout_s."""
        pending = sorted(self.m.perturbations, key=lambda p: p.at_height)
        powers = sorted(self.m.power_changes, key=lambda p: p.at_height)
        revive_at: list[tuple[float, int, str]] = []

        def tick():
            h = self.max_height()
            while pending and h >= pending[0].at_height:
                self._apply(pending.pop(0), revive_at)
            while powers and h >= powers[0].at_height:
                self._apply_power_change(powers.pop(0))
            now = time.monotonic()
            for t, node, action in list(revive_at):
                if now >= t:
                    revive_at.remove((t, node, action))
                    self._revive(node, action)

        self._progress_wait(
            self.max_height,
            lambda h: (h >= self.m.target_height and not pending
                       and not powers and not revive_at),
            idle_budget_s=timeout_s / 3.0, hard_cap_s=timeout_s * 4.0,
            what=f"testnet reaching height {self.m.target_height}",
            tick=tick)

    def _apply(self, p: Perturbation, revive_at: list) -> None:
        if p.action == "partition":
            groups = p.groups or [[p.node],
                                  [i for i in range(self.m.validators)
                                   if i != p.node]]
            self.partition(groups)
            revive_at.append((time.monotonic() + p.revive_after_s,
                              p.node, p.action))
            return
        if p.action == "heal":
            self.heal()
            return
        proc = self.procs.get(p.node)
        if proc is None:
            return
        if p.action == "kill":
            proc.kill()
            proc.wait()
            self.procs[p.node] = None
        elif p.action == "restart":
            proc.terminate()
            proc.wait()
            self.procs[p.node] = None
        elif p.action == "pause":
            proc.send_signal(signal.SIGSTOP)
            self._paused.add(p.node)
        revive_at.append((time.monotonic() + p.revive_after_s, p.node, p.action))

    def _apply_power_change(self, pc: PowerChange) -> None:
        """Broadcast the app's ``val:`` tx changing validator `pc.node`'s
        power (pubkey from the shared genesis doc). Best effort over every
        reachable node: a power change racing a perturbation must not kill
        the schedule."""
        import base64

        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.types.genesis import GenesisDoc

        gen = GenesisDoc.from_file(
            os.path.join(self.workdir, "node0", "config", "genesis.json"))
        if not 0 <= pc.node < len(gen.validators):
            return
        pub = gen.validators[pc.node].pub_key
        tx = KVStoreApplication.make_val_tx(pub.bytes(), pc.power)
        for i in self._load_targets():
            if i in self._paused or self.procs.get(i) is None:
                continue
            try:
                self._rpc(i, "broadcast_tx_sync",
                          {"tx": base64.b64encode(tx).decode()})
                return
            except Exception:  # noqa: BLE001 - next node
                continue

    def _revive(self, node: int, action: str) -> None:
        if action in ("kill", "restart"):
            self.procs[node] = self._spawn(node)
        elif action == "pause":
            self.procs[node].send_signal(signal.SIGCONT)
            self._paused.discard(node)
        elif action == "partition":
            self.heal()

    # --- nemesis control (reference: runner/perturb.go drives docker
    # network disconnects; here each node's link plane over unsafe RPC) ----

    def node_ids(self) -> dict[int, str]:
        """node index -> p2p node id, from each node's status RPC."""
        ids = {}
        for i in list(self.rpc_addrs):
            try:
                st = self._rpc(i, "status", {})
                ids[i] = st["node_info"]["id"]
            except Exception:  # noqa: BLE001 - dead/paused node
                continue
        return ids

    def _nemesis_all(self, params: dict) -> None:
        """Install the same nemesis command on every reachable node — a
        partition is a property of the NETWORK, so every member must agree
        on the cut for it to be symmetric."""
        for i in list(self.rpc_addrs):
            if i in self._paused or self.procs.get(i) is None:
                continue
            try:
                self._rpc(i, "unsafe_nemesis", params)
            except Exception:  # noqa: BLE001 - a dead node needs no cut
                continue

    def partition(self, groups: list) -> None:
        """Cut the network into groups of node INDICES (e.g. [[0,1],[2,3]]):
        messages and dials between different groups are dropped on every
        node until heal()."""
        ids = self.node_ids()
        id_groups = [[ids[i] for i in g if i in ids] for g in groups]
        id_groups = [g for g in id_groups if g]
        self._nemesis_all({"partition": id_groups})

    def heal(self) -> None:
        """Remove the partition on every node (persistent-peer backoff is
        kicked node-side so links re-establish promptly)."""
        self._nemesis_all({"heal": True})

    # --- checks (reference: test/e2e/tests/) --------------------------------

    def max_height(self) -> int:
        best = 0
        for i in list(self.rpc_addrs):
            try:
                st = self._rpc(i, "status", {})
                best = max(best, int(st["sync_info"]["latest_block_height"]))
            except Exception:  # noqa: BLE001
                continue
        return best

    def assert_consistent(self, height: int) -> None:
        """All reachable nodes agree on the block hash at `height`."""
        hashes = {}
        for i in list(self.rpc_addrs):
            try:
                b = self._rpc(i, "block", {"height": str(height)})
                hashes[i] = b["block_id"]["hash"]
            except Exception:  # noqa: BLE001
                continue
        assert len(hashes) >= 2, f"too few reachable nodes: {hashes}"
        assert len(set(hashes.values())) == 1, f"fork detected: {hashes}"

    def audit_agreement(self, min_height: int = 1) -> int:
        """The BFT safety audit: block-hash agreement across EVERY
        committed height on all reachable nodes, not one sampled height —
        a fork at any height anywhere is a safety violation the
        single-height check can miss (nodes can agree at h and have forked
        at h-3). A node that hasn't committed a height yet simply doesn't
        vote for it. Returns the number of heights audited; raises
        AssertionError with the full per-node hash map on any fork."""
        max_h = self.max_height()
        audited = 0
        for h in range(min_height, max_h + 1):
            hashes = {}
            for i in list(self.rpc_addrs):
                try:
                    b = self._rpc(i, "block", {"height": str(h)})
                    hashes[i] = b["block_id"]["hash"]
                except Exception:  # noqa: BLE001 - not committed there yet
                    continue
            if len(hashes) >= 2:
                audited += 1
                assert len(set(hashes.values())) == 1, (
                    f"fork at height {h}: {hashes}")
        assert audited >= 1, f"no height auditable across nodes (max {max_h})"
        return audited

    def min_height(self) -> int:
        """Lowest latest-height over the reachable nodes (−1: none)."""
        worst = None
        for i in list(self.rpc_addrs):
            try:
                st = self._rpc(i, "status", {})
                h = int(st["sync_info"]["latest_block_height"])
                worst = h if worst is None else min(worst, h)
            except Exception:  # noqa: BLE001
                continue
        return -1 if worst is None else worst

    def assert_liveness(self, delta: int = 2, within_s: float = 30.0) -> None:
        """Post-heal liveness bound: every node catches up to within
        `delta` heights of the max height within `within_s` (load-scaled
        idle budget; hard cap 4x)."""
        self._progress_wait(
            self.min_height,
            lambda _h: self.min_height() >= self.max_height() - delta,
            idle_budget_s=within_s, hard_cap_s=within_s * 4.0,
            what=f"all nodes within {delta} heights of the tip")

    def light_crowd_report(self, n_clients: int,
                           queries_each: int = 6) -> dict:
        """``n_clients`` concurrent light clients behind one LightGateway
        over the net's real RPC (docs/LIGHT.md): node0 is the primary,
        the other reachable nodes witnesses/spares, the trust anchor is
        the earliest still-in-trust-period header. Each client hammers
        seeded height queries; every VERIFIED answer is cross-checked
        against the committed block id node0 reports. Refusals are
        acceptable — a mismatch means the gateway served a wrong answer
        and fails the run."""
        import random
        import threading

        from tendermint_tpu.light.client import TrustOptions
        from tendermint_tpu.light.gateway import LightGateway
        from tendermint_tpu.light.provider import HTTPProvider
        from tendermint_tpu.light.store import DBStore
        from tendermint_tpu.light.verifier import header_expired
        from tendermint_tpu.store.db import MemDB
        from tendermint_tpu.types.ttime import Time

        chain_id = self.m.chain_id or "e2e-chain"
        alive = []
        for i in sorted(self.rpc_addrs):
            try:
                self._rpc(i, "status", {})
            except Exception:  # noqa: BLE001 - a down node can't serve
                continue
            alive.append(i)
        assert alive, "no reachable RPC node to serve light clients"
        alive = alive[:4]
        providers = [HTTPProvider(chain_id, self.rpc_addrs[i])
                     for i in alive]
        period_s = 168 * 3600
        anchor = providers[0].light_block(0)
        now = Time.now()
        for h in range(1, min(anchor.height, 17)):
            lb = providers[0].light_block(h)
            if not header_expired(lb.signed_header, period_s, now):
                anchor = lb
                break
        gw = LightGateway(
            chain_id,
            TrustOptions(period_s=period_s, height=anchor.height,
                         hash=anchor.hash()),
            providers, DBStore(MemDB(), chain_id),
            provider_names=[f"node{i}" for i in alive])
        tip = max(self.max_height(), 1)
        stats = {"clients": n_clients, "queries": 0, "served": 0,
                 "refused": 0, "mismatches": []}
        mtx = threading.Lock()

        def client(c: int) -> None:
            rng = random.Random(f"light:{self.m.chain_id}:{c}")
            for _ in range(queries_each):
                height = rng.randint(1, tip)
                try:
                    lb, _verdict = gw.serve_light_block(height)
                except Exception:  # noqa: BLE001 - typed refusal, not a lie
                    with mtx:
                        stats["queries"] += 1
                        stats["refused"] += 1
                    continue
                try:
                    want = self._rpc(alive[0], "block",
                                     {"height": str(lb.height)})
                    want_hash = want["block_id"]["hash"].lower()
                except Exception:  # noqa: BLE001 - chain check unavailable
                    want_hash = None
                with mtx:
                    stats["queries"] += 1
                    stats["served"] += 1
                    if (want_hash is not None
                            and lb.hash().hex().lower() != want_hash):
                        stats["mismatches"].append(lb.height)

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(n_clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60.0)
        assert stats["served"] > 0, f"crowd never got an answer: {stats}"
        assert not stats["mismatches"], (
            f"gateway served wrong answers at heights {stats['mismatches']}")
        stats["gateway"] = gw.describe()["counters"]
        return stats

    def join_statesync_node(self, timeout_s: float = 120.0) -> int:
        """Spawn a NEW non-validator node that joins the live net via state
        sync (snapshot bootstrap + light-client trust through node0's RPC),
        then fast-syncs to the tip (reference: test/e2e 'stateSync' node
        perturbation). Returns the joiner's node index."""
        import shutil

        from tendermint_tpu.cli.main import _ensure_dirs, default_config
        from tendermint_tpu.config.toml import write_config_toml

        idx = self.m.validators  # next slot
        home = os.path.join(self.workdir, f"node{idx}")
        _ensure_dirs(home)
        # same genesis as the net
        shutil.copy(os.path.join(self.workdir, "node0", "config", "genesis.json"),
                    os.path.join(home, "config", "genesis.json"))
        # trust anchor from node0 (height 2 hash via RPC)
        meta = self._rpc(0, "block", {"height": "2"})
        trust_hash = meta["block_id"]["hash"]

        cfg = default_config().set_root(home)
        base_port = self.m.starting_port + 2 * idx
        cfg.p2p.laddr = f"tcp://127.0.0.1:{base_port}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{base_port + 1}"
        cfg.p2p.pex = False
        peers = []
        for i in range(self.m.validators):
            try:
                st = self._rpc(i, "status", {})
                peers.append(f"{st['node_info']['id']}@127.0.0.1:"
                             f"{self.m.starting_port + 2 * i}")
            except Exception:  # noqa: BLE001
                continue
        cfg.p2p.persistent_peers = ",".join(peers)
        cfg.base.fast_sync_mode = True
        cfg.statesync.enable = True
        cfg.statesync.rpc_servers = (self.rpc_addrs[0],)
        cfg.statesync.trust_height = 2
        cfg.statesync.trust_hash = trust_hash.lower()
        cfg.statesync.trust_period_s = 10 * 365 * 24 * 3600.0
        cfg.statesync.discovery_time_s = 1.0
        write_config_toml(cfg, os.path.join(home, "config", "config.toml"))

        self.rpc_addrs[idx] = f"http://127.0.0.1:{base_port + 1}"
        self.procs[idx] = self._spawn(idx)

        def joiner_height() -> int:
            try:
                st = self._rpc(idx, "status", {})
                return int(st["sync_info"]["latest_block_height"])
            except Exception:  # noqa: BLE001
                return -1

        def synced(_h) -> bool:
            try:
                st = self._rpc(idx, "status", {})
                return (int(st["sync_info"]["latest_block_height"])
                        >= self.m.target_height
                        and int(st["sync_info"]["earliest_block_height"]) > 1)
            except Exception:  # noqa: BLE001
                return False

        try:
            # idle basis timeout_s/2: the joiner pays a cold JAX import
            # before its RPC even answers (first "progress" is -1 -> 0),
            # which the load factor stretches on a contended host
            self._progress_wait(joiner_height, synced,
                                idle_budget_s=timeout_s / 2.0,
                                hard_cap_s=timeout_s * 4.0,
                                what="state-sync joiner reaching the tip")
            return idx
        except TimeoutError as e:
            timeout_msg = str(e)
        tail = ""
        try:
            with open(os.path.join(self.workdir, f"node{idx}.log"), "rb") as fh:
                fh.seek(0, os.SEEK_END)
                fh.seek(max(0, fh.tell() - 4096))
                raw = fh.read().decode("utf-8", "replace")
            tail = "\n".join(raw.splitlines()[-12:])
        except OSError:
            pass
        raise TimeoutError(
            f"joined node never state-synced to the tip ({timeout_msg}); "
            "joiner log tail:\n" + tail)

    def stop(self) -> None:
        for i, proc in self.procs.items():
            if proc is None:
                continue
            if i in self._paused:
                proc.send_signal(signal.SIGCONT)
            proc.terminate()
        for proc in self.procs.values():
            if proc is not None:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()

    def _rpc(self, node: int, method: str, params: dict):
        body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                           "params": params}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                self.rpc_addrs[node], data=body,
                headers={"Content-Type": "application/json"}), timeout=5) as r:
            doc = json.loads(r.read())
        if doc.get("error"):
            raise RuntimeError(doc["error"])
        return doc["result"]


def run_manifest(manifest: Manifest, workdir: str,
                 with_load_report: bool = False) -> dict:
    """All stages end to end (reference: runner/main.go). Returns a report
    dict (throughput numbers when with_load_report)."""
    r = Runner(manifest, workdir)
    r.setup()
    r.start()
    report: dict = {}
    try:
        r.load()
        r.perturb_and_wait()
        # full-prefix safety audit: every crash/pause/partition matrix run
        # gets fork detection at EVERY committed height, not one sample
        audited = r.audit_agreement()
        if with_load_report:
            report = r.load_report()
        report["heights_audited"] = audited
        if manifest.light_clients:
            report["light"] = r.light_crowd_report(manifest.light_clients)
        if manifest.statesync_joiner:
            report["joiner_index"] = r.join_statesync_node()
    finally:
        r.stop()
    return report
