"""Crash-recovery matrix over the NAMED fault sites of the deterministic
chaos layer (tendermint_tpu/utils/faults.py), plus the real-kernel circuit
breaker re-probe.

Each matrix case boots a real single-validator node subprocess
(tests/crash_node.py) with TMTPU_FAULTS pinning one fault at one site (fixed
seed -> fully replayable interleaving), asserts the injected fault actually
killed the process, restarts fault-free, and asserts the recovered node
CONVERGES TO THE FAULT-FREE APP HASH: both runs apply the same fixed tx
universe exactly once (the kvstore app hash is the big-endian applied-tx
count, and crash_node's committed-tx scan + the mempool's committed-tx cache
make re-feeding idempotent), so hash equality is an exact end-state check,
not just internal consistency.

The legacy TMTPU_FAIL_INDEX matrix (tests/test_fastsync_recovery.py) keeps
covering the five finalize sites positionally; this matrix exercises the
named-site layer, the WAL torn/partial-frame writer, and the store-write
crash sites it adds."""

import json
import os
import subprocess
import sys
import time

import pytest

from tendermint_tpu.utils import faults

N_TXS = 5
TARGET_H = 6
FAULT_FREE_APP_HASH = (N_TXS).to_bytes(8, "big").hex()

# Crash-class matrix: every site where a hard crash (or a torn write that
# ends in one) must leave a recoverable tree. @N triggers make each run
# deterministic; the seed fixes torn-frame cut points.
CRASH_MATRIX = [
    "wal.write:torn@12",
    "wal.write:partial@12",
    "wal.fsync:crash@6",
    "store.block.save:crash@3",
    "store.state.save:crash@3",
    "consensus.finalize.save_block:crash@3",
    "consensus.finalize.apply_block:crash@3",
]

# Sites whose failure mode is degradation rather than crash-recovery, with
# the test that owns each (see test_every_site_is_covered).
DEGRADE_SITES = {
    "ops.ed25519.device": "test_faults.py breaker smoke + real-kernel test here",
    "ops.sr25519.device": "test_faults.py sr25519 breaker smoke",
    "ops.ed25519.probe": "probe-owned twin site (keeps device-site hit "
                         "indices deterministic); real-kernel test here",
    "ops.sr25519.probe": "sr25519 probe twin",
    "p2p.send": "faults registry drop determinism (chaos knob for e2e)",
    "p2p.recv": "disconnect action unit test (chaos knob for e2e)",
    "p2p.dial": "reconnect backoff schedule test (chaos knob for e2e)",
    "abci.call": "chaos knob for socket-app runs (in-proc apps bypass it)",
    "mempool.ingest": "batched-CheckTx degradation to the serial loop "
                      "(test_ingest.py + __graft_entry__.ingest_stage)",
    "consensus.finalize.end_height": "legacy TMTPU_FAIL_INDEX matrix "
                                     "(test_fastsync_recovery.py)",
    "consensus.finalize.prune": "legacy TMTPU_FAIL_INDEX matrix",
    "consensus.finalize.done": "legacy TMTPU_FAIL_INDEX matrix",
    # the self-healing storage plane (docs/DURABILITY.md): bit-rot at the
    # record-read sites degrades to quarantine + peer-assisted repair, not
    # crash-recovery — owned by the durability matrix
    "store.block.load": "test_durability.py detect/quarantine/repair matrix "
                        "+ __graft_entry__.durability_stage",
    "store.state.load": "test_durability.py state rebuild-from-blockstore",
    "store.evidence.load": "test_durability.py evidence quarantine-is-repair",
    "store.txindex.load": "test_durability.py reindex-from-stores",
}


def _crash_node(root, mode, env_extra, timeout=240):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("TMTPU_FAULTS", "TMTPU_FAULT_SEED", "TMTPU_FAIL_INDEX"):
        env.pop(k, None)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "crash_node.py"),
         root, mode, str(TARGET_H), str(N_TXS)],
        env=env, capture_output=True, timeout=timeout)


def _doc(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _assert_converged(doc):
    assert doc["app_size"] == N_TXS, doc
    assert doc["app_hash"] == FAULT_FREE_APP_HASH, doc
    assert doc["height"] >= TARGET_H, doc
    assert doc["state_height"] == doc["height"], doc
    assert doc["app_height"] == doc["height"], doc
    assert doc["app_hash"] == doc["state_app_hash"], doc


def test_every_site_is_covered():
    """The matrix enumerates every registered fault site: a new site must be
    consciously added to the crash matrix or the degradation list."""
    covered = {s.split(":")[0] for s in CRASH_MATRIX} | set(DEGRADE_SITES)
    assert covered == set(faults.CANONICAL_SITES), (
        covered ^ set(faults.CANONICAL_SITES))


def test_fault_free_baseline(tmp_path):
    """The fault-free run converges to the analytic app hash (tx count);
    every matrix case below must land on the same hash after recovery."""
    r = _crash_node(str(tmp_path / "clean"), "recover", {})
    assert r.returncode == 0, r.stderr[-2000:]
    _assert_converged(_doc(r))


@pytest.mark.parametrize("spec", CRASH_MATRIX)
def test_named_site_crash_recovery(tmp_path, spec):
    root = str(tmp_path / spec.replace(":", "_").replace("@", "_"))
    crash = _crash_node(root, "crash",
                        {"TMTPU_FAULTS": spec, "TMTPU_FAULT_SEED": "1234"})
    assert crash.returncode == 1, (spec, crash.returncode, crash.stderr[-500:])

    recover = _crash_node(root, "recover", {})
    assert recover.returncode == 0, (spec, recover.stderr[-2000:])
    _assert_converged(_doc(recover))


def test_torn_write_plus_dead_device_acceptance(tmp_path):
    """The ISSUE acceptance scenario: with a fixed fault seed, a WAL
    torn-write plus a persistently failing batch-verifier device during a
    multi-height run. The crash run dies at the torn frame; the recovery
    run keeps the device fault active the whole time -- the node must
    recover to the fault-free app hash with the circuit breaker open,
    committing every height via the host fallback."""
    root = str(tmp_path / "combined")
    # batching on (TM_TPU_DISABLE_BATCH=0 preempts crash_node's setdefault),
    # every batch forced toward the device, breaker cooldown longer than the
    # run so no probe closes the circuit mid-test. The device rule has no
    # trigger suffix: EVERY dispatch fails, so nothing ever compiles XLA.
    knobs = {
        "TM_TPU_DISABLE_BATCH": "0",
        "TM_TPU_SKIP_WARMUP": "1",
        "TM_TPU_BATCH_MIN": "1",
        "TM_TPU_HOST_CROSSOVER": "0",
        "TM_TPU_BREAKER_COOLDOWN_S": "300",
        "TMTPU_FAULT_SEED": "1234",
    }
    crash = _crash_node(root, "crash", {
        **knobs, "TMTPU_FAULTS": "wal.write:torn@12,ops.ed25519.device:raise"})
    assert crash.returncode == 1, (crash.returncode, crash.stderr[-500:])

    recover = _crash_node(root, "recover", {
        **knobs, "TMTPU_FAULTS": "ops.ed25519.device:raise"})
    assert recover.returncode == 0, recover.stderr[-2000:]
    doc = _doc(recover)
    _assert_converged(doc)
    # the accelerator was dead the whole run: the breaker tripped and every
    # verified commit went through the host fallback
    assert doc.get("breaker_trips", 0) >= 1, doc
    assert doc.get("breaker_open") is True, doc


def test_device_breaker_recloses_with_real_kernel(monkeypatch):
    """Slow-tier twin of the quick breaker smoke: the background probe runs
    the REAL device route (jnp kernel on the CPU) and re-closes the
    circuit; the next batch verifies on the device again."""
    from tendermint_tpu.crypto import ed25519 as ref
    from tendermint_tpu.ops import ed25519_batch as edb

    monkeypatch.setenv("TM_TPU_HOST_CROSSOVER", "0")
    monkeypatch.setenv("TM_TPU_BREAKER_COOLDOWN_S", "0.2")
    priv = ref.gen_priv_key(b"\x33" * 32)
    pub = priv.pub_key().data
    items = [(pub, b"k%d" % i, ref.sign(priv.data, b"k%d" % i))
             for i in range(8)]
    items.append((pub, b"forged", b"\x01" * 64))
    expect = [True] * 8 + [False]

    edb.BREAKER.reset()
    faults.configure(["ops.ed25519.device:raise@1"], seed=99)
    try:
        assert edb.verify_batch(items).tolist() == expect  # host fallback
        assert edb.BREAKER.is_open
        # wait for the real probe (compiles the kernel once) to re-close
        deadline = time.monotonic() + 600
        while edb.BREAKER.is_open and time.monotonic() < deadline:
            edb.verify_batch(items[:1])  # keeps kicking allow()
            time.sleep(0.25)
        assert not edb.BREAKER.is_open, "probe never re-closed the circuit"
        # device route live again, accept/reject still byte-identical
        assert edb.verify_batch(items).tolist() == expect
        assert not edb.BREAKER.is_open and edb.BREAKER.trips == 1
    finally:
        faults.clear()
        edb.BREAKER.reset()
