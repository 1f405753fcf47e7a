"""Test configuration: force 8 virtual CPU devices, so that the chunk loop's
placement over several devices (tests/test_placed_chunks.py) is exercised
without TPU hardware. The environment is set before jax is imported; nothing
here may run a JAX op before the config updates below."""

import os

# TM_TPU_TEST_BACKEND=tpu keeps the session on the real chip (for the
# on-chip tests like test_pallas_tpu.py); default is the CPU's devices.
_KEEP_TPU = os.environ.get("TM_TPU_TEST_BACKEND") == "tpu"

# The env vars also reach the child processes tests spawn (e2e runner, node
# subprocesses), which therefore inherit the same CPU setup.
if not _KEEP_TPU:
    # Short-lived test processes must not race a background XLA warmup
    # compile at interpreter exit (C++ teardown abort); see crypto/batch.py.
    os.environ.setdefault("TM_TPU_SKIP_WARMUP", "1")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
if not _KEEP_TPU and (
        "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

import jax  # noqa: E402

# TMTPU_LOCKWITNESS=1 runs the WHOLE session under the lock-order witness
# (utils/lockwitness.py): every Lock/RLock created from here on records
# runtime acquisition-order edges. The two mesh scenario tests always run
# under it via lockwitness.witness(); this hook is the opt-in for full-
# suite sweeps.
from tendermint_tpu.utils import lockwitness  # noqa: E402

lockwitness.install_from_env()

# Tier split (the full suite crossed 7 min, dominated by subprocess e2e
# tests each paying a cold JAX import).
# `-m quick` runs the fast tier (<3 min); `-m slow` the process-heavy rest.
_SLOW_MODULES = {
    # subprocess / multi-node e2e
    "test_e2e_runner", "test_fastsync_recovery", "test_statesync",
    "test_observability", "test_p2p_node", "test_consensus",
    "test_remote_signer", "test_pallas_tpu", "test_adversarial",
    # kernel-bound: wide batches / fresh XLA shapes on the CPU
    "test_perf_gate", "test_sr25519_batch", "test_ed25519_batch",
    # exhaustive state-space exploration (spec/model.py)
    "test_spec_model",
    # subprocess crash-recovery matrix + real-kernel breaker re-probe
    "test_fault_matrix",
}


def pytest_configure(config):
    config.addinivalue_line("markers", "quick: fast in-process tier (<3 min)")
    config.addinivalue_line("markers", "slow: subprocess/e2e tier")
    config.addinivalue_line(
        "markers",
        "soak: long-running seeded soak scenarios (docs/SOAK.md); always "
        "implies slow, so tier-1's `-m 'not slow'` never picks one up")


def pytest_sessionfinish(session, exitstatus):
    # The session-wide witness sweep must actually VERDICT: any lock-order
    # cycle observed anywhere in the run fails the whole session.
    if lockwitness.WITNESS.enabled:
        cycles = lockwitness.WITNESS.cycles()
        if cycles or lockwitness.WITNESS.truncated:
            print("\nLOCKWITNESS: "
                  + (f"acquisition-order cycle {' -> '.join(cycles[0])}"
                     if cycles else
                     f"edge graph truncated at {lockwitness.MAX_EDGES}"),
                  f"(edges={len(lockwitness.WITNESS.edges)}, "
                  f"acquires={lockwitness.WITNESS.acquires})")
            session.exitstatus = 1


# Modules whose point is exercising the DEVICE kernels: pin the host/kernel
# crossover to 0 there so the C host verifier (ops/chost) cannot absorb the
# batches they mean to run through the kernel. Everything else keeps the
# production adaptive routing.
_KERNEL_PATH_MODULES = {
    "test_ed25519_batch", "test_sr25519_batch", "test_pallas_tpu",
    "test_perf_gate",
}


@pytest.fixture(autouse=True)
def _pin_kernel_path(request, monkeypatch):
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod in _KERNEL_PATH_MODULES:
        monkeypatch.setenv("TM_TPU_HOST_CROSSOVER", "0")


@pytest.fixture
def fake_tpu_host(monkeypatch):
    """-> fake(ndev, chunk): from then on the host code sees a TPU backend
    whose chips are the first `ndev` of the CPU's forced devices and whose
    Pallas chunk is `chunk` lanes -- the module attributes the routing
    reads, as tests replace `host_crossover`. The chunk programs are the
    caller's to stand in for; the key table's build, which every route
    behind a TPU backend launches for a key it has not met, is stood in for
    here by the jnp build. -> the devices."""
    def fake(ndev: int, chunk: int):
        from tendermint_tpu.ops import ed25519_batch as edb
        from tendermint_tpu.ops import ed25519_pallas as edp

        devices = jax.local_devices()[:ndev]
        assert len(devices) == ndev
        monkeypatch.setattr(edb, "_use_pallas", lambda: True)
        monkeypatch.setattr(edp, "CHUNK", chunk)
        monkeypatch.setattr(edp, "_build_comb_lanes", edb._build_comb_tables)
        monkeypatch.setattr(jax, "local_devices", lambda *a, **k: list(devices))
        monkeypatch.setattr(jax, "local_device_count", lambda *a, **k: ndev)
        monkeypatch.delenv("TM_TPU_SHARD", raising=False)
        return devices

    return fake


@pytest.fixture(autouse=True)
def _breaker_counts_stay_with_their_test():
    """The device breakers count failures for the life of the process, and a
    test that injects a device fault leaves its count behind: whatever reads
    the count later (the benchmark's `correct`, chip_smoke's end state) then
    fails or passes by which file ran before it in its worker. Put the
    counts back after every test; a module nobody imported is not imported."""
    import sys

    def breakers():
        return [m.BREAKER for m in (
            sys.modules.get("tendermint_tpu.ops.ed25519_batch"),
            sys.modules.get("tendermint_tpu.ops.sr25519_batch")) if m is not None]

    before = {id(b): (b.failures, b.trips, b.last_error) for b in breakers()}
    yield
    for b in breakers():
        b.failures, b.trips, b.last_error = before.get(id(b), (0, 0, None))


def pytest_collection_modifyitems(config, items):
    for item in items:
        # A soak-marked test is always slow-tier, whatever its module says.
        if item.get_closest_marker("soak"):
            if not item.get_closest_marker("slow"):
                item.add_marker(pytest.mark.slow)
            continue
        # An explicit @pytest.mark.quick/slow on the test wins over the
        # module default (a no-kernel gate in a kernel-heavy module can
        # opt into the quick tier).
        if item.get_closest_marker("quick") or item.get_closest_marker("slow"):
            continue
        mod = item.module.__name__.rsplit(".", 1)[-1]
        item.add_marker(pytest.mark.slow if mod in _SLOW_MODULES
                        else pytest.mark.quick)

if not _KEEP_TPU:
    # an exported JAX_PLATFORMS=tpu must not win over the CPU default
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    assert jax.default_backend() == "cpu" and len(jax.devices()) == 8
