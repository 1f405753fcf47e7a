"""chip_smoke.py's plumbing, on the CPU: it refuses a non-TPU backend, its
phase functions run at a tiny size when called directly (chip budget is not
for typos), and a breaker that recorded a failure fails the end state.
What only a chip can show (device output, the Pallas lowering, the warm-up's
success) is behind ``on_chip`` and runs through the chip tool."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def clean_breakers(monkeypatch):
    """The breakers' lifetime counters are process-wide; earlier tests
    inject device failures on purpose."""
    from tendermint_tpu.ops import ed25519_batch, sr25519_batch

    for mod in (ed25519_batch, sr25519_batch):
        mod.BREAKER.reset()
        monkeypatch.setattr(mod.BREAKER, "failures", 0)
        monkeypatch.setattr(mod.BREAKER, "trips", 0)
    yield
    for mod in (ed25519_batch, sr25519_batch):
        mod.BREAKER.reset()


def test_refuses_non_tpu_backend():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--seed", "5"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert "refusing to run" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line
    assert '"platform": "cpu"' in proc.stdout  # but it says what it found


def test_phases_run_tiny_on_cpu(clean_breakers):
    out = chip_smoke.phase_commit(5, n_vals=24, on_chip=False, warm_calls=1)
    kinds = " ".join(out["corruptions"].values())
    for kind in ("flipped signature bit", "S >= L", "truncated signature",
                 "off-curve pubkey"):
        assert kind in kinds
    assert out["serial_reference_sigs"] == 24
    assert out["first_bad_index"] == min(map(int, out["corruptions"]))

    out = chip_smoke.phase_fastsync(5, n_ed=5, n_sr=2, n_blocks=3,
                                    on_chip=False)
    assert 2 <= out["rejected"]["height"] <= 3 and len(out["app_hash"]) == 64

    out = chip_smoke.phase_node(5, heights=2, n_txs=2, on_chip=False)
    assert out["txs"] == 2 and out["height"] >= 2

    out = chip_smoke.phase_end_state()
    assert out["chost"] and out["chash"]
    assert set(out["calibration"]) == {"crossover", "floor_ms", "host_us"}


def test_breaker_failure_fails_the_end_state(clean_breakers, monkeypatch):
    """What TMTPU_FAULTS="ops.ed25519.device:raise" does to a run on the
    chip: the host answers correctly, and the smoke still fails."""
    from tendermint_tpu.crypto import ed25519 as ref
    from tendermint_tpu.ops import ed25519_batch as edb
    from tendermint_tpu.utils import faults

    monkeypatch.setenv("TM_TPU_HOST_CROSSOVER", "0")  # the device route
    monkeypatch.setattr(edb.BREAKER, "probe", None)
    priv = ref.gen_priv_key(b"\x33" * 32)
    items = [(priv.pub_key().data, b"m", ref.sign(priv.data, b"m"))]
    faults.configure(["ops.ed25519.device:raise"], seed=1)
    try:
        assert edb.verify_batch(items).tolist() == [True]
    finally:
        faults.clear()
    with pytest.raises(chip_smoke.SmokeFailure, match="ed25519-device"):
        chip_smoke.phase_end_state()
