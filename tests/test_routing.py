"""The one routing decision of the verify path (ops/ed25519_batch.route_batch).

Which route a batch of n signatures takes -- the registry's pure-Python
loop, the C host verifier, the one-chip kernel, every local chip (a TPU
host of four chips, faked as far as the host code can tell;
tests/test_placed_chunks.py has the real floor and the placement) --
and whether the verify service owns the launch, all come from this one
function. The table below is docs/PARALLEL.md's, case by case; the tests
after it hold both dispatch_batch entry points, the registry and the
service to the answer.

Kernels are stood in for by their `valid` argument (the slow tier's
test_ed25519_batch / test_sr25519_batch run the real ones): routing, the
service's choice and `finish.route` are host work."""

import ast
import pathlib

import jax
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import ed25519, sr25519, verify_service
from tendermint_tpu.ops import chost
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import ed25519_pallas as edp
from tendermint_tpu.ops import sr25519_batch as srb
from tendermint_tpu.utils import faults

SCALAR_MIN, CROSSOVER, SHARD_MIN = 32, 512, 64
# one size on each side of every threshold: below scalar_min, between it
# and the crossover (and above the shard floor), above the crossover
SIZES = (8, 100, 2000)

# (C library, force_device, mesh) -> the routes of SIZES, in order
TABLE = {
    ("loaded", False, "1dev"): ("host", "host", "device"),
    ("loaded", False, "4chip"): ("host", "sharded", "sharded"),
    ("loaded", False, "4chip_shard_off"): ("host", "host", "device"),
    ("loaded", True, "1dev"): ("device", "device", "device"),
    ("loaded", True, "4chip"): ("device", "sharded", "sharded"),
    ("loaded", True, "4chip_shard_off"): ("device", "device", "device"),
    ("building", False, "1dev"): ("scalar", "host", "device"),
    ("building", False, "4chip"): ("scalar", "sharded", "sharded"),
    ("building", False, "4chip_shard_off"): ("scalar", "host", "device"),
    ("building", True, "1dev"): ("device", "device", "device"),
    ("building", True, "4chip"): ("device", "sharded", "sharded"),
    ("building", True, "4chip_shard_off"): ("device", "device", "device"),
    ("absent", False, "1dev"): ("scalar", "device", "device"),
    ("absent", False, "4chip"): ("scalar", "sharded", "sharded"),
    ("absent", False, "4chip_shard_off"): ("scalar", "device", "device"),
    ("absent", True, "1dev"): ("device", "device", "device"),
    ("absent", True, "4chip"): ("device", "sharded", "sharded"),
    ("absent", True, "4chip_shard_off"): ("device", "device", "device"),
}


def test_no_ops_module_imports_the_parallel_package():
    """The routing decision and everything it asks live in ops: no module
    there reaches up into a `parallel` package for half of its answer."""
    for path in sorted(pathlib.Path(edb.__file__).parent.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [f"{node.module}.{a.name}" for a in node.names]
            else:
                continue
            assert not any("parallel" in n.split(".") for n in names), (
                path.name, node.lineno, names)


def _set_chost(monkeypatch, state: str) -> None:
    monkeypatch.setattr(chost, "available", lambda: state == "loaded")
    monkeypatch.setattr(chost, "building", lambda: state == "building")


def _set_mesh(monkeypatch, fake_tpu_host, mesh: str) -> None:
    """One device, or a TPU host of four chips whose chunk puts the shard
    floor at SHARD_MIN signatures."""
    if mesh == "1dev":
        monkeypatch.setattr(jax, "local_device_count", lambda: 1)
        return
    fake_tpu_host(4, SHARD_MIN - 1)
    if mesh == "4chip_shard_off":
        monkeypatch.setenv("TM_TPU_SHARD", "0")


@pytest.mark.parametrize(
    "lib, force, mesh, n, want",
    [(lib, force, mesh, n, want)
     for (lib, force, mesh), wants in TABLE.items()
     for n, want in zip(SIZES, wants)],
    ids=str)
def test_route_table(lib, force, mesh, n, want, monkeypatch, fake_tpu_host):
    _set_chost(monkeypatch, lib)
    _set_mesh(monkeypatch, fake_tpu_host, mesh)
    # as tests/benchmark does: the module ATTRIBUTE, not the environment
    monkeypatch.setattr(edb, "host_crossover", lambda: CROSSOVER)
    assert edb.route_batch(n, force, SCALAR_MIN) == want


@pytest.mark.parametrize("lib, want", [
    ("loaded", "host"), ("building", "host"), ("absent", "device")])
def test_no_scalar_route_for_direct_callers(lib, want, monkeypatch):
    """scalar_min defaults to 0: the service and other direct callers of
    dispatch_batch never get "scalar", whatever the size."""
    _set_chost(monkeypatch, lib)
    monkeypatch.setattr(jax, "local_device_count", lambda: 1)
    monkeypatch.setattr(edb, "host_crossover", lambda: CROSSOVER)
    assert edb.route_batch(1) == want


# --- the entry points take the route the function names ---------------------

_KINDS = {"ed25519": (ed25519, edb), "sr25519": (sr25519, srb)}
_FINISH_ROUTE = {"host": "host_c", "device": "pallas", "sharded": "sharded"}


def _items(kind: str, n: int):
    """n valid (PubKey, msg, sig): eight signed, tiled (sr25519 signs in
    pure Python)."""
    mod = _KINDS[kind][0]
    base = []
    for i in range(8):
        priv = mod.gen_priv_key((b"route-%s-%d" % (kind.encode(), i))
                                .ljust(32, b"\x07"))
        msg = b"route-%d" % i
        sig = (ed25519.sign(priv.data, msg) if kind == "ed25519"
               else priv.sign(msg))
        base.append((priv.pub_key(), msg, sig))
    return (base * -(-n // 8))[:n]


def _raw(items):
    return [(pk.bytes(), m, s) for pk, m, s in items]


@pytest.fixture
def stand_ins(monkeypatch, fake_tpu_host):
    """Crossover 32 and a TPU host of four chips with a 64-lane chunk, as
    far as the host code can tell; kernels and chunk programs answering
    `valid`; -> the (n, finish) of every ops dispatch_batch."""
    # build inline: the non-blocking available() answers False until a
    # background build lands, and the host route here is the C verifier's
    if not chost.ensure_available():
        pytest.skip("C host verifier unavailable (no gcc?)")
    monkeypatch.setattr(edb, "host_crossover", lambda: 32)
    fake_tpu_host(4, 64)
    monkeypatch.delenv("TMTPU_VERIFY_SERVICE", raising=False)
    monkeypatch.setattr(edb, "_jnp_kernel", lambda tab, **kw: kw["valid"])
    monkeypatch.setattr(srb, "_kernel", lambda tab, *arrays: arrays[-1])
    for program in ("_verify_chunk", "_sr_verify_chunk"):
        monkeypatch.setattr(edp, program,
                            lambda tab, *cols: cols[-1].astype(jnp.int32))
    monkeypatch.setattr(edb.KeySet, "gathered_lane",
                        lambda self, idx, device=None: None)
    seen = []
    for mod in (edb, srb):
        def spy(items, force_device=False, _real=mod.dispatch_batch):
            dev, finish = _real(items, force_device=force_device)
            seen.append((len(items), finish))
            return dev, finish
        monkeypatch.setattr(mod, "dispatch_batch", spy)
    verify_service.reset()
    yield seen
    verify_service.reset()


@pytest.mark.parametrize("kind", ["ed25519", "sr25519"])
@pytest.mark.parametrize("n, want", [(16, "host"), (40, "device"),
                                     (72, "sharded")])
def test_entry_points_and_registry_take_the_named_route(kind, n, want,
                                                        stand_ins):
    """One batch on each side of the crossover and of the shard floor (one
    chunk), through ops.dispatch_batch and through the registry: the route
    on the finish is the one route_batch names, and the service owns the
    launch exactly when that route pays the sync floor."""
    items = _items(kind, n)
    assert edb.route_batch(n) == want
    assert edb.route_batch(n, False, SCALAR_MIN) == want  # C library loaded

    dev, finish = _KINDS[kind][1].dispatch_batch(_raw(items))
    assert (dev is None) == (want == "host")
    assert finish(cbatch._device_get(dev) if dev is not None else None).all()
    assert finish.route == _FINISH_ROUTE[want]

    del stand_ins[:]
    v = cbatch.create_batch_verifier(kind)
    for pk, m, s in items:
        v.add(pk, m, s)
    p = v.dispatch()
    assert isinstance(p, cbatch.ServicePending) == (want != "host")
    assert p.resolve() == (True, [True] * n)
    assert [(got, f.route) for got, f in stand_ins] == [
        (n, _FINISH_ROUTE[want])]


@pytest.mark.parametrize("kind", ["ed25519", "sr25519"])
def test_the_host_route_is_the_scalar_loop_while_the_library_builds(
        kind, stand_ins, monkeypatch):
    _set_chost(monkeypatch, "building")
    items = _items(kind, 4)
    assert edb.route_batch(4) == "host"
    dev, finish = _KINDS[kind][1].dispatch_batch(_raw(items))
    assert dev is None and finish(None).all()
    assert finish.route == "host_scalar"
    # the registry's own rung comes first: below batch_min, no library
    del stand_ins[:]
    v = cbatch.create_batch_verifier(kind)
    for pk, m, s in items:
        v.add(pk, m, s)
    assert edb.route_batch(4, False, v._batch_min_default) == "scalar"
    assert v.dispatch().resolve() == (True, [True] * 4) and not stand_ins


# --- what "device" and "sharded" launch for sr25519 --------------------------


@pytest.mark.parametrize("backend, n, want, launch", [
    ("tpu", 40, "pallas", ("chunk", 64)),         # one chunk: the Pallas chunk
    ("cpu", 40, "jnp", ("tile", edb.JNP_TILE)),   # no TPU backend: the jnp tile
    ("cpu", 72, "jnp", ("tile", edb.JNP_TILE)),   # ... whatever the devices
    ("tpu", 72, "sharded", ("chunk", 64)),  # several chips: a chunk a chip
])
def test_sr25519_kernel_follows_backend_and_device_count(
        backend, n, want, launch, stand_ins, monkeypatch):
    """route_batch names the route; which program the sr25519 device route
    launches follows from what the process can observe, the backend and the
    device count, and nothing else."""
    monkeypatch.setattr(edb, "_use_pallas", lambda: backend == "tpu")
    launched = []

    def chunk(tab, k32, s32, r32, valid):
        launched.append(("chunk", valid.shape[1]))
        return valid.astype(jnp.int32)

    def tile(tab, *arrays):
        launched.append(("tile", arrays[-1].shape[0]))
        return arrays[-1]

    monkeypatch.setattr(edp, "_sr_verify_chunk", chunk)
    monkeypatch.setattr(srb, "_kernel", tile)
    items = _raw(_items("sr25519", n))
    assert edb.route_batch(n) == ("sharded" if want == "sharded" else "device")
    dev, finish = srb.dispatch_batch(items)
    assert finish(cbatch._device_get(dev)).all() and finish.route == want
    assert launched == [launch] * (2 if want == "sharded" else 1)


# --- the service's guess and the dispatch agree -----------------------------


@pytest.mark.parametrize("lib, n, force", [
    ("loaded", 40, False),    # at or above the crossover
    ("loaded", 72, False),    # above the shard floor, one chunk
    ("loaded", 9, True),      # below the crossover, pinned to the device
    ("absent", 33, False),    # no C verifier: the device at any size
], ids=["above_crossover", "above_shard_floor", "forced", "no_c_library"])
def test_a_service_owned_request_is_not_answered_by_the_host(
        lib, n, force, stand_ins, monkeypatch):
    """What the registry hands to the service, the service's own dispatch
    routes to the device too: nobody pays the thread hop and the window to
    reach the C verifier."""
    _set_chost(monkeypatch, lib)
    v = cbatch.create_batch_verifier("ed25519")
    for pk, m, s in _items("ed25519", n):
        v.add(pk, m, s)
    p = v.dispatch(force_device=force)
    assert isinstance(p, cbatch.ServicePending)
    assert p.resolve() == (True, [True] * n)
    assert [f.route for _n, f in stand_ins] == [
        "sharded" if n > 64 else "pallas"]


def test_only_an_open_breaker_sends_a_service_owned_request_to_the_host(
        stand_ins, monkeypatch):
    for field in ("failures", "trips", "last_error"):  # put back afterwards
        monkeypatch.setattr(edb.BREAKER, field, getattr(edb.BREAKER, field))
    monkeypatch.setattr(edb.BREAKER, "probe", None)
    edb.BREAKER.reset()
    failures = edb.BREAKER.failures
    faults.configure(["ops.ed25519.device:raise@1"], seed=3)
    try:
        v = cbatch.create_batch_verifier("ed25519")
        for pk, m, s in _items("ed25519", 40):
            v.add(pk, m, s)
        p = v.dispatch()
        assert isinstance(p, cbatch.ServicePending)
        assert p.resolve() == (True, [True] * 40)
        assert edb.BREAKER.failures == failures + 1 and edb.BREAKER.is_open
        assert [f.route for _n, f in stand_ins] == ["breaker_fallback"]
    finally:
        faults.configure([], seed=0)
        edb.BREAKER.reset()
