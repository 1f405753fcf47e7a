"""Multi-chip spreading of the batch-verify kernels.

The reference's parallelism analogue (SURVEY.md section 2.3): inside one
validator process, the signature batch for a commit is data-parallel over the
validator axis, and the lanes share nothing: the tally is the host's
(types/validator_set.go:685-714's loop, our commit.tally). Two ways to put
that axis on the local devices, chosen by what the process observes:

  TPU backend    :func:`dispatch_placed`: the chunk loop of the one-chip path
                 (ops/ed25519_pallas.launch_chunks) with the local devices to
                 place on. Chunk k of a batch -- 4,096 lanes of the Pallas
                 kernel -- runs on local device k mod ndev, which holds its
                 own copy of the key table (KeySet.gathered_lane) and packs
                 its own piece of the bitmap; the pieces come back in the
                 caller's one device_get. The same jitted programs as on one
                 chip, compiled once a device; no collective.
  other backends :func:`dispatch_sharded`: shard_map of the jnp kernels over
                 a 1-D ("dp",) mesh in n_devices * JNP_TILE chunks, the key
                 table replicated. (The CPU mesh of the tests and of
                 __graft_entry__.dryrun_multichip; :func:`sharded_verify_tally`
                 is the same body with the voting-power tally all-reduced by
                 psum, the on-device analogue of libs/bits.BitArray +
                 talliedVotingPower.)

Production routing (docs/PARALLEL.md): ops/ed25519_batch.route_batch, the one
routing decision of the verify path, asks :func:`should_shard` for both key
types, so every caller of the BatchVerifier registry -- verify_commit_async,
the fast-sync verify-ahead pipeline, the consensus vote drain, light
range_verify -- reaches the other devices transparently through the deferred
dispatch()/PendingVerify contract, from the floor of :func:`shard_threshold`
upward. With the continuous-batching verify
service on (crypto/verify_service.py, the default), the size
:func:`should_shard` sees is the COALESCED generation -- several callers'
concurrent dispatches merged into one launch -- so multi-caller traffic
crosses the sharding threshold sooner than any single caller would. Knobs:

  TM_TPU_SHARD=0       opt out of sharding entirely (single-device paths)
  TM_TPU_SHARD_MIN=N   batch-size floor for the sharded route (default: on a
                       TPU backend one Pallas chunk plus one, since a single
                       chunk has nothing to spread; elsewhere n_devices *
                       MIN_BUCKET, below one kernel bucket per device the
                       fan-out cannot pay for itself)
"""

from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tendermint_tpu.ops import ed25519_batch


# ---------------------------------------------------------------------------
# Shard-routing policy (asked by ed25519_batch.route_batch)
# ---------------------------------------------------------------------------


def shard_enabled() -> bool:
    """False when the operator opted out (TM_TPU_SHARD=0)."""
    return os.environ.get("TM_TPU_SHARD") != "0"


def shard_threshold(ndev: int) -> int:
    """Batch-size floor for the sharded route. On a TPU backend: more than
    one Pallas chunk, the unit that is placed on a device -- a batch of one
    chunk or less runs as on a one-chip host. Elsewhere one kernel
    MIN_BUCKET per device -- smaller batches cannot fill the mesh, and the
    per-device dispatch overhead would exceed the fan-out win."""
    v = os.environ.get("TM_TPU_SHARD_MIN")
    if v:
        return int(v)
    if ed25519_batch._use_pallas():
        return _pallas_chunk() + 1
    return ndev * ed25519_batch.MIN_BUCKET


def _pallas_chunk() -> int:
    from tendermint_tpu.ops import ed25519_pallas

    return ed25519_pallas.CHUNK


def should_shard(n: int) -> bool:
    """The mesh's half of the routing decision (ed25519_batch.route_batch):
    >1 local device, sharding not opted out, and the batch at or above the
    threshold. On 1 device this is always False, so every path behaves
    exactly as the single-device build."""
    ndev = jax.local_device_count()
    return ndev > 1 and shard_enabled() and n >= shard_threshold(ndev)


def make_mesh(devices=None) -> Mesh:
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices, dtype=object).reshape(-1), ("dp",))


def _local_verify_tally(tab, h_win, s_win, r_y, r_sign, valid, power, for_block):
    ok = ed25519_batch._verify_kernel(
        tab, h_win, s_win, r_y, r_sign, valid, axis_name="dp"
    )
    # Tally voting power of passing, block-committing signatures; psum over
    # the device mesh so every chip holds the global tally.
    local = jnp.sum(jnp.where(ok & for_block, power, 0))
    tally = jax.lax.psum(local, "dp")
    all_ok = jax.lax.psum(jnp.sum(~ok & valid), "dp") == 0
    return ok, tally, all_ok


def sharded_verify_tally(mesh: Mesh):
    """Build the jitted multi-chip verify+tally step for `mesh`.

    Inputs are sharded on the signature axis; outputs: (bitmap (N,) sharded,
    global tally scalar, global all-valid-passed scalar)."""
    spec = P("dp")
    fn = jax.shard_map(
        _local_verify_tally,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, spec, spec, spec),
        out_specs=(spec, P(), P()),
    )
    return jax.jit(fn)


def shard_args(mesh: Mesh, args: dict, power, for_block):
    """Device-put prepared numpy args with the dp sharding layout."""
    spec = NamedSharding(mesh, P("dp"))
    out = {k: jax.device_put(v, spec) for k, v in args.items()}
    out["power"] = jax.device_put(power, spec)
    out["for_block"] = jax.device_put(for_block, spec)
    return out


# ---------------------------------------------------------------------------
# Production path: the "sharded" route of both ops dispatch_batch
# ---------------------------------------------------------------------------

_mesh_cache: tuple[tuple, Mesh] | None = None
_fn_cache: dict[tuple, object] = {}


def _get_mesh() -> Mesh:
    global _mesh_cache
    devs = tuple(jax.devices())
    if _mesh_cache is None or _mesh_cache[0] != devs:
        _mesh_cache = (devs, make_mesh(list(devs)))
    return _mesh_cache[1]


def _local_verify(tab_full, idx, h_win, s_win, r_y, r_sign, valid):
    """Per-device ed25519 body: gather this shard's comb tables from the
    replicated key-set table, then run the verify kernel. Gathering INSIDE
    shard_map keeps the per-call H2D payload to indices + scalars; the
    (heavy, height-persistent) table replicates once per append to it."""
    tab = jnp.take(tab_full, idx, axis=0)
    return ed25519_batch._verify_kernel(
        tab, h_win, s_win, r_y, r_sign, valid, axis_name="dp")


def _local_verify_sr(tab_full, idx, k_win, s_win, r_limbs, valid):
    """Per-device sr25519 body: same replicated-table gather, schnorrkel
    kernel (ops/sr25519_batch; the challenge k stands in for h)."""
    from tendermint_tpu.ops import sr25519_batch

    tab = jnp.take(tab_full, idx, axis=0)
    return sr25519_batch._sr_verify_kernel(
        tab, k_win, s_win, r_limbs, valid, axis_name="dp")


# kind -> (per-device body, number of sharded args: idx + per-item arrays).
# The count is declared, not introspected: a later signature change (default
# arg, decorator) must force this table to be updated in the same edit.
_BODIES = {"ed25519": (_local_verify, 6), "sr25519": (_local_verify_sr, 5)}


def _sharded_verify_fn(mesh: Mesh, kind: str = "ed25519"):
    body, n_item_args = _BODIES[kind]
    key = (kind,) + tuple(id(d) for d in mesh.devices.flat)
    fn = _fn_cache.get(key)
    if fn is None:
        fn = jax.jit(jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(),) + (P("dp"),) * n_item_args,
            out_specs=P("dp"),
        ))
        _fn_cache[key] = fn
        if len(_fn_cache) > 8:
            _fn_cache.pop(next(iter(_fn_cache)))
    return fn


def replicated_tables(ks, mesh: Mesh):
    """The key type's whole per-key comb table on every device of the mesh:
    copied once per mesh and per append to the table (KeySet.replicated),
    not once per signer set; a set is row numbers into it."""
    return ks.replicated(tuple(id(d) for d in mesh.devices.flat),
                         NamedSharding(mesh, P()))


@contextlib.contextmanager
def _shard_dispatch(kind: str, n: int, chunks: int, devices: int):
    """A sharded dispatch: the verify.shard_dispatch span around it and one
    count on verify_sharded_total after it, `chunks` launches over
    `devices` devices."""
    from tendermint_tpu.utils import metrics as tmmetrics
    from tendermint_tpu.utils import trace as _trace

    with (_trace.current().span("verify.shard_dispatch", kind=kind, n=n,
                                chunks=chunks, devices=devices)
          if _trace.ENABLED else _trace.NULL_SPAN):
        yield
    if tmmetrics.GLOBAL_NODE_METRICS is not None:
        tmmetrics.GLOBAL_NODE_METRICS.verify_sharded.add(devices=devices)


def dispatch_placed(kind: str, n: int, launch):
    """The sharded route on a TPU backend: `launch(devices)` is a key type's
    chunk loop (ops/ed25519_pallas.launch_chunks) taking the devices to
    place on, all the local ones, chunk k on device k mod ndev. -> what it
    returns, the packed pieces of the bitmap, nothing fetched; bit for bit
    the one-chip path's."""
    devices = tuple(jax.local_devices())
    chunks = -(-n // _pallas_chunk())
    with _shard_dispatch(kind, n, chunks, min(chunks, len(devices))):
        return launch(devices)


def dispatch_sharded(kind: str, ks, key_idx, arrays: list, n: int):
    """The sharded route off a TPU backend: the signature axis shards over
    the ("dp",) mesh under shard_map. Dispatches in fixed
    n_devices*JNP_TILE chunks so no batch size triggers a fresh compile;
    padding lanes carry valid=False (every kernel masks its result with
    `valid`, so they can never read as accepted) and key index 0.

    `arrays` is the kernel-specific per-item numpy argument list, valid
    LAST (ed25519: h_win, s_win, r_y, r_sign, valid; sr25519: k_win, s_win,
    r_limbs, valid). Returns the (Npad,) bool device array without fetching
    (callers batch the readback); the bitmap is byte-identical to the
    single-device path."""
    mesh = _get_mesh()
    ndev = mesh.devices.size
    chunk = ndev * ed25519_batch.JNP_TILE
    nb = -(-n // chunk) * chunk
    with _shard_dispatch(kind, n, nb // chunk, ndev):
        return _dispatch_sharded(mesh, kind, ks, key_idx, arrays, n, nb, chunk)


def _dispatch_sharded(mesh, kind, ks, key_idx, arrays, n, nb, chunk):
    import numpy as np

    def pad(v):
        out = np.zeros((nb,) + v.shape[1:], dtype=v.dtype)
        out[:n] = v
        return out

    idx = np.zeros((nb,), dtype=np.int32)
    idx[:n] = key_idx
    padded = [pad(np.asarray(v)) for v in arrays]

    tab_full = replicated_tables(ks, mesh)
    fn = _sharded_verify_fn(mesh, kind)
    spec = NamedSharding(mesh, P("dp"))
    program = "jit_" + _BODIES[kind][0].__name__
    outs = []
    for off in range(0, nb, chunk):
        sl = slice(off, off + chunk)
        with ed25519_batch.launch_span(program, "sharded", n - off, chunk,
                                       "mesh"):
            outs.append(fn(
                tab_full,
                jax.device_put(idx[sl], spec),
                *(jax.device_put(v[sl], spec) for v in padded),
            ))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def dispatch_batch_sharded(ks, key_idx, items, pub_ok):
    """ed25519 sharded dispatch (the original production entry): host prep
    here, then the generic chunked shard_map driver."""
    import numpy as np

    s = ed25519_batch.prepare_scalars(items, pub_ok, windows=True)
    r_y, r_sign = ed25519_batch._r_to_limbs(s["r32"])
    arrays = [s["h_win"].astype(np.int32), s["s_win"].astype(np.int32),
              r_y, r_sign, s["valid"]]
    return dispatch_sharded("ed25519", ks, key_idx, arrays, len(items))
