"""Batched sr25519 (schnorrkel) verification on TPU.

The reference wraps native go-schnorrkel and verifies serially at ~50-100
us/sig (reference: crypto/sr25519/pubkey.go:10); the repo's spec-faithful
pure-Python path (crypto/sr25519.py) costs ~18 ms/sig. This module makes
sr25519 a first-class batched key type by reusing the ed25519 Edwards comb
kernel for the curve work:

    schnorrkel verify:  s*B == R + k*A
    rearranged:         R' = [s]B + [k](-A)  must equal R as ristretto points

which is EXACTLY the ed25519 kernel's comb evaluation shape ([s]B + [h](-A))
with the challenge k in place of h. The three sr25519-specific pieces:

 * merlin transcript challenges k: batched in C (csrc/sr25519_strobe.c, one
   FFI crossing; pure-Python Transcript fallback), reduced mod L with the
   vectorized scalar25519.reduce_mod_l.
 * ristretto255 decode of R: ON DEVICE -- the sqrt-ratio exponentiation
   (field25519.pow_p58) vectorizes over the batch; the host uploads raw R
   bytes only.
 * ristretto equality: coset check X'*y_r == Y'*x_r  OR  Y'*y_r == X'*x_r
   (projective, RFC 9496 4.5) instead of compress-and-compare -- no encode
   needed, 4 field muls.

Accept/reject is byte-identical with crypto/sr25519.verify: the same
structural checks (marker bit, canonical s < L), the same ristretto decode
validity conditions (host-checked canonical field element + device-checked
square/t-sign/y-zero), the same transcript bytes (differential test in
tests/test_sr25519_batch.py).

Two kernels evaluate this, chosen by what the process can observe: on a
TPU backend the Pallas chunk ops/ed25519_pallas._sr_verify_chunk (the
ed25519 kernel's comb loop, this decode and comparison as its tail, 4,096
lanes a call, a chunk a chip where there are several); where the backend is
no TPU, the jnp _sr_verify_kernel below (256 lanes a call).

Pubkey comb tables live in one device-resident table keyed per key, a row a
key, exactly like ed25519's and through the same code (edb.build_keyset,
edb.KeyTable): a batch is a list of row numbers, and only a key the table
has not met is decoded and has its tables built.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto import sr25519 as srref
from tendermint_tpu.ops import breaker as _cbreaker
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.utils import faults
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.ops import edwards25519 as ed
from tendermint_tpu.ops import field25519 as fe
from tendermint_tpu.ops import scalar25519 as sc
from tendermint_tpu.ops import chash

P = fe.P
L = sc.L

_ONE = fe.from_int(1)
_D_LIMBS = fe.from_int(ed.D)
_SQRT_M1_LIMBS = fe.from_int(srref.SQRT_M1)

_P_BYTES_BE = np.frombuffer(P.to_bytes(32, "big"), dtype=np.uint8).astype(np.int16)


# ---------------------------------------------------------------------------
# Transcript challenges (host)
# ---------------------------------------------------------------------------

_prefix_lock = threading.Lock()
_prefix: tuple[bytes, int, int] | None = None


def _transcript_prefix() -> tuple[bytes, int, int]:
    """Strobe state after Transcript("SigningContext") + append_message("",""),
    shared by every signature; built once with the pure-Python stack."""
    global _prefix
    with _prefix_lock:
        if _prefix is None:
            t = srref.Transcript(b"SigningContext")
            t.append_message(b"", b"")
            s = t.strobe
            _prefix = (bytes(s.state), s.pos, s.pos_begin)
        return _prefix


def challenges(msgs: list[bytes], pubs: np.ndarray, rs: np.ndarray) -> np.ndarray:
    """Per-signature challenge scalars -> (N, 32) uint8 canonical LE mod L.

    pubs, rs: C-contiguous (N, 32) uint8."""
    state, pos, pos_begin = _transcript_prefix()
    wide = chash.sr25519_challenges(state, pos, pos_begin, msgs, pubs, rs)
    if wide is None:
        # Pure-Python fallback: clone the prefix per item.
        wide = np.empty((len(msgs), 64), dtype=np.uint8)
        pb, rb = pubs.tobytes(), rs.tobytes()
        for i, m in enumerate(msgs):
            t = srref.Transcript.__new__(srref.Transcript)
            t.strobe = srref.Strobe128.__new__(srref.Strobe128)
            t.strobe.state = bytearray(state)
            t.strobe.pos, t.strobe.pos_begin, t.strobe.cur_flags = pos, pos_begin, 0
            t.append_message(b"sign-bytes", m)
            t.append_message(b"proto-name", b"Schnorr-sig")
            t.append_message(b"sign:pk", pb[32 * i:32 * i + 32])
            t.append_message(b"sign:R", rb[32 * i:32 * i + 32])
            wide[i] = np.frombuffer(t.challenge_bytes(b"sign:c", 64), dtype=np.uint8)
    return sc.reduce_mod_l(wide)


# ---------------------------------------------------------------------------
# Device kernel
# ---------------------------------------------------------------------------


def _ct_abs(x):
    """|x| mod p: negate when the canonical representative is odd."""
    xc = fe.to_canonical(x)
    neg = (xc[..., 0] & 1) == 1
    return fe.select(neg, fe.sub(jnp.zeros_like(xc), xc), xc)


def _sqrt_ratio_m1(u, v):
    """RFC 9496 4.2 SQRT_RATIO_M1, vectorized (mirrors srref._sqrt_ratio_m1)."""
    sqrt_m1 = jnp.asarray(_SQRT_M1_LIMBS)
    v3 = fe.mul(fe.square(v), v)
    v7 = fe.mul(fe.square(v3), v)
    r = fe.mul(fe.mul(u, v3), fe.pow_p58(fe.mul(u, v7)))
    check = fe.to_canonical(fe.mul(v, fe.square(r)))
    u_c = fe.to_canonical(u)
    neg_u = fe.to_canonical(fe.sub(jnp.zeros_like(u), u))
    neg_u_i = fe.to_canonical(fe.mul(fe.sub(jnp.zeros_like(u), u), sqrt_m1))
    correct = fe.eq(check, u_c)
    flipped = fe.eq(check, neg_u)
    flipped_i = fe.eq(check, neg_u_i)
    r = fe.select(flipped | flipped_i, fe.mul(r, sqrt_m1), r)
    return correct | flipped, _ct_abs(r)


def _ristretto_decode_dev(s_limbs):
    """(N, 20) canonical field limbs of the 32-byte encoding (host has
    already rejected s >= p and odd s) -> (x, y, ok). Mirrors
    srref.ristretto_decode."""
    shape = s_limbs.shape[:-1]
    one = jnp.broadcast_to(jnp.asarray(_ONE), shape + (20,))
    d = jnp.asarray(_D_LIMBS)
    ss = fe.square(s_limbs)
    u1 = fe.sub(one, ss)
    u2 = fe.add(one, ss)
    u2_sqr = fe.square(u2)
    # v = -(D * u1^2) - u2^2
    v = fe.sub(jnp.zeros_like(ss), fe.add(fe.mul(fe.mul(d, u1), u1), u2_sqr))
    was_square, invsqrt = _sqrt_ratio_m1(one, fe.mul(v, u2_sqr))
    den_x = fe.mul(invsqrt, u2)
    den_y = fe.mul(fe.mul(invsqrt, den_x), v)
    x = _ct_abs(fe.mul(fe.mul_small(s_limbs, 2), den_x))
    y = fe.mul(u1, den_y)
    t_c = fe.to_canonical(fe.mul(x, y))
    y_c = fe.to_canonical(y)
    ok = was_square & ((t_c[..., 0] & 1) == 0) & ~jnp.all(y_c == 0, axis=-1)
    return x, y, ok


def _sr_verify_kernel(tab, k_win, s_win, r_limbs, valid):
    """The jitted batch verify.

    tab:     (N, 16, 4, 20) int32  comb table of -A per signature (cached)
    k_win:   (N, 64) int32   comb windows of the challenge k
    s_win:   (N, 64) int32   comb windows of s
    r_limbs: (N, 20) int32   field limbs of the sig's 32-byte R encoding
    valid:   (N,)    bool    host-side precheck results
    ->       (N,)    bool
    """
    n = tab.shape[0]
    tab_b = jnp.broadcast_to(jnp.asarray(edb.TAB_B), (n, 16, 4, 20))

    def body(j, acc):
        acc = ed.double(acc)
        wk = jax.lax.dynamic_slice_in_dim(k_win, j, 1, axis=1)[:, 0]
        ws = jax.lax.dynamic_slice_in_dim(s_win, j, 1, axis=1)[:, 0]
        acc = ed.add(acc, edb._gather_point(tab, wk))
        acc = ed.add(acc, edb._gather_point(tab_b, ws))
        return acc

    acc0 = ed.identity((n,))
    acc = jax.lax.fori_loop(0, 64, body, acc0)

    x_r, y_r, ok_r = _ristretto_decode_dev(r_limbs)
    X, Y = acc[..., 0, :], acc[..., 1, :]
    # Ristretto coset equality of R' = (X:Y:Z) and R = (x_r, y_r), projective:
    # x'*y_r == y'*x_r  OR  y'*y_r == x'*x_r  (RFC 9496 4.5; Z cancels).
    e1 = fe.eq(fe.to_canonical(fe.mul(X, y_r)), fe.to_canonical(fe.mul(Y, x_r)))
    e2 = fe.eq(fe.to_canonical(fe.mul(Y, y_r)), fe.to_canonical(fe.mul(X, x_r)))
    return (e1 | e2) & ok_r & valid


_kernel = jax.jit(_sr_verify_kernel)


# ---------------------------------------------------------------------------
# Pubkey key sets (ristretto decode differs from ed25519 decompress)
# ---------------------------------------------------------------------------

_decode_cache: dict[bytes, np.ndarray | None] = {}


def _decode_neg(pub: bytes) -> np.ndarray | None:
    """Cached: ristretto pubkey bytes -> extended limbs of -A, or None."""
    hit = _decode_cache.get(pub)
    if hit is not None or pub in _decode_cache:
        return hit
    pt = srref.ristretto_decode(pub)
    out = None
    if pt is not None:
        x, y, _, _ = pt
        out = ed.negate_affine(x, y)
    if len(_decode_cache) < 1_000_000:
        _decode_cache[pub] = out
    return out


_KS_LOCK = threading.Lock()
# sequence -> row numbers memo, and this key type's per-key table (see
# edb.build_keyset: all of its state lives in these two objects)
_KS_CACHE: OrderedDict[bytes, tuple[edb.KeySet, np.ndarray]] = OrderedDict()
_KS_UNIQ_CACHE = edb.KeyTable()


def get_keyset(pubs: list[bytes]) -> tuple[edb.KeySet, np.ndarray, np.ndarray]:
    """-> (KeySet, key_idx (N,) int32 row numbers, pub_ok (N,) bool): the
    rows of sr25519's device-resident table that hold the comb tables of
    each item's ristretto-decoded -A; a key it does not hold is built."""
    return edb.build_keyset(pubs, _KS_CACHE, _KS_LOCK, _decode_neg,
                            uniq_cache=_KS_UNIQ_CACHE, kind="sr25519")


# ---------------------------------------------------------------------------
# Host prep + dispatch
# ---------------------------------------------------------------------------

_BIT_W = (1 << np.arange(13, dtype=np.int64)).astype(np.int32)


def _bytes_to_limbs(b32: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 LE field-element encodings -> (N, 20) int32 limbs."""
    bits = np.unpackbits(b32, axis=1, bitorder="little").astype(np.int32)
    bits = np.concatenate(
        [bits, np.zeros((bits.shape[0], 4), dtype=np.int32)], axis=1)  # 260
    return (bits.reshape(-1, 20, 13) @ _BIT_W).astype(np.int32)


def _lt_p(s_le: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 LE -> (N,) bool: value < p (canonical field encoding)."""
    return sc.lt_bound(s_le, _P_BYTES_BE)


def _parse_items(items, n: int):
    """-> (sig_ok, marker_ok, r32, s32 (marker stripped), pubs_arr,
    pub_size_ok): the structural prechecks every route shares."""
    sig_ok = np.fromiter(
        (len(it[2]) == srref.SIGNATURE_SIZE for it in items), dtype=bool, count=n)
    zero64 = b"\x00" * 64
    sigs = np.frombuffer(
        b"".join(it[2] if len(it[2]) == 64 else zero64 for it in items),
        dtype=np.uint8).reshape(n, 64)
    r32 = np.ascontiguousarray(sigs[:, :32])
    s32 = np.ascontiguousarray(sigs[:, 32:]).copy()
    marker_ok = (s32[:, 31] & 128) != 0  # schnorrkel v1 marker bit
    s32[:, 31] &= 127
    pubs32, pub_size_ok = edb._normalize_pubs([it[0] for it in items])
    pubs_arr = np.frombuffer(pubs32, dtype=np.uint8).reshape(n, 32)
    return sig_ok, marker_ok, r32, s32, pubs_arr, pub_size_ok


def _scalar_fallback_bitmap(items) -> np.ndarray:
    """Pure-Python serial re-verification (the degradation floor)."""
    return np.fromiter((srref.verify(p, m, s) for (p, m, s) in items),
                       dtype=bool, count=len(items))


def _scalars_span(n: int):
    """prep.scalars around the merlin challenges and the comb windows."""
    return (_trace.current().span("prep.scalars", sigs=n, kind="sr25519")
            if _trace.ENABLED else _trace.NULL_SPAN)


def _host_fallback(items, n, route: str | None = None):
    """(device_out=None, finish) via the C host verifier when loaded, else
    the pure-Python scalar loop. `route` names the answer when it is not
    the host's own choice (breaker_fallback)."""
    from tendermint_tpu.ops import chost

    c_verifier = chost.available()
    route = route or ("host_c" if c_verifier else "host_scalar")
    with edb._host_span(route, n, kind="sr25519"):
        if c_verifier:
            sig_ok, marker_ok, r32, s32, pubs_arr, pub_size_ok = _parse_items(items, n)
            with _scalars_span(n):
                k32 = challenges([it[1] for it in items], pubs_arr, r32)
            bitmap = chost.sr25519_verify(
                pubs_arr, k32, s32, r32, sig_ok & marker_ok & pub_size_ok)
        else:
            bitmap = _scalar_fallback_bitmap(items)
    return None, _cbreaker.routed(lambda _unused: bitmap, route)


def _dispatch_device(items, n: int, multichip: bool = False):
    """The accelerator route proper; raises on device failure (injected or
    real) -- the circuit breaker in dispatch_batch owns the fallback. The
    fault site fires in dispatch_batch, not here, so the breaker probe
    never consumes consensus-path hit indices (see the ed25519 twin)."""
    sig_ok, marker_ok, r32, s32, pubs_arr, _pub_size_ok = _parse_items(items, n)
    ks, key_idx, pub_ok = get_keyset([it[0] for it in items])
    pub_ok = pub_ok & ks.valid[key_idx]
    s_ok = sc.lt_l(s32)
    # R must be a canonical ristretto encoding: s < p and s even (the square
    # test runs on device inside the decode).
    r_ok = _lt_p(r32) & ((r32[:, 0] & 1) == 0)
    valid = sig_ok & marker_ok & s_ok & r_ok & pub_ok

    pallas = edb._use_pallas()
    with _scalars_span(n):
        k32 = challenges([it[1] for it in items], pubs_arr, r32)
        if not pallas:  # the Pallas chunk cuts its windows on the device
            k_win = sc.comb_windows(k32).astype(np.int32)
            s_win = sc.comb_windows(s32).astype(np.int32)

    if pallas:
        # One fixed CHUNK-lane executable through the ed25519 twin's chunk
        # loop (ed25519_pallas.launch_chunks): raw bytes up, the per-key
        # niels rows gathered by row number, packed pieces of a bitmap back;
        # on the "sharded" route a chunk a local device.
        from tendermint_tpu.ops import ed25519_pallas as edp

        def launch(devices=()):
            return edp.launch_chunks(
                "jit__sr_verify_chunk", edp._sr_verify_chunk, ks, key_idx, n,
                lambda sl: (k32[sl], s32[sl], r32[sl], valid[sl]), devices)

        return edp.dispatch_chunks("sr25519", n, launch, multichip)

    r_limbs = _bytes_to_limbs(r32)
    # No TPU backend: fixed-tile chunking through the one JNP_TILE-shaped
    # executable of the jnp kernel.
    tile = edb.JNP_TILE
    nb = max(edb._round_up(n, tile), tile)
    idx = np.zeros((nb,), dtype=np.int32)
    idx[:n] = key_idx

    def pad(v):
        out = np.zeros((nb,) + v.shape[1:], dtype=v.dtype)
        out[:n] = v
        return out

    kw, sw, rl, va = pad(k_win), pad(s_win), pad(r_limbs), pad(valid)
    outs = []
    for off in range(0, nb, tile):
        with edb.launch_span("jit__sr_verify_kernel", "jnp", n - off, tile):
            tab = ks.take(idx[off:off + tile])
            outs.append(_kernel(
                tab,
                jnp.asarray(kw[off:off + tile]),
                jnp.asarray(sw[off:off + tile]),
                jnp.asarray(rl[off:off + tile]),
                jnp.asarray(va[off:off + tile]),
            ))
    ok = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    edb._start_host_copy(ok)
    return ok, _cbreaker.routed(lambda v: np.asarray(v)[:n], "jnp")


def _device_probe() -> bool:
    """Circuit-breaker probe: one real signature through the device route
    (breaker background thread, never the consensus path); fires its own
    fault site, ops.sr25519.probe."""
    faults.fire("ops.sr25519.probe")
    priv = srref.gen_priv_key(b"\x7c" * 32)
    items = [(priv.pub_key().data, b"breaker-probe",
              srref.sign(priv.data, b"breaker-probe"))]
    dev, finish = _dispatch_device(items, 1)
    return bool(np.all(finish(jax.device_get(dev))))


BREAKER = _cbreaker.CircuitBreaker("sr25519-device", probe=_device_probe)


def dispatch_batch(items: list[tuple[bytes, bytes, bytes]],
                   force_device: bool = False):
    """Async batched verify (same contract as ed25519_batch.dispatch_batch):
    returns (device_out, finish) with nothing fetched, so mixed-key commits
    overlap the ed25519 and sr25519 readbacks in one device_get. The route
    is edb.route_batch's, one decision for both key types; the device route
    sits behind the same circuit-breaker degradation as the ed25519 twin."""
    if not items:
        return None, _cbreaker.routed(
            lambda _: np.zeros((0,), dtype=bool), "host_scalar")
    n = len(items)
    route = edb.route_batch(n, force_device)
    if route == "host":
        # ops/chost does its own ristretto decodes + s<L
        return _host_fallback(items, n)

    def _device():
        faults.fire("ops.sr25519.device")
        return _dispatch_device(items, n, route == "sharded")

    return _cbreaker.guarded_dispatch(
        BREAKER, _device,
        lambda: _host_fallback(items, n, route="breaker_fallback"))


def verify_batch(items: list[tuple[bytes, bytes, bytes]],
                 force_device: bool = False) -> np.ndarray:
    """Batched verify of [(pub, msg, sig)]; returns (len(items),) bool,
    byte-identical accept/reject with crypto/sr25519.verify."""
    dev, finish = dispatch_batch(items, force_device=force_device)
    return _cbreaker.guarded_fetch(
        BREAKER, dev, finish,
        lambda: _host_fallback(items, len(items), route="breaker_fallback"))
