"""Pallas TPU kernel for batched ed25519 verification.

Same math as ops/ed25519_batch._verify_kernel (comb evaluation of
[s]B + [h](-A), canonical-encoding compare) but fused into ONE TPU kernel so
the point state never leaves VMEM. Layout choices:

 * batch on the LANE axis: field elements are (20, T) int32 tiles (limb rows
   x T signatures), so every field op is a full-width VPU op. The jnp path's
   (N, 20) layout wastes 108 of 128 lanes.
 * vectorized carries: instead of a 20-step sequential carry chain, each pass
   computes all carries at once and shifts them down one limb row (with the
   2^260 === 608 fold wrapping row 19 -> row 0). Pass counts per op are fixed
   by worst-case bound analysis (see _carry_n).
 * per-key comb tables come in NIELS form (16 entries x 3 field elements
   y+x | y-x | 2dxy = 60 rows/entry, 960 rows x T lanes), gathered from the
   device-resident per-key table (edb.KeySet) by row number - nothing per-key
   is rebuilt per call, and each table addition is a 7-mul mixed add. The
   fixed-base comb table for B is baked in as niels constants the same way.

Bound discipline matches ops/field25519: all stored limbs < 9500, products
and 20-term accumulations stay below 2^31 in int32 (squaring's doubled
cross-products included: 10 * 9500 * 19000 + 9500^2 + fold < 2^31).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import edwards25519 as ed
from tendermint_tpu.ops import field25519 as fe
from tendermint_tpu.ops import scalar25519 as sc_mod

MASK = fe.MASK
FOLD = fe.FOLD
NLIMB = fe.NLIMB
P = fe.P
# Lanes per grid step (multiple of 128). 256 measured best on v5e; larger
# tiles spill VMEM (TILE=512 benched 2.6x slower end to end).
TILE = 256

_PSUB = np.asarray(fe.PSUB_LIMBS, dtype=np.int32).reshape(NLIMB, 1)
_P_CANON = np.asarray(fe.P_LIMBS, dtype=np.int32).reshape(NLIMB, 1)
_TWO_D = np.asarray(fe.from_int(2 * ed.D % P), dtype=np.int32).reshape(NLIMB, 1)


# Fixed-base niels comb table: TAB_B[w] = (y+x, y-x, 2dxy) of the comb point
# sum_j w_j [2^(64j)] B (shared with the jnp path's extended-coordinate form).
def _build_b_niels() -> np.ndarray:
    out = np.zeros((16, 3, NLIMB), dtype=np.int32)
    for w, (x, y) in enumerate(edb._B_COMB_AFFINE):
        out[w, 0] = fe.from_int((y + x) % P)
        out[w, 1] = fe.from_int((y - x) % P)
        out[w, 2] = fe.from_int(2 * ed.D * x * y % P)
    return out


_TAB_B = _build_b_niels()

# Pallas kernels may not capture array constants; everything per-lane-uniform
# is packed into one (1020, 1) int32 input: rows 0-19 = 64p limbs, 20-39 =
# canonical p limbs, 40-59 = 2d limbs, 60-1019 = the 16x3x20 B niels table.
CONSTS = np.concatenate(
    [_PSUB, _P_CANON, _TWO_D, _TAB_B.reshape(960, 1)], axis=0
).astype(np.int32)

# Trace-time context: set at kernel entry to slices of the consts ref so the
# field helpers below can use them without captures.
_CTX: dict = {}


# --- field ops on (20, T) int32 values --------------------------------------


def _carry_n(e, n: int):
    """n vectorized carry passes. Each pass: split rows into low 13 bits +
    carries, shift carries down one row, fold row-19 carry into row 0 by 608.

    Pass counts (worst-case bound analysis, mirrors ops/field25519 docstring):
      mul/sq output (<= 1.95e9): 4 passes -> rows <= 8799
      sub output (<= 25881):  2 passes -> rows <= 8799
      2x  output (<= 17598):  1 pass   -> rows <= 9407
      add output (<= 19000):  1 pass   -> rows <= 9407
    """
    for _ in range(n):
        c = e >> 13
        e = e & MASK
        e = e + jnp.concatenate([c[19:20] * FOLD, c[:19]], axis=0)
    return e


def _fold39(conv):
    """(39, T) convolution -> carried (20, T) via the 2^260 === 608 fold."""
    t = conv.shape[1]
    zrow = jnp.zeros((1, t), dtype=jnp.int32)
    c = conv[:NLIMB]
    d = conv[NLIMB:]
    lo = d & MASK
    hi = d >> 13
    c = c + jnp.concatenate([FOLD * lo, zrow], axis=0)
    c = c + jnp.concatenate([zrow, FOLD * hi], axis=0)
    return _carry_n(c, 4)


def _mul(a, b):
    """(20,T) x (20,T) -> (20,T), inputs NORM (<9500), output <= 8799.

    Shift-accumulate via concatenation (Pallas TPU lowering has no scatter;
    static concats lower cleanly)."""
    t = a.shape[1]
    zrow = jnp.zeros((1, t), dtype=jnp.int32)
    conv = None
    for i in range(NLIMB):
        prod = a[i : i + 1] * b  # (20, T)
        shifted = jnp.concatenate(
            [zrow] * i + [prod] + [zrow] * (NLIMB - 1 - i), axis=0
        )  # (39, T)
        conv = shifted if conv is None else conv + shifted
    return _fold39(conv)


def _sq(a):
    """Dedicated squaring: ~half the multiplies of _mul via doubled
    cross-products. Bound: worst conv coeff <= 10*9500*19000 + 9500^2 =
    1.895e9; + fold terms < 1.45e8 -> < 2.04e9 < 2^31."""
    t = a.shape[1]
    zrow = jnp.zeros((1, t), dtype=jnp.int32)
    a2 = a * 2  # limbs <= 19000, no carry needed before the products
    conv = None
    for i in range(NLIMB):
        # rows i+i .. i+19: a_i * [a_i, 2a_{i+1}, ..., 2a_{19}]
        parts = [a[i : i + 1]]
        if i + 1 < NLIMB:
            parts.append(a2[i + 1 :])
        row = jnp.concatenate(parts, axis=0)  # (20 - i, T)
        prod = a[i : i + 1] * row
        shifted = jnp.concatenate(
            [zrow] * (2 * i) + [prod] + [zrow] * (NLIMB - 1 - i), axis=0
        )  # (39, T)
        conv = shifted if conv is None else conv + shifted
    return _fold39(conv)


def _add(a, b):
    return _carry_n(a + b, 1)


def _sub(a, b):
    """a + 64p(fat limbs, every limb >= 9500) - b: limb-wise non-negative."""
    return _carry_n(a + _CTX["psub"] - b, 2)


def _dbl_limb(a):
    return _carry_n(a * 2, 1)


# --- point ops: points are (X, Y, Z, T) tuples of (20, T) -------------------


def _pt_double(p):
    X, Y, Z, _ = p
    a = _sq(X)
    b = _sq(Y)
    c = _dbl_limb(_sq(Z))
    h = _add(a, b)
    e = _sub(h, _sq(_add(X, Y)))
    g = _sub(a, b)
    f = _add(c, g)
    return (_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h))


def _pt_add(p, q):
    """Complete extended addition (both operands full points)."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    a = _mul(_sub(Y1, X1), _sub(Y2, X2))
    b = _mul(_add(Y1, X1), _add(Y2, X2))
    c = _mul(_mul(T1, T2), _CTX["two_d"])
    d = _dbl_limb(_mul(Z1, Z2))
    e = _sub(b, a)
    f = _sub(d, c)
    g = _add(d, c)
    h = _add(b, a)
    return (_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h))


def _pt_madd_niels(p, ypx, ymx, txy2d):
    """Mixed add with a niels-form affine point (y+x, y-x, 2dxy): 7 muls."""
    X1, Y1, Z1, T1 = p
    a = _mul(_sub(Y1, X1), ymx)
    b = _mul(_add(Y1, X1), ypx)
    c = _mul(T1, txy2d)
    d = _dbl_limb(Z1)
    e = _sub(b, a)
    f = _sub(d, c)
    g = _add(d, c)
    h = _add(b, a)
    return (_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h))


def _select16(w, table_rows):
    """Per-lane 16-way select via a 4-level binary where-tree (15 selects vs
    31 multiply-accumulate ops). w: (1, T) window index; table_rows: list of
    16 (rows, T')-broadcastable arrays."""
    cur = list(table_rows)
    for bit in range(4):
        m = ((w >> bit) & 1) != 0  # (1, T) bool
        cur = [jnp.where(m, cur[k + 1], cur[k]) for k in range(0, len(cur), 2)]
    return cur[0]


def _inv(a):
    z2 = _sq(a)
    z9 = _mul(a, _sq(_sq(z2)))
    z11 = _mul(z2, z9)
    z_5_0 = _mul(z9, _sq(z11))
    t = z_5_0
    for _ in range(5):
        t = _sq(t)
    z_10_0 = _mul(t, z_5_0)
    t = z_10_0
    for _ in range(10):
        t = _sq(t)
    z_20_0 = _mul(t, z_10_0)
    t = z_20_0
    for _ in range(20):
        t = _sq(t)
    z_40_0 = _mul(t, z_20_0)
    t = z_40_0
    for _ in range(10):
        t = _sq(t)
    z_50_0 = _mul(t, z_10_0)
    t = z_50_0
    for _ in range(50):
        t = _sq(t)
    z_100_0 = _mul(t, z_50_0)
    t = z_100_0
    for _ in range(100):
        t = _sq(t)
    z_200_0 = _mul(t, z_100_0)
    t = z_200_0
    for _ in range(50):
        t = _sq(t)
    z_250_0 = _mul(t, z_50_0)
    t = z_250_0
    for _ in range(5):
        t = _sq(t)
    return _mul(t, z11)


def _to_canonical(a):
    for _ in range(2):
        top = a[19:20]
        a = jnp.concatenate([a[0:1] + (top >> 8) * 19, a[1:19], top & 0xFF], axis=0)
        a = _carry_n(a, 2)
    p_limbs = _CTX["p_canon"]
    for _ in range(2):
        # a - p with borrow propagation (sequential over 20 rows)
        rows = []
        borrow = jnp.zeros_like(a[0:1])
        for k in range(NLIMB):
            v = a[k : k + 1] - p_limbs[k : k + 1] - borrow
            borrow = (v < 0).astype(jnp.int32)
            rows.append(v + borrow * (MASK + 1))
        diff = jnp.concatenate(rows, axis=0)
        a = jnp.where(borrow == 0, diff, a)
    return a


# --- the kernel --------------------------------------------------------------


def _kernel(consts_ref, tab_ref, h_win_ref, s_win_ref, r_y_ref, r_sv_ref, ok_ref):
    t = TILE
    _CTX["psub"] = consts_ref[0:20, :]
    _CTX["p_canon"] = consts_ref[20:40, :]
    _CTX["two_d"] = consts_ref[40:60, :]

    zero = jnp.zeros((20, t), dtype=jnp.int32)
    one = jnp.concatenate(
        [jnp.ones((1, t), dtype=jnp.int32), jnp.zeros((19, t), dtype=jnp.int32)], axis=0
    )
    identity = (zero, one, one, zero)

    def tab_b(k: int, f: int):
        base = 60 + (k * 3 + f) * 20
        return consts_ref[base : base + 20, :]  # (20, 1)

    def body(j, acc):
        acc = _pt_double(acc)
        wh = h_win_ref[pl.ds(j, 1), :]  # (1, T)
        ws = s_win_ref[pl.ds(j, 1), :]
        # comb point of -A: 16-way select over the gathered per-key NIELS
        # table (60 rows/entry; mixed add = 7 muls vs 9 for extended add)
        rows = [tab_ref[k * 60 : k * 60 + 60, :] for k in range(16)]
        pa = _select16(wh, rows)
        acc = _pt_madd_niels(acc, pa[0:20], pa[20:40], pa[40:60])
        # comb point of B from niels constants ((20,1) broadcast over lanes)
        ypx = _select16(ws, [tab_b(k, 0) for k in range(16)])
        ymx = _select16(ws, [tab_b(k, 1) for k in range(16)])
        txy = _select16(ws, [tab_b(k, 2) for k in range(16)])
        acc = _pt_madd_niels(acc, ypx, ymx, txy)
        return acc

    acc = jax.lax.fori_loop(0, 64, body, identity)

    zinv = _inv(acc[2])
    x = _to_canonical(_mul(acc[0], zinv))
    y = _to_canonical(_mul(acc[1], zinv))
    sign = x[0:1] & 1

    r_y = r_y_ref[:, :]
    r_sign = r_sv_ref[0:1, :]
    valid = r_sv_ref[1:2, :]
    y_eq = jnp.all(y == r_y, axis=0, keepdims=True)
    ok = y_eq & (sign == r_sign) & (valid != 0)
    ok_ref[:, :] = ok.astype(jnp.int32)


def _pallas_verify(tab, h_win, s_win, r_y, r_sv, *, interpret=False):
    """tab (960,N) niels rows, h_win (64,N), s_win (64,N), r_y (20,N),
    r_sv (2,N) -> ok (1, N) int32. N must be a multiple of TILE."""
    n = tab.shape[1]
    grid = (n // TILE,)

    def spec(rows):
        return pl.BlockSpec((rows, TILE), lambda i: (0, i), memory_space=pltpu.VMEM)

    consts_spec = pl.BlockSpec(
        (CONSTS.shape[0], 1), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        grid=grid,
        in_specs=[consts_spec, spec(960), spec(64), spec(64), spec(20), spec(2)],
        out_specs=spec(1),
        interpret=interpret,
    )(jnp.asarray(CONSTS), tab, h_win, s_win, r_y, r_sv)


def _r_limbs_device(r32):
    """(32, N) uint8 R bytes -> ((20, N) int32 y limbs of bits 0..254,
    (1, N) int32 sign bit). Runs on device (XLA): the host uploads raw bytes,
    keeping the per-call H2D payload small over slow links."""
    b = r32.astype(jnp.int32)
    sign = b[31:32] >> 7
    b = jnp.concatenate(
        [b[:31], b[31:32] & 0x7F, jnp.zeros((2, b.shape[1]), jnp.int32)], axis=0
    )
    limbs = []
    for j in range(NLIMB):
        k, s = divmod(13 * j, 8)
        v = (b[k] >> s) | (b[k + 1] << (8 - s)) | (b[k + 2] << (16 - s))
        limbs.append(v & 0x1FFF)
    return jnp.stack(limbs), sign


@jax.jit
def verify_kernel_pallas(tab, h_win, s_win, r32, valid):
    """tab (960, N) int32 (pre-gathered niels tables, device-resident);
    h_win/s_win (64, N) uint8; r32 (32, N) uint8; valid (1, N) uint8.
    -> ok (1, N) int32. One upload of packed uint8 per call, one readback."""
    hw = h_win.astype(jnp.int32)
    sw = s_win.astype(jnp.int32)
    r_y, sign = _r_limbs_device(r32)
    r_sv = jnp.concatenate([sign, valid.astype(jnp.int32)], axis=0)
    return _pallas_verify(tab, hw, sw, r_y, r_sv)


# --- device-side mod-L reduction (radix-2^12 int32 limbs) -------------------
#
# Mirrors scalar25519.reduce_mod_l exactly (differential-tested) but runs as
# XLA int32 ops on device, so the host uploads the raw 64-byte SHA-512
# digests and pays no per-signature reduction work. Radix 2^12 because
# 2^252 = 2^(12*21) is an exact limb boundary (the fold identity is
# 2^252 === -DELTA mod L) and 12x12-bit products convolved over DELTA's 11
# limbs stay < 2^31 in int32.

_L_RADIX = 12
_L_NLIMB = 43  # 43 * 12 = 516 >= 512 bits
_DELTA12 = np.array(
    [(sc_mod.DELTA >> (_L_RADIX * i)) & 0xFFF for i in range(11)], dtype=np.int32)
assert sum(int(d) << (_L_RADIX * i) for i, d in enumerate(_DELTA12)) == sc_mod.DELTA


def _digest_to_limbs12(d64):
    """(64, T) uint8 digest columns -> (43, T) int32 radix-2^12 limbs."""
    b = d64.astype(jnp.int32)
    limbs = []
    for j in range(_L_NLIMB):
        k, s = divmod(_L_RADIX * j, 8)
        v = b[k] >> s
        if k + 1 < 64:
            v = v | (b[k + 1] << (8 - s))
        if s + _L_RADIX > 16 and k + 2 < 64:
            v = v | (b[k + 2] << (16 - s))
        limbs.append(v & 0xFFF)
    return jnp.stack(limbs)


def _carry_signed12(x, top: int):
    """Sequential signed floor-carry over rows 0..top-1; row top-1 absorbs
    the (possibly negative) residue (mirrors scalar25519._carry_signed_t)."""
    rows = []
    carry = jnp.zeros_like(x[0])
    for k in range(top):
        t = x[k] + carry
        carry = t >> _L_RADIX  # arithmetic shift = floor division
        rows.append(t - (carry << _L_RADIX))
    rows[top - 1] = rows[top - 1] + (carry << _L_RADIX)
    return jnp.stack(rows + [jnp.zeros_like(x[0])] * (x.shape[0] - top))


def _reduce_mod_l_device(d64):
    """(64, T) uint8 LE 512-bit digests -> (22, T) int32 canonical radix-2^12
    limbs of the value mod L. Same 4-fold walk as the host reduce_mod_l
    (v = hi*2^252 + lo -> lo - DELTA*hi, shrinking ~127 bits per fold); each
    fold's hi covers every limb the previous fold's top residual can reach."""
    x = _digest_to_limbs12(d64)
    delta = [int(v) for v in _DELTA12]
    for nhi, top in ((22, 34), (13, 23), (2, 22), (1, 22)):
        hi = x[21:21 + nhi]
        x = jnp.concatenate(
            [x[:21], jnp.zeros_like(hi), x[21 + nhi:]], axis=0)
        # x -= conv(DELTA12, hi): 11 shifted row-block subtractions.
        for i in range(11):
            x = jnp.concatenate(
                [x[:i], x[i:i + nhi] - delta[i] * hi, x[i + nhi:]], axis=0)
        x = _carry_signed12(x, top)
    return x[:22]


def _windows_from_limbs12(limbs):
    """(22, T) canonical radix-2^12 limbs -> (64, T) int32 comb windows in
    processing order (mirrors scalar25519.comb_windows bit-for-bit)."""
    def bit(i):
        return (limbs[i // _L_RADIX] >> (i % _L_RADIX)) & 1

    rows = []
    for idx in range(64):
        j = 63 - idx
        w = bit(j) | (bit(64 + j) << 1) | (bit(128 + j) << 2) | (bit(192 + j) << 3)
        rows.append(w)
    return jnp.stack(rows)


def _windows_device(s32):
    """(32, T) uint8 LE scalars -> (64, T) int32 comb windows in processing
    order (mirrors scalar25519.comb_windows exactly: w_j = b_j + 2 b_{64+j}
    + 4 b_{128+j} + 8 b_{192+j}, emitted j=63..0). Runs as fused XLA bit
    ops so the host uploads 32 raw bytes per scalar instead of 64 window
    bytes and does no per-signature bit work."""
    b = s32.astype(jnp.int32)
    rows = []
    for i in range(64):
        j = 63 - i
        w = None
        for t in range(4):
            k = j + 64 * t
            bit = (b[k // 8] >> (k % 8)) & 1
            w = bit if w is None else w | (bit << t)
        rows.append(w)
    return jnp.stack(rows)


@jax.jit
def _verify_chunk(tab, h64, s32, r32, valid):
    """One fixed-shape chunk: tab (960, CHUNK) int32 device-resident niels
    tables; h64 (64, CHUNK) uint8 RAW SHA-512 digests (mod-L reduction and
    comb windows both run on device); s32/r32 (32, CHUNK) uint8;
    valid (1, CHUNK) uint8."""
    hw = _windows_from_limbs12(_reduce_mod_l_device(h64))
    sw = _windows_device(s32)
    r_y, sign = _r_limbs_device(r32)
    r_sv = jnp.concatenate([sign, valid.astype(jnp.int32)], axis=0)
    return _pallas_verify(tab, hw, sw, r_y, r_sv)


# Fixed dispatch shape: XLA compiles one executable per input shape, so the
# pallas call always runs at a multiple of CHUNK lanes (small batches pad to
# one CHUNK; large ones loop). A fresh batch size must never trigger a cold
# compile inside the consensus loop.
# CHUNK is a multiple of TILE: a non-multiple would silently truncate the
# pallas grid and leave trailing output lanes unwritten.
CHUNK = 16 * TILE  # 4096


@jax.jit
def pack_bitmap(ok):
    """(1, N) int32 pass/fail lanes -> (N//32,) uint32 bitmask on device.
    Shrinks the readback 32x (20,480 lanes: 80 KB -> 2.5 KB); unpacked
    host-side by unpack_bitmap."""
    b = ok.reshape(-1, 32).astype(jnp.uint32)
    w = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return (b * w).sum(axis=1, dtype=jnp.uint32)


def unpack_bitmap(v: np.ndarray, n: int) -> np.ndarray:
    """(N//32,) uint32 -> (n,) bool."""
    bits = (v[:, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(-1)[:n].astype(bool)


def dispatch_items_pipelined(ks, key_idx: np.ndarray, items, pub_ok):
    """Chunk-pipelined dispatch: host prep of chunk i+1 overlaps device
    compute of chunk i (dispatches are async). Returns the (1, Npad) int32
    device array WITHOUT fetching -- callers batch the readback. On the
    1-core host this hides min(prep, device) per chunk versus the
    prep-everything-then-dispatch path."""
    n = len(items)
    outs = []
    for off in range(0, n, CHUNK):
        sl = slice(off, min(off + CHUNK, n))
        s = edb.prepare_scalars(items[sl], pub_ok[sl], windows=False,
                                reduce=False)
        cn = sl.stop - sl.start
        idx = np.zeros((CHUNK,), dtype=np.int32)
        idx[:cn] = key_idx[sl]

        def pad_cols(x, rows):
            out = np.zeros((rows, CHUNK), dtype=np.uint8)
            out[:, :cn] = x.T if x.ndim == 2 else x[None, :]
            return out

        with edb.launch_span("jit__verify_chunk", "pallas", cn, CHUNK):
            h64 = jnp.asarray(pad_cols(s["h64"], 64))
            tab = ks.gathered_lane(idx)
            outs.append(_verify_chunk(
                tab,
                h64,
                jnp.asarray(pad_cols(s["s32"], 32)),
                jnp.asarray(pad_cols(s["r32"], 32)),
                jnp.asarray(pad_cols(s["valid"].astype(np.uint8), 1)),
            ))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
