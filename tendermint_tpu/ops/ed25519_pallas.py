"""Pallas TPU kernels for batched ed25519 and sr25519 verification.

Same math as ops/ed25519_batch._verify_kernel (comb evaluation of
[s]B + [h](-A), canonical-encoding compare) but fused into ONE TPU kernel so
the point state never leaves VMEM. sr25519 (ops/sr25519_batch) evaluates the
same comb with its challenge k in place of h, so its kernel is the same loop
(_comb) with another tail: a ristretto255 decode of R and the projective
coset comparison, where ed25519 inverts Z and compares encodings. Layout
choices:

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

The per-key tables themselves are built by a third kernel over the same
field and point helpers (_build_kernel: 192 doublings and 15 additions a
lane, a key a lane), one TILE of keys a launch; edb.KeySet.append is its
caller on a TPU backend.

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

from tendermint_tpu.crypto.sr25519 import SQRT_M1
from tendermint_tpu.ops import breaker as _cbreaker
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import edwards25519 as ed
from tendermint_tpu.ops import field25519 as fe
from tendermint_tpu.ops import scalar25519 as sc_mod
from tendermint_tpu.utils import metrics as tmmetrics
from tendermint_tpu.utils import trace as _trace

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


def _const_rows(v: int) -> np.ndarray:
    return np.asarray(fe.from_int(v % P), dtype=np.int32).reshape(NLIMB, 1)


# The sr25519 kernel's input: CONSTS, then what the ristretto tail needs,
# every element in canonical limbs. Rows 1020-1039 = d, 1040-1059 = sqrt(-1),
# 1060-1079 = -1, 1080-1099 = -sqrt(-1).
SR_CONSTS = np.concatenate(
    [CONSTS, _const_rows(ed.D), _const_rows(SQRT_M1), _const_rows(-1),
     _const_rows(-SQRT_M1)], axis=0).astype(np.int32)

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


def _sq_n(t, n: int):
    for _ in range(n):
        t = _sq(t)
    return t


def _sq_n_rolled(t, n: int):
    """_sq_n as a loop on the device: a run of n squarings is traced and
    compiled as one. Same device time (a 4,096-lane sr25519 chunk 4.345 ms
    against 4.342 unrolled on a v5e), a quarter of the tracing and half of
    the compile of a kernel whose tail is unrolled."""
    return jax.lax.fori_loop(0, n, lambda _, x: _sq(x), t)


def _pow_2_250_1(a, sq_n=_sq_n):
    """-> (a^(2^250 - 1), a^11): the curve25519 addition chain up to where
    the inversion and the (p-5)/8 power part."""
    z2 = _sq(a)
    z9 = _mul(a, _sq(_sq(z2)))
    z11 = _mul(z2, z9)
    z_5_0 = _mul(z9, _sq(z11))
    z_10_0 = _mul(sq_n(z_5_0, 5), z_5_0)
    z_20_0 = _mul(sq_n(z_10_0, 10), z_10_0)
    z_40_0 = _mul(sq_n(z_20_0, 20), z_20_0)
    z_50_0 = _mul(sq_n(z_40_0, 10), z_10_0)
    z_100_0 = _mul(sq_n(z_50_0, 50), z_50_0)
    z_200_0 = _mul(sq_n(z_100_0, 100), z_100_0)
    z_250_0 = _mul(sq_n(z_200_0, 50), z_50_0)
    return z_250_0, z11


def _inv(a):
    """a^(p-2) = a^(2^255 - 21)."""
    z_250_0, z11 = _pow_2_250_1(a)
    return _mul(_sq_n(z_250_0, 5), z11)


def _pow_p58(a):
    """a^((p-5)/8) = a^(2^252 - 3)."""
    z_250_0, _ = _pow_2_250_1(a, _sq_n_rolled)
    return _mul(_sq(_sq(z_250_0)), a)


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


def _bind_consts(consts_ref) -> None:
    _CTX["psub"] = consts_ref[0:20, :]
    _CTX["p_canon"] = consts_ref[20:40, :]
    _CTX["two_d"] = consts_ref[40:60, :]


def _comb(consts_ref, tab_ref, a_win_ref, b_win_ref):
    """[b]B + [a](-A) per lane, extended coordinates: 64 x (double, mixed
    add of the -A comb point window a selects from the gathered niels rows,
    mixed add of the B comb point window b selects from the constants). The
    loop both kernels run; binds the field helpers' constants."""
    t = TILE
    _bind_consts(consts_ref)

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
        wh = a_win_ref[pl.ds(j, 1), :]  # (1, T)
        ws = b_win_ref[pl.ds(j, 1), :]
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

    return jax.lax.fori_loop(0, 64, body, identity)


def _kernel(consts_ref, tab_ref, h_win_ref, s_win_ref, r_y_ref, r_sv_ref, ok_ref):
    acc = _comb(consts_ref, tab_ref, h_win_ref, s_win_ref)

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


def _is(a_canon, b_canon):
    """(1, T) bool: two canonical elements are equal (b may be (20, 1))."""
    return jnp.all(a_canon == b_canon, axis=0, keepdims=True)


def _is_zero(a):
    return _is(_to_canonical(a), 0)


def _abs(a):
    """|a|: the canonical representative, negated where it is odd."""
    c = _to_canonical(a)
    return jnp.where((c[0:1] & 1) != 0, _sub(jnp.zeros_like(c), c), c)


def _ristretto_decode(s, consts_ref):
    """(20, T) limbs of the 32-byte encoding (the host has rejected s >= p
    and odd s) -> (x, y, ok (1, T) bool). RFC 9496 4.3.1, the arithmetic of
    sr25519_batch._ristretto_decode_dev lane for lane, with two of its steps
    left out because they cannot change an answer: SQRT_RATIO_M1 runs with
    u = 1 (the two multiplications by u), and its result is not made
    non-negative (y takes invsqrt squared and x is made non-negative
    itself)."""
    d = consts_ref[1020:1040, :]
    sqrt_m1 = consts_ref[1040:1060, :]
    zero = jnp.zeros_like(s)
    one = jnp.concatenate([jnp.ones_like(s[0:1]), zero[1:]], axis=0)
    ss = _sq(s)
    u1 = _sub(one, ss)
    u2 = _add(one, ss)
    u2_sqr = _sq(u2)
    # v = -(d * u1^2) - u2^2
    v = _sub(zero, _add(_mul(_mul(u1, d), u1), u2_sqr))
    # invsqrt = 1/sqrt(w) up to sign, was_square: w is a square
    w = _mul(v, u2_sqr)
    w3 = _mul(_sq(w), w)
    w7 = _mul(_sq(w3), w)
    r = _mul(w3, _pow_p58(w7))
    check = _to_canonical(_mul(w, _sq(r)))
    flipped = _is(check, consts_ref[1060:1080, :])  # check == -1
    flipped_i = _is(check, consts_ref[1080:1100, :])  # check == -sqrt(-1)
    was_square = _is(check, one) | flipped
    invsqrt = jnp.where(flipped | flipped_i, _mul(r, sqrt_m1), r)
    den_x = _mul(invsqrt, u2)
    den_y = _mul(_mul(invsqrt, den_x), v)
    x = _abs(_mul(_dbl_limb(s), den_x))
    y = _mul(u1, den_y)
    t_even = (_to_canonical(_mul(x, y))[0:1] & 1) == 0
    return x, y, was_square & t_even & jnp.logical_not(_is_zero(y))


def _sr_kernel(consts_ref, tab_ref, k_win_ref, s_win_ref, r_ref, valid_ref, ok_ref):
    """sr25519: R' = [s]B + [k](-A) must equal R as ristretto points."""
    X, Y, _, _ = _comb(consts_ref, tab_ref, k_win_ref, s_win_ref)
    x_r, y_r, ok_r = _ristretto_decode(r_ref[:, :], consts_ref)
    # coset equality of R' = (X:Y:Z) and R = (x_r, y_r), projective:
    # X*y_r == Y*x_r or Y*y_r == X*x_r (RFC 9496 4.5; Z cancels, so no
    # inversion)
    e1 = _is_zero(_sub(_mul(X, y_r), _mul(Y, x_r)))
    e2 = _is_zero(_sub(_mul(Y, y_r), _mul(X, x_r)))
    ok = (e1 | e2) & ok_r & (valid_ref[:, :] != 0)
    ok_ref[:, :] = ok.astype(jnp.int32)


def _pallas_call(kernel, consts: np.ndarray, rows: tuple, args, interpret,
                 out_rows: int = 1, scratch_rows: tuple = ()):
    """One launch of `kernel` over TILE-lane grid steps: `consts` whole at
    every step, each of `args` ((rows[i], N), N a multiple of TILE) a tile
    at a time -> (out_rows, N) int32 (the verify kernels' ok lanes: one
    row). `scratch_rows`: a (rows, TILE) VMEM buffer each, handed to the
    kernel after its output."""
    n = args[0].shape[1]
    grid = (n // TILE,)

    def spec(r):
        return pl.BlockSpec((r, TILE), lambda i: (0, i), memory_space=pltpu.VMEM)

    consts_spec = pl.BlockSpec(
        (consts.shape[0], 1), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((out_rows, n), jnp.int32),
        grid=grid,
        in_specs=[consts_spec] + [spec(r) for r in rows],
        out_specs=spec(out_rows),
        scratch_shapes=[pltpu.VMEM((r, TILE), jnp.int32)
                        for r in scratch_rows],
        interpret=interpret,
    )(jnp.asarray(consts), *args)


def _pallas_verify(tab, h_win, s_win, r_y, r_sv, *, interpret=False):
    """tab (960,N) niels rows, h_win (64,N), s_win (64,N), r_y (20,N),
    r_sv (2,N) -> ok (1, N) int32. N must be a multiple of TILE."""
    return _pallas_call(_kernel, CONSTS, (960, 64, 64, 20, 2),
                        (tab, h_win, s_win, r_y, r_sv), interpret)


def _pallas_sr_verify(tab, k_win, s_win, r_limbs, valid, *, interpret=False):
    """tab (960,N) niels rows of -A, k_win (64,N), s_win (64,N), r_limbs
    (20,N) limbs of R's encoding, valid (1,N) -> ok (1, N) int32."""
    return _pallas_call(_sr_kernel, SR_CONSTS, (960, 64, 64, 20, 1),
                        (tab, k_win, s_win, r_limbs, valid), interpret)


# --- the key table's build ----------------------------------------------------

_PT_ROWS = 4 * NLIMB  # an extended point (X, Y, Z, T) as rows of one lane


def _pt_split(rows):
    """(80, T) rows of a point -> the (X, Y, Z, T) tuple the point ops take."""
    return tuple(rows[NLIMB * c : NLIMB * (c + 1)] for c in range(4))


def _pt_rows(k):
    """The rows of the k-th point of a ref of points ((80 * points, T)),
    k traced."""
    return pl.ds(pl.multiple_of(k * _PT_ROWS, 8), _PT_ROWS)


def _build_kernel(consts_ref, a_ref, tab_ref, ps_ref):
    """The comb table of the point a lane holds: a_ref (80, T) extended
    limbs of -A -> tab_ref (1280, T), entry w = sum_j w_j [2^(64j)](-A) in
    extended coordinates. edb._build_comb_tables_impl lane-major, operation
    for operation: ps[j + 1] = 64 doublings of ps[j] (kept in the scratch
    ps_ref, (320, T)), then T[w] = T[w ^ lsb(w)] + ps[log2 lsb(w)] for
    w = 1..15 from T[0], the identity (the addition is complete, so the
    identity takes part like any point, and so does a padding lane that
    holds it). No inversion: the niels form stays edb._to_niels's."""
    _bind_consts(consts_ref)
    ps_ref[0:_PT_ROWS, :] = a_ref[:, :]

    def dbl64(j, carry):
        p = jax.lax.fori_loop(0, 64, lambda _, q: _pt_double(q),
                              _pt_split(ps_ref[_pt_rows(j), :]))
        ps_ref[_pt_rows(j + 1), :] = jnp.concatenate(p, axis=0)
        return carry

    jax.lax.fori_loop(0, 3, dbl64, 0)

    zero = jnp.zeros((NLIMB, TILE), dtype=jnp.int32)
    one = jnp.concatenate(
        [jnp.ones((1, TILE), dtype=jnp.int32), zero[1:]], axis=0)
    tab_ref[0:_PT_ROWS, :] = jnp.concatenate([zero, one, one, zero], axis=0)

    def entry(w, carry):
        lsb = w & -w
        j = (lsb >> 1) - (lsb >> 3)  # log2 of 1, 2, 4, 8
        p = _pt_add(_pt_split(tab_ref[_pt_rows(w ^ lsb), :]),
                    _pt_split(ps_ref[_pt_rows(j), :]))
        tab_ref[_pt_rows(w), :] = jnp.concatenate(p, axis=0)
        return carry

    jax.lax.fori_loop(1, 16, entry, 0)


@functools.partial(jax.jit, static_argnames="interpret")
def _build_comb_lanes(a_neg, interpret=False):
    """(N, 4, 20) extended limbs of -A, N a multiple of TILE -> (N, 16, 4, 20)
    comb tables, row-major as edb.KeySet keeps them: one launch of
    _build_kernel over N lanes, the transposes in and out around it (the
    program runs at N = TILE; the tests and probes may ask for more).
    `interpret` is for the tests, which run the kernel body on the CPU."""
    n = a_neg.shape[0]
    out = _pallas_call(_build_kernel, CONSTS[:60], (_PT_ROWS,),
                       (a_neg.reshape(n, _PT_ROWS).T,), interpret,
                       out_rows=16 * _PT_ROWS, scratch_rows=(4 * _PT_ROWS,))
    return out.T.reshape(n, 16, 4, NLIMB)


def build_comb_tile(a_neg: np.ndarray):
    """(k, 4, 20) limbs of -A, k <= TILE -> (TILE, 16, 4, 20) comb tables on
    the device, the lanes past the keys holding the identity's: what
    edb.KeySet.append launches a KEY_TILE of new keys on a TPU backend, in
    the place of edb._build_comb_tables_tiled."""
    return _build_comb_lanes(jnp.asarray(edb.pad_identity(a_neg, TILE)))


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


@functools.partial(jax.jit, static_argnames="interpret")
def _sr_verify_chunk(tab, k32, s32, r32, valid, interpret=False):
    """One fixed-shape chunk of sr25519: tab (960, CHUNK) int32 device-resident
    niels tables of -A; k32 (32, CHUNK) uint8 merlin challenges reduced mod L
    by the host; s32 (marker bit stripped) / r32 (32, CHUNK) uint8;
    valid (1, CHUNK) uint8. Comb windows and R's limbs are XLA ops, as in
    _verify_chunk; R is below p (or its lane is not valid), so its sign bit
    is clear and its y limbs are the whole encoding. `interpret` is for the
    tests, which run the kernel body on the CPU."""
    r_limbs, _ = _r_limbs_device(r32)
    return _pallas_sr_verify(tab, _windows_device(k32), _windows_device(s32),
                             r_limbs, valid.astype(jnp.int32),
                             interpret=interpret)


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


def pad_rows(key_idx: np.ndarray) -> np.ndarray:
    """A chunk's row numbers, padded to CHUNK with row 0."""
    idx = np.zeros((CHUNK,), dtype=np.int32)
    idx[: len(key_idx)] = key_idx
    return idx


def pad_cols(x: np.ndarray) -> np.ndarray:
    """(n, rows) bytes or (n,) flags of a chunk's n signatures -> (rows or 1,
    CHUNK) uint8, a signature a lane, as the chunk programs take them."""
    out = np.zeros((x.shape[1] if x.ndim == 2 else 1, CHUNK), dtype=np.uint8)
    out[:, : len(x)] = x.T if x.ndim == 2 else x[None, :]
    return out


def launch_chunks(program: str, chunk_fn, ks, key_idx: np.ndarray, n: int,
                  columns, devices=()):
    """The chunk loop of both key types: CHUNK signatures at a time, the
    chunk's four byte arrays (`columns(sl)`, host work that runs while the
    device computes the chunk before) go up, its niels rows are gathered,
    and the jitted `chunk_fn` (XLA module `program`) is enqueued. Nothing is
    fetched: -> the packed pieces of the bitmap, a tuple that one
    `device_get` brings back and :func:`unpack_pieces` joins in chunk order.

    With `devices` (the local devices of the "sharded" route,
    :func:`dispatch_chunks`) chunk k is placed on devices[k mod ndev], from
    devices[0] in every call: its arrays are put there, the rows come from
    that device's copy of the table, the same program runs there and packs
    its own piece, so the chunks of a batch run on their chips at the same
    time. devices[0] is where unplaced arrays go, so a chunk for it is
    enqueued exactly as on a one-chip host. Without `devices` (or with one)
    nothing is placed: one concatenate, one pack, as ever."""
    place = len(devices) > 1
    route = "sharded" if place else "pallas"
    outs = []
    for k, off in enumerate(range(0, n, CHUNK)):
        sl = slice(off, min(off + CHUNK, n))
        cols = columns(sl)
        # None: where unplaced arrays go (devices[0]), no placement call
        at = k % len(devices) if place else 0
        device = devices[at] if at else None
        put = (jnp.asarray if device is None
               else functools.partial(jax.device_put, device=device))
        with edb.launch_span(program, route, sl.stop - sl.start, CHUNK, device):
            first = put(pad_cols(cols[0]))
            tab = ks.gathered_lane(pad_rows(key_idx[sl]), device)
            outs.append(chunk_fn(
                tab, first, *(put(pad_cols(c)) for c in cols[1:])))
    if place:
        return tuple(pack_bitmap(o) for o in outs)
    return (pack_bitmap(
        outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)),)


def unpack_pieces(fetched, n: int) -> np.ndarray:
    """The fetched pieces of :func:`launch_chunks` -> (n,) bool."""
    pieces = [np.asarray(p) for p in fetched]
    return unpack_bitmap(
        pieces[0] if len(pieces) == 1 else np.concatenate(pieces), n)


def dispatch_chunks(kind: str, n: int, launch, multichip: bool):
    """The (device_out, finish) of the dispatch contract for a key type's
    chunk loop: `launch(devices=())` is :func:`launch_chunks` with all but
    the devices bound. The "sharded" route (`multichip`) hands it all the
    local devices, chunk k on device k mod ndev, inside the
    verify.shard_dispatch span, and counts the dispatch on
    verify_sharded_total by the devices used: the same jitted programs as
    on one chip, bit for bit the same pieces, no collective. Either way
    every piece's host copy starts now."""
    if multichip:
        devices = tuple(jax.local_devices())
        chunks = -(-n // CHUNK)
        used = min(chunks, len(devices))
        with (_trace.current().span("verify.shard_dispatch", kind=kind, n=n,
                                    chunks=chunks, devices=used)
              if _trace.ENABLED else _trace.NULL_SPAN):
            dev = launch(devices)
        if tmmetrics.GLOBAL_NODE_METRICS is not None:
            tmmetrics.GLOBAL_NODE_METRICS.verify_sharded.add(devices=used)
    else:
        dev = launch()
    edb._start_host_copy(dev)
    return dev, _cbreaker.routed(lambda v: unpack_pieces(v, n),
                                 "sharded" if multichip else "pallas")


def dispatch_items_pipelined(ks, key_idx: np.ndarray, items, pub_ok,
                             devices=()):
    """Chunk-pipelined ed25519 dispatch: host prep of chunk i+1 (the
    hashing) overlaps device compute of chunk i (dispatches are async), on
    one device or, with `devices`, a chunk a device. -> the packed pieces
    of :func:`launch_chunks`, nothing fetched -- callers batch the readback."""

    def columns(sl):
        s = edb.prepare_scalars(items[sl], pub_ok[sl], windows=False,
                                reduce=False)
        return s["h64"], s["s32"], s["r32"], s["valid"]

    return launch_chunks("jit__verify_chunk", _verify_chunk, ks, key_idx,
                         len(items), columns, devices)
