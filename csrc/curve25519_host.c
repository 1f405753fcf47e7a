/* Host-side curve25519 verification: serial + RLC-batch (Pippenger).
 *
 * WHY THIS EXISTS: the TPU kernel (ops/ed25519_batch) owns large batches,
 * but every kernel flush pays a fixed host<->device round trip and a whole
 * padded chunk, so a flush below the measured crossover LOSES to a CPU.
 * This file is the CPU side of the adaptive crossover
 * (ops/ed25519_batch.host_crossover):
 * a from-scratch C implementation of
 *
 *   - ed25519 verify with semantics byte-identical to the Python reference
 *     (crypto/ed25519.py, itself mirroring Go crypto/ed25519 — reference
 *     crypto/ed25519/ed25519.go:148): S < L, RFC 8032 A decode, accept iff
 *     encode([S]B - [h]A) == sig[:32].
 *   - sr25519 (schnorrkel) verify: ristretto255 decode (RFC 9496),
 *     [s]B - [c]A ~ R under ristretto equality (crypto/sr25519.py:354).
 *   - batch mode: random-linear-combination check
 *         [sum z_i s_i mod L]B + sum [(z_i h_i) mod 8L](-A_i) + [z_i](-R_i)
 *     evaluated with one Pippenger multi-scalar multiplication.
 *     Scalars on A_i are reduced mod 8L (not L): 8L is the group exponent,
 *     so the reduction is exact on torsion components and "each serial
 *     equation holds" => "batch sum is identity" holds UNCONDITIONALLY
 *     (the reverse fails with probability 2^-128 over the z_i).  On batch
 *     mismatch we re-verify serially, so accept/reject decisions delivered
 *     to callers are always identical to the serial path.
 *     For sr25519 the per-item residue lives in the ristretto kernel (a
 *     4-torsion subgroup), so the batch check is [8]S == identity.
 *
 * Field arithmetic: radix-2^51, unsigned __int128 products (the standard
 * public-domain representation).  NOT constant-time — verification inputs
 * are public (pubkeys, messages, signatures); no secrets are processed.
 *
 * Built by tendermint_tpu/ops/chost.py the same way chash.py builds
 * libhashbatch (content-hashed .so name, lazy g++).
 */

#include <stdint.h>
#include <stdlib.h>
#include <string.h>
#include <pthread.h>

typedef unsigned __int128 u128;
typedef uint64_t u64;
typedef uint32_t u32;
typedef uint8_t u8;

/* ------------------------------------------------------------------ */
/* SHA-512 (only for deriving batch coefficients z_i from a seed)      */
/* ------------------------------------------------------------------ */

static const u64 SHA512_K[80] = {
    0x428a2f98d728ae22ULL, 0x7137449123ef65cdULL, 0xb5c0fbcfec4d3b2fULL,
    0xe9b5dba58189dbbcULL, 0x3956c25bf348b538ULL, 0x59f111f1b605d019ULL,
    0x923f82a4af194f9bULL, 0xab1c5ed5da6d8118ULL, 0xd807aa98a3030242ULL,
    0x12835b0145706fbeULL, 0x243185be4ee4b28cULL, 0x550c7dc3d5ffb4e2ULL,
    0x72be5d74f27b896fULL, 0x80deb1fe3b1696b1ULL, 0x9bdc06a725c71235ULL,
    0xc19bf174cf692694ULL, 0xe49b69c19ef14ad2ULL, 0xefbe4786384f25e3ULL,
    0x0fc19dc68b8cd5b5ULL, 0x240ca1cc77ac9c65ULL, 0x2de92c6f592b0275ULL,
    0x4a7484aa6ea6e483ULL, 0x5cb0a9dcbd41fbd4ULL, 0x76f988da831153b5ULL,
    0x983e5152ee66dfabULL, 0xa831c66d2db43210ULL, 0xb00327c898fb213fULL,
    0xbf597fc7beef0ee4ULL, 0xc6e00bf33da88fc2ULL, 0xd5a79147930aa725ULL,
    0x06ca6351e003826fULL, 0x142929670a0e6e70ULL, 0x27b70a8546d22ffcULL,
    0x2e1b21385c26c926ULL, 0x4d2c6dfc5ac42aedULL, 0x53380d139d95b3dfULL,
    0x650a73548baf63deULL, 0x766a0abb3c77b2a8ULL, 0x81c2c92e47edaee6ULL,
    0x92722c851482353bULL, 0xa2bfe8a14cf10364ULL, 0xa81a664bbc423001ULL,
    0xc24b8b70d0f89791ULL, 0xc76c51a30654be30ULL, 0xd192e819d6ef5218ULL,
    0xd69906245565a910ULL, 0xf40e35855771202aULL, 0x106aa07032bbd1b8ULL,
    0x19a4c116b8d2d0c8ULL, 0x1e376c085141ab53ULL, 0x2748774cdf8eeb99ULL,
    0x34b0bcb5e19b48a8ULL, 0x391c0cb3c5c95a63ULL, 0x4ed8aa4ae3418acbULL,
    0x5b9cca4f7763e373ULL, 0x682e6ff3d6b2b8a3ULL, 0x748f82ee5defb2fcULL,
    0x78a5636f43172f60ULL, 0x84c87814a1f0ab72ULL, 0x8cc702081a6439ecULL,
    0x90befffa23631e28ULL, 0xa4506cebde82bde9ULL, 0xbef9a3f7b2c67915ULL,
    0xc67178f2e372532bULL, 0xca273eceea26619cULL, 0xd186b8c721c0c207ULL,
    0xeada7dd6cde0eb1eULL, 0xf57d4f7fee6ed178ULL, 0x06f067aa72176fbaULL,
    0x0a637dc5a2c898a6ULL, 0x113f9804bef90daeULL, 0x1b710b35131c471bULL,
    0x28db77f523047d84ULL, 0x32caab7b40c72493ULL, 0x3c9ebe0a15c9bebcULL,
    0x431d67c49c100d4cULL, 0x4cc5d4becb3e42b6ULL, 0x597f299cfc657e2aULL,
    0x5fcb6fab3ad6faecULL, 0x6c44198c4a475817ULL,
};

static inline u64 rotr64(u64 x, int n) { return (x >> n) | (x << (64 - n)); }

static void sha512_compress(u64 st[8], const u8 blk[128]) {
    u64 w[80];
    for (int i = 0; i < 16; i++) {
        w[i] = ((u64)blk[8 * i] << 56) | ((u64)blk[8 * i + 1] << 48) |
               ((u64)blk[8 * i + 2] << 40) | ((u64)blk[8 * i + 3] << 32) |
               ((u64)blk[8 * i + 4] << 24) | ((u64)blk[8 * i + 5] << 16) |
               ((u64)blk[8 * i + 6] << 8) | (u64)blk[8 * i + 7];
    }
    for (int i = 16; i < 80; i++) {
        u64 s0 = rotr64(w[i - 15], 1) ^ rotr64(w[i - 15], 8) ^ (w[i - 15] >> 7);
        u64 s1 = rotr64(w[i - 2], 19) ^ rotr64(w[i - 2], 61) ^ (w[i - 2] >> 6);
        w[i] = w[i - 16] + s0 + w[i - 7] + s1;
    }
    u64 a = st[0], b = st[1], c = st[2], d = st[3];
    u64 e = st[4], f = st[5], g = st[6], h = st[7];
    for (int i = 0; i < 80; i++) {
        u64 S1 = rotr64(e, 14) ^ rotr64(e, 18) ^ rotr64(e, 41);
        u64 ch = (e & f) ^ (~e & g);
        u64 t1 = h + S1 + ch + SHA512_K[i] + w[i];
        u64 S0 = rotr64(a, 28) ^ rotr64(a, 34) ^ rotr64(a, 39);
        u64 maj = (a & b) ^ (a & c) ^ (b & c);
        u64 t2 = S0 + maj;
        h = g; g = f; f = e; e = d + t1;
        d = c; c = b; b = a; a = t1 + t2;
    }
    st[0] += a; st[1] += b; st[2] += c; st[3] += d;
    st[4] += e; st[5] += f; st[6] += g; st[7] += h;
}

/* sha512 of a short (< 112 byte) message: one padded block */
static void sha512_short(const u8 *msg, size_t len, u8 out[64]) {
    u64 st[8] = {0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL,
                 0x3c6ef372fe94f82bULL, 0xa54ff53a5f1d36f1ULL,
                 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
                 0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};
    u8 blk[128];
    memset(blk, 0, sizeof(blk));
    memcpy(blk, msg, len);
    blk[len] = 0x80;
    u64 bits = (u64)len * 8;
    for (int i = 0; i < 8; i++) blk[127 - i] = (u8)(bits >> (8 * i));
    sha512_compress(st, blk);
    for (int i = 0; i < 8; i++)
        for (int j = 0; j < 8; j++) out[8 * i + j] = (u8)(st[i] >> (56 - 8 * j));
}

/* ------------------------------------------------------------------ */
/* fe25519: radix-2^51 field element                                   */
/* ------------------------------------------------------------------ */

typedef struct { u64 v[5]; } fe;

#define MASK51 ((1ULL << 51) - 1)

/* 2p in radix 2^51: limb0 = 2^52-38, limbs1-4 = 2^52-2 */
#define TWO_P0 0xFFFFFFFFFFFDAULL
#define TWO_P1234 0xFFFFFFFFFFFFEULL

static void fe_zero(fe *h) { memset(h, 0, sizeof(*h)); }
static void fe_one(fe *h) { fe_zero(h); h->v[0] = 1; }

static void fe_add(fe *h, const fe *f, const fe *g) {
    for (int i = 0; i < 5; i++) h->v[i] = f->v[i] + g->v[i];
}

/* h = f - g + 2p (limbwise non-negative for reduced g) */
static void fe_sub(fe *h, const fe *f, const fe *g) {
    h->v[0] = f->v[0] + TWO_P0 - g->v[0];
    for (int i = 1; i < 5; i++) h->v[i] = f->v[i] + TWO_P1234 - g->v[i];
}

static void fe_neg(fe *h, const fe *f) {
    h->v[0] = TWO_P0 - f->v[0];
    for (int i = 1; i < 5; i++) h->v[i] = TWO_P1234 - f->v[i];
}

/* one carry pass; inputs up to ~2^63 per limb are safe */
static void fe_carry(fe *h) {
    u64 c;
    c = h->v[0] >> 51; h->v[0] &= MASK51; h->v[1] += c;
    c = h->v[1] >> 51; h->v[1] &= MASK51; h->v[2] += c;
    c = h->v[2] >> 51; h->v[2] &= MASK51; h->v[3] += c;
    c = h->v[3] >> 51; h->v[3] &= MASK51; h->v[4] += c;
    c = h->v[4] >> 51; h->v[4] &= MASK51; h->v[0] += c * 19;
    c = h->v[0] >> 51; h->v[0] &= MASK51; h->v[1] += c;
}

static void fe_mul(fe *h, const fe *f, const fe *g) {
    u64 f0 = f->v[0], f1 = f->v[1], f2 = f->v[2], f3 = f->v[3], f4 = f->v[4];
    u64 g0 = g->v[0], g1 = g->v[1], g2 = g->v[2], g3 = g->v[3], g4 = g->v[4];
    u64 g1_19 = 19 * g1, g2_19 = 19 * g2, g3_19 = 19 * g3, g4_19 = 19 * g4;
    u128 h0 = (u128)f0 * g0 + (u128)f1 * g4_19 + (u128)f2 * g3_19 +
              (u128)f3 * g2_19 + (u128)f4 * g1_19;
    u128 h1 = (u128)f0 * g1 + (u128)f1 * g0 + (u128)f2 * g4_19 +
              (u128)f3 * g3_19 + (u128)f4 * g2_19;
    u128 h2 = (u128)f0 * g2 + (u128)f1 * g1 + (u128)f2 * g0 +
              (u128)f3 * g4_19 + (u128)f4 * g3_19;
    u128 h3 = (u128)f0 * g3 + (u128)f1 * g2 + (u128)f2 * g1 +
              (u128)f3 * g0 + (u128)f4 * g4_19;
    u128 h4 = (u128)f0 * g4 + (u128)f1 * g3 + (u128)f2 * g2 +
              (u128)f3 * g1 + (u128)f4 * g0;
    u64 c;
    u64 r0 = (u64)h0 & MASK51; h1 += (u64)(h0 >> 51);
    u64 r1 = (u64)h1 & MASK51; h2 += (u64)(h1 >> 51);
    u64 r2 = (u64)h2 & MASK51; h3 += (u64)(h2 >> 51);
    u64 r3 = (u64)h3 & MASK51; h4 += (u64)(h3 >> 51);
    u64 r4 = (u64)h4 & MASK51; r0 += (u64)(h4 >> 51) * 19;
    c = r0 >> 51; r0 &= MASK51; r1 += c;
    h->v[0] = r0; h->v[1] = r1; h->v[2] = r2; h->v[3] = r3; h->v[4] = r4;
}

static void fe_sq(fe *h, const fe *f) {
    u64 f0 = f->v[0], f1 = f->v[1], f2 = f->v[2], f3 = f->v[3], f4 = f->v[4];
    u64 f0_2 = 2 * f0, f1_2 = 2 * f1, f2_2 = 2 * f2, f3_2 = 2 * f3;
    u64 f3_19 = 19 * f3, f4_19 = 19 * f4;
    u128 h0 = (u128)f0 * f0 + (u128)f1_2 * f4_19 + (u128)f2_2 * f3_19;
    u128 h1 = (u128)f0_2 * f1 + (u128)f2_2 * f4_19 + (u128)f3 * f3_19;
    u128 h2 = (u128)f0_2 * f2 + (u128)f1 * f1 + (u128)f3_2 * f4_19;
    u128 h3 = (u128)f0_2 * f3 + (u128)f1_2 * f2 + (u128)f4 * f4_19;
    u128 h4 = (u128)f0_2 * f4 + (u128)f1_2 * f3 + (u128)f2 * f2;
    u64 c;
    u64 r0 = (u64)h0 & MASK51; h1 += (u64)(h0 >> 51);
    u64 r1 = (u64)h1 & MASK51; h2 += (u64)(h1 >> 51);
    u64 r2 = (u64)h2 & MASK51; h3 += (u64)(h2 >> 51);
    u64 r3 = (u64)h3 & MASK51; h4 += (u64)(h3 >> 51);
    u64 r4 = (u64)h4 & MASK51; r0 += (u64)(h4 >> 51) * 19;
    c = r0 >> 51; r0 &= MASK51; r1 += c;
    h->v[0] = r0; h->v[1] = r1; h->v[2] = r2; h->v[3] = r3; h->v[4] = r4;
}

static void fe_sqn(fe *h, const fe *f, int n) {
    fe_sq(h, f);
    for (int i = 1; i < n; i++) fe_sq(h, h);
}

/* canonical little-endian bytes (value fully reduced mod p) */
static void fe_tobytes(u8 out[32], const fe *f) {
    fe t = *f;
    fe_carry(&t);
    fe_carry(&t);
    /* now limbs < 2^51; compute t + 19, use its carry-out as "t >= p" */
    u64 q = (t.v[0] + 19) >> 51;
    q = (t.v[1] + q) >> 51;
    q = (t.v[2] + q) >> 51;
    q = (t.v[3] + q) >> 51;
    q = (t.v[4] + q) >> 51; /* q = 1 iff t >= p */
    t.v[0] += 19 * q;
    u64 c;
    c = t.v[0] >> 51; t.v[0] &= MASK51; t.v[1] += c;
    c = t.v[1] >> 51; t.v[1] &= MASK51; t.v[2] += c;
    c = t.v[2] >> 51; t.v[2] &= MASK51; t.v[3] += c;
    c = t.v[3] >> 51; t.v[3] &= MASK51; t.v[4] += c;
    t.v[4] &= MASK51; /* drop the 2^255 bit */
    u64 w0 = t.v[0] | (t.v[1] << 51);
    u64 w1 = (t.v[1] >> 13) | (t.v[2] << 38);
    u64 w2 = (t.v[2] >> 26) | (t.v[3] << 25);
    u64 w3 = (t.v[3] >> 39) | (t.v[4] << 12);
    for (int i = 0; i < 8; i++) {
        out[i] = (u8)(w0 >> (8 * i));
        out[8 + i] = (u8)(w1 >> (8 * i));
        out[16 + i] = (u8)(w2 >> (8 * i));
        out[24 + i] = (u8)(w3 >> (8 * i));
    }
}

/* load 32 LE bytes, top bit ignored (RFC 8032 sign bit handled by caller) */
static void fe_frombytes(fe *h, const u8 in[32]) {
    u64 w0 = 0, w1 = 0, w2 = 0, w3 = 0;
    for (int i = 7; i >= 0; i--) {
        w0 = (w0 << 8) | in[i];
        w1 = (w1 << 8) | in[8 + i];
        w2 = (w2 << 8) | in[16 + i];
        w3 = (w3 << 8) | in[24 + i];
    }
    h->v[0] = w0 & MASK51;
    h->v[1] = ((w0 >> 51) | (w1 << 13)) & MASK51;
    h->v[2] = ((w1 >> 38) | (w2 << 26)) & MASK51;
    h->v[3] = ((w2 >> 25) | (w3 << 39)) & MASK51;
    h->v[4] = (w3 >> 12) & MASK51;
}

static int fe_iszero(const fe *f) {
    u8 b[32];
    fe_tobytes(b, f);
    u8 acc = 0;
    for (int i = 0; i < 32; i++) acc |= b[i];
    return acc == 0;
}

static int fe_eq(const fe *f, const fe *g) {
    u8 a[32], b[32];
    fe_tobytes(a, f);
    fe_tobytes(b, g);
    return memcmp(a, b, 32) == 0;
}

static int fe_isneg(const fe *f) {
    u8 b[32];
    fe_tobytes(b, f);
    return b[0] & 1;
}

/* z^(2^250 - 1) ladder shared by invert and pow22523 */
static void fe_pow250(fe *out, fe *z11out, const fe *z) {
    fe z2, z9, z11, t;
    fe_sq(&z2, z);              /* 2 */
    fe_sqn(&t, &z2, 2);         /* 8 */
    fe_mul(&z9, &t, z);         /* 9 */
    fe_mul(&z11, &z9, &z2);     /* 11 */
    fe_sq(&t, &z11);            /* 22 */
    fe_mul(&t, &t, &z9);        /* 2^5 - 1 */
    fe z5 = t;
    fe_sqn(&t, &z5, 5);
    fe_mul(&t, &t, &z5);        /* 2^10 - 1 */
    fe z10 = t;
    fe_sqn(&t, &z10, 10);
    fe_mul(&t, &t, &z10);       /* 2^20 - 1 */
    fe z20 = t;
    fe_sqn(&t, &z20, 20);
    fe_mul(&t, &t, &z20);       /* 2^40 - 1 */
    fe_sqn(&t, &t, 10);
    fe_mul(&t, &t, &z10);       /* 2^50 - 1 */
    fe z50 = t;
    fe_sqn(&t, &z50, 50);
    fe_mul(&t, &t, &z50);       /* 2^100 - 1 */
    fe z100 = t;
    fe_sqn(&t, &z100, 100);
    fe_mul(&t, &t, &z100);      /* 2^200 - 1 */
    fe_sqn(&t, &t, 50);
    fe_mul(&t, &t, &z50);       /* 2^250 - 1 */
    *out = t;
    if (z11out) *z11out = z11;
}

static void fe_invert(fe *out, const fe *z) {
    fe t, z11;
    fe_pow250(&t, &z11, z);
    fe_sqn(&t, &t, 5);          /* 2^255 - 32 */
    fe_mul(out, &t, &z11);      /* 2^255 - 21 = p - 2 */
}

/* z^((p-5)/8) = z^(2^252 - 3) */
static void fe_pow22523(fe *out, const fe *z) {
    fe t;
    fe_pow250(&t, NULL, z);
    fe_sqn(&t, &t, 2);          /* 2^252 - 4 */
    fe_mul(out, &t, z);         /* 2^252 - 3 */
}

/* ------------------------------------------------------------------ */
/* group: extended coordinates + niels forms                           */
/* ------------------------------------------------------------------ */

typedef struct { fe X, Y, Z, T; } ge;            /* x=X/Z y=Y/Z xy=T/Z */
typedef struct { fe ypx, ymx, t2d; } nielspt;    /* affine precomp      */
typedef struct { fe ypx, ymx, Z, t2d; } cachedpt;

static fe FE_D, FE_2D, FE_SQRT_M1, FE_INVSQRT_A_MINUS_D;
static ge GE_BASE;

static void ge_identity(ge *h) {
    fe_zero(&h->X); fe_one(&h->Y); fe_one(&h->Z); fe_zero(&h->T);
}

static int ge_is_identity(const ge *p) {
    return fe_iszero(&p->X) && fe_iszero(&p->T) && fe_eq(&p->Y, &p->Z);
}

static void ge_dbl(ge *r, const ge *p) {
    fe a, b, c, h, e, g, f, t;
    fe_sq(&a, &p->X);
    fe_sq(&b, &p->Y);
    fe_sq(&c, &p->Z);
    fe_add(&c, &c, &c); fe_carry(&c);
    fe_add(&h, &a, &b);
    fe_add(&t, &p->X, &p->Y); fe_carry(&t);
    fe_sq(&t, &t);
    fe_sub(&e, &h, &t); fe_carry(&e);
    fe_sub(&g, &a, &b); fe_carry(&g);
    fe_add(&f, &c, &g);
    fe_mul(&r->X, &e, &f);
    fe_mul(&r->Y, &g, &h);
    fe_mul(&r->Z, &f, &g);
    fe_mul(&r->T, &e, &h);
}

/* r = p + q where q is an affine niels point (Z=1); sgn=-1 adds -q */
static void ge_madd(ge *r, const ge *p, const nielspt *q, int sgn) {
    fe a, b, c, d, e, f, g, h;
    fe_sub(&a, &p->Y, &p->X); fe_carry(&a);
    fe_add(&b, &p->Y, &p->X); fe_carry(&b);
    if (sgn > 0) {
        fe_mul(&a, &a, &q->ymx);
        fe_mul(&b, &b, &q->ypx);
        fe_mul(&c, &p->T, &q->t2d);
    } else {
        fe_mul(&a, &a, &q->ypx);
        fe_mul(&b, &b, &q->ymx);
        fe neg;
        fe_neg(&neg, &q->t2d);
        fe_carry(&neg);
        fe_mul(&c, &p->T, &neg);
    }
    fe_add(&d, &p->Z, &p->Z); fe_carry(&d);
    fe_sub(&e, &b, &a); fe_carry(&e);
    fe_sub(&f, &d, &c); fe_carry(&f);
    fe_add(&g, &d, &c); fe_carry(&g);
    fe_add(&h, &b, &a); fe_carry(&h);
    fe_mul(&r->X, &e, &f);
    fe_mul(&r->Y, &g, &h);
    fe_mul(&r->Z, &f, &g);
    fe_mul(&r->T, &e, &h);
}

static void ge_add_cached(ge *r, const ge *p, const cachedpt *q) {
    fe a, b, c, d, e, f, g, h;
    fe_sub(&a, &p->Y, &p->X); fe_carry(&a);
    fe_mul(&a, &a, &q->ymx);
    fe_add(&b, &p->Y, &p->X); fe_carry(&b);
    fe_mul(&b, &b, &q->ypx);
    fe_mul(&c, &p->T, &q->t2d);
    fe_mul(&d, &p->Z, &q->Z);
    fe_add(&d, &d, &d); fe_carry(&d);
    fe_sub(&e, &b, &a); fe_carry(&e);
    fe_sub(&f, &d, &c); fe_carry(&f);
    fe_add(&g, &d, &c); fe_carry(&g);
    fe_add(&h, &b, &a); fe_carry(&h);
    fe_mul(&r->X, &e, &f);
    fe_mul(&r->Y, &g, &h);
    fe_mul(&r->Z, &f, &g);
    fe_mul(&r->T, &e, &h);
}

static void ge_to_cached(cachedpt *c, const ge *p) {
    fe_add(&c->ypx, &p->Y, &p->X); fe_carry(&c->ypx);
    fe_sub(&c->ymx, &p->Y, &p->X); fe_carry(&c->ymx);
    c->Z = p->Z;
    fe_mul(&c->t2d, &p->T, &FE_2D);
}

static void ge_add(ge *r, const ge *p, const ge *q) {
    cachedpt c;
    ge_to_cached(&c, q);
    ge_add_cached(r, p, &c);
}

/* affine (x, y) with xy=t -> niels */
static void niels_from_affine(nielspt *n, const fe *x, const fe *y) {
    fe t;
    fe_add(&n->ypx, y, x); fe_carry(&n->ypx);
    fe_sub(&n->ymx, y, x); fe_carry(&n->ymx);
    fe_mul(&t, x, y);
    fe_mul(&n->t2d, &t, &FE_2D);
}

/* normalize extended -> affine niels (one inversion) */
static void ge_to_niels(nielspt *n, const ge *p) {
    fe zi, x, y;
    fe_invert(&zi, &p->Z);
    fe_mul(&x, &p->X, &zi);
    fe_mul(&y, &p->Y, &zi);
    niels_from_affine(n, &x, &y);
}

static void ge_compress(u8 out[32], const ge *p) {
    fe zi, x, y;
    fe_invert(&zi, &p->Z);
    fe_mul(&x, &p->X, &zi);
    fe_mul(&y, &p->Y, &zi);
    fe_tobytes(out, &y);
    u8 xb[32];
    fe_tobytes(xb, &x);
    out[31] |= (xb[0] & 1) << 7;
}

/* RFC 8032 5.1.3 decode, exactly as crypto/ed25519.py _decompress.
 * Returns 1 and fills (x, y) on success, 0 on failure. */
static int ed_decompress(fe *x, fe *y, const u8 in[32]) {
    int sign = in[31] >> 7;
    /* y >= p check: load then compare canonical re-encoding */
    fe_frombytes(y, in);
    u8 chk[32];
    fe_tobytes(chk, y);
    u8 masked[32];
    memcpy(masked, in, 32);
    masked[31] &= 0x7F;
    if (memcmp(chk, masked, 32) != 0) return 0; /* non-canonical y */
    fe y2, u, v, v3, v7, t, x2;
    fe_sq(&y2, y);
    fe one;
    fe_one(&one);
    fe_sub(&u, &y2, &one); fe_carry(&u);
    fe_mul(&v, &FE_D, &y2);
    fe_add(&v, &v, &one); fe_carry(&v);
    fe_sq(&v3, &v);
    fe_mul(&v3, &v3, &v);          /* v^3 */
    fe_sq(&v7, &v3);
    fe_mul(&v7, &v7, &v);          /* v^7 */
    fe_mul(&t, &u, &v7);
    fe_pow22523(&t, &t);           /* (u v^7)^((p-5)/8) */
    fe_mul(&t, &t, &v3);
    fe_mul(x, &t, &u);             /* u v^3 (u v^7)^((p-5)/8) */
    fe_sq(&x2, x);
    fe_mul(&x2, &x2, &v);          /* v x^2 */
    fe negu;
    fe_neg(&negu, &u); fe_carry(&negu);
    if (fe_eq(&x2, &u)) {
        /* ok */
    } else if (fe_eq(&x2, &negu)) {
        fe_mul(x, x, &FE_SQRT_M1);
    } else {
        return 0;
    }
    if (fe_iszero(x)) {
        if (sign) return 0;
    }
    if (fe_isneg(x) != sign) {
        fe_neg(x, x);
        fe_carry(x);
    }
    return 1;
}

/* ristretto255 decode, exactly as crypto/sr25519.py ristretto_decode.
 * Fills extended point; returns 1 on success. */
static int ristretto_decode_c(ge *p, const u8 in[32]) {
    fe s;
    fe_frombytes(&s, in);
    u8 chk[32];
    fe_tobytes(chk, &s);
    if (memcmp(chk, in, 32) != 0) return 0;  /* >= p or high bit set */
    if (in[0] & 1) return 0;                 /* negative s */
    fe ss, u1, u2, u2s, v, t, one;
    fe_one(&one);
    fe_sq(&ss, &s);
    fe_sub(&u1, &one, &ss); fe_carry(&u1);
    fe_add(&u2, &one, &ss); fe_carry(&u2);
    fe_sq(&u2s, &u2);
    fe_mul(&v, &FE_D, &u1);
    fe_mul(&v, &v, &u1);
    fe_neg(&v, &v); fe_carry(&v);
    fe_sub(&v, &v, &u2s); fe_carry(&v);      /* -(d u1^2) - u2^2 */
    /* invsqrt = sqrt_ratio_m1(1, v * u2s) */
    fe arg;
    fe_mul(&arg, &v, &u2s);
    /* r = arg^((p-5)/8) * ... : sqrt_ratio(1, w): r = w^((p-5)/8) * w^3 *
       ... mirror python: v3=w^3? python computes with u=1: r = v3 * (v7)^(..)
       where v=arg. */
    fe a3, a7, r;
    fe_sq(&a3, &arg); fe_mul(&a3, &a3, &arg);
    fe_sq(&a7, &a3); fe_mul(&a7, &a7, &arg);
    fe_pow22523(&r, &a7);
    fe_mul(&r, &r, &a3);
    fe check;
    fe_sq(&check, &r);
    fe_mul(&check, &check, &arg);            /* arg * r^2 */
    fe negone, negi;
    fe_neg(&negone, &one); fe_carry(&negone);
    fe_mul(&negi, &negone, &FE_SQRT_M1);
    int correct = fe_eq(&check, &one);
    int flipped = fe_eq(&check, &negone);
    int flipped_i = fe_eq(&check, &negi);
    if (flipped || flipped_i) fe_mul(&r, &r, &FE_SQRT_M1);
    int was_square = correct || flipped;
    if (fe_isneg(&r)) { fe_neg(&r, &r); fe_carry(&r); }
    fe den_x, den_y, x, y, tt;
    fe_mul(&den_x, &r, &u2);
    fe_mul(&den_y, &r, &den_x);
    fe_mul(&den_y, &den_y, &v);
    fe s2;
    fe_add(&s2, &s, &s); fe_carry(&s2);
    fe_mul(&x, &s2, &den_x);
    if (fe_isneg(&x)) { fe_neg(&x, &x); fe_carry(&x); }
    fe_mul(&y, &u1, &den_y);
    fe_mul(&tt, &x, &y);
    if (!was_square || fe_isneg(&tt) || fe_iszero(&y)) return 0;
    p->X = x; p->Y = y; fe_one(&p->Z); p->T = tt;
    return 1;
}

/* ristretto equality, as crypto/sr25519.py ristretto_eq (X/Z cross-mul) */
static int ristretto_eq_c(const ge *p, const ge *q) {
    fe a, b;
    fe_mul(&a, &p->X, &q->Y);
    fe_mul(&b, &p->Y, &q->X);
    if (fe_eq(&a, &b)) return 1;
    fe_mul(&a, &p->Y, &q->Y);
    fe_mul(&b, &p->X, &q->X);
    return fe_eq(&a, &b);
}

/* ------------------------------------------------------------------ */
/* scalars: u32-limb helpers + mod-(2^k + e) folding                   */
/* ------------------------------------------------------------------ */

/* L (little-endian bytes) and the folds L = 2^252 + DELTA, 8L = 2^255+8D */
static const u8 L_BYTES[32] = {
    0xED, 0xD3, 0xF5, 0x5C, 0x1A, 0x63, 0x12, 0x58,
    0xD6, 0x9C, 0xF7, 0xA2, 0xDE, 0xF9, 0xDE, 0x14,
    0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0x10};

static u32 L_LIMBS[8], DELTA_LIMBS[4], L8_LIMBS[8], DELTA8_LIMBS[5];

static void bytes_to_limbs(u32 *out, const u8 *b, int nbytes, int nlimbs) {
    memset(out, 0, 4 * nlimbs);
    for (int i = 0; i < nbytes; i++) out[i / 4] |= (u32)b[i] << (8 * (i % 4));
}

static int big_bits(const u32 *a, int n) {
    for (int i = n - 1; i >= 0; i--) {
        if (a[i]) {
            int b = 32 * i;
            u32 v = a[i];
            while (v) { b++; v >>= 1; }
            return b;
        }
    }
    return 0;
}

static int big_cmp(const u32 *a, const u32 *b, int n) {
    for (int i = n - 1; i >= 0; i--) {
        if (a[i] != b[i]) return a[i] < b[i] ? -1 : 1;
    }
    return 0;
}

/* r = a - b (a >= b), n limbs */
static void big_sub(u32 *r, const u32 *a, const u32 *b, int n) {
    u64 borrow = 0;
    for (int i = 0; i < n; i++) {
        u64 t = (u64)a[i] - b[i] - borrow;
        r[i] = (u32)t;
        borrow = (t >> 32) & 1;
    }
}

static void big_add(u32 *r, const u32 *a, const u32 *b, int n) {
    u64 carry = 0;
    for (int i = 0; i < n; i++) {
        u64 t = (u64)a[i] + b[i] + carry;
        r[i] = (u32)t;
        carry = t >> 32;
    }
}

/* out(an+bn limbs) = a * b */
static void big_mul(u32 *out, const u32 *a, int an, const u32 *b, int bn) {
    memset(out, 0, 4 * (an + bn));
    for (int i = 0; i < an; i++) {
        u64 carry = 0;
        for (int j = 0; j < bn; j++) {
            u64 t = (u64)a[i] * b[j] + out[i + j] + carry;
            out[i + j] = (u32)t;
            carry = t >> 32;
        }
        out[i + bn] = (u32)carry;
    }
}

#define SC_MAX 24 /* scratch limbs (768 bits) */

/* x (inout, xl limbs) mod m where m = 2^k + e; e has el limbs, m ml limbs.
 * Unsigned folding: x = hi*2^k + lo  ==>  x := lo + (m << s) - e*hi with
 * m<<s chosen >= e*hi, repeated until x < 2^(k+2), then subtract m. */
static void big_mod_fold(u32 *x, int xl, int k, const u32 *e, int el,
                         const u32 *m, int ml) {
    u32 hi[SC_MAX], p[SC_MAX], ms[SC_MAX], acc[SC_MAX];
    for (int guard = 0; guard < 12; guard++) {
        int xb = big_bits(x, xl);
        if (xb <= k + 1) break; /* final conditional subtracts finish it */
        int hb = xb - k;
        int hl = (hb + 31) / 32;
        /* hi = x >> k */
        int ks = k / 32, kb = k % 32;
        memset(hi, 0, sizeof(hi));
        for (int i = 0; i < hl; i++) {
            u32 lo_part = (ks + i < xl) ? x[ks + i] >> kb : 0;
            u32 hi_part = (kb && ks + i + 1 < xl) ? x[ks + i + 1] << (32 - kb) : 0;
            hi[i] = lo_part | hi_part;
        }
        /* lo = x mod 2^k */
        for (int i = ks + 1; i < xl; i++) x[i] = 0;
        if (ks < xl) x[ks] &= (kb ? ((1u << kb) - 1) : 0xFFFFFFFFu);
        if (kb == 0 && ks < xl) x[ks] = 0;
        /* p = e * hi */
        int eb = big_bits(e, el);
        memset(p, 0, sizeof(p));
        big_mul(p, e, el, hi, hl);
        int pl = el + hl;
        int pb = eb + hb; /* upper bound on bits of p */
        /* ms = m << s with s making m<<s >= 2^pb > p */
        int s = pb - (k + 1) + 1;
        if (s < 0) s = 0;
        memset(ms, 0, sizeof(ms));
        int ss = s / 32, sb = s % 32;
        for (int i = ml - 1; i >= 0; i--) {
            ms[i + ss] |= m[i] << sb;
            if (sb && i + ss + 1 < SC_MAX) ms[i + ss + 1] |= m[i] >> (32 - sb);
        }
        int msl = ml + ss + 1;
        if (msl > SC_MAX) msl = SC_MAX;
        /* x = lo + ms - p */
        memset(acc, 0, sizeof(acc));
        memcpy(acc, x, 4 * xl);
        big_add(acc, acc, ms, SC_MAX);
        big_sub(acc, acc, p, SC_MAX);
        (void)pl;
        memcpy(x, acc, 4 * xl);
    }
    /* final: subtract m while x >= m (bounded) */
    u32 mm[SC_MAX];
    memset(mm, 0, sizeof(mm));
    memcpy(mm, m, 4 * ml);
    for (int guard = 0; guard < 8; guard++) {
        if (big_cmp(x, mm, xl > SC_MAX ? SC_MAX : xl) < 0) break;
        big_sub(x, x, mm, xl);
    }
}

/* scalar (LE bytes, sl limbs worth) fits and is < L ? */
static int sc_is_lt_l(const u8 s[32]) {
    for (int i = 31; i >= 0; i--) {
        if (s[i] != L_BYTES[i]) return s[i] < L_BYTES[i];
    }
    return 0; /* equal -> not less */
}

/* ------------------------------------------------------------------ */
/* recodings                                                           */
/* ------------------------------------------------------------------ */

/* signed fixed-window digits, w bits, from a 32-byte scalar (value < 2^256).
 * digits in [-2^(w-1), 2^(w-1)]; ndig = ceil(256/w)+1 covers the carry. */
static void recode_signed(const u8 sc[32], int w, int16_t *dig, int ndig) {
    int carry = 0;
    int half = 1 << (w - 1);
    u32 wmask = (1u << w) - 1;
    for (int j = 0; j < ndig; j++) {
        int bitpos = j * w;
        int byte = bitpos >> 3, off = bitpos & 7;
        u32 raw = 0;
        if (byte < 32) raw |= sc[byte];
        if (byte + 1 < 32) raw |= (u32)sc[byte + 1] << 8;
        if (byte + 2 < 32) raw |= (u32)sc[byte + 2] << 16;
        int d = (int)((raw >> off) & wmask) + carry;
        carry = 0;
        if (d > half) { d -= (1 << w); carry = 1; }
        dig[j] = (int16_t)d;
    }
}

/* wNAF with window w: digits odd in (-2^w, 2^w); returns length */
static int wnaf(int8_t *out, const u8 sc[32], int w) {
    /* copy scalar into u32 limbs we can shift */
    u32 x[9];
    bytes_to_limbs(x, sc, 32, 9);
    int len = 0;
    int bits = big_bits(x, 9);
    int pos = 0;
    memset(out, 0, 257);
    while (pos <= bits) {
        if (!((x[pos / 32] >> (pos % 32)) & 1)) { pos++; continue; }
        /* take w+1 bits at pos */
        int byte = pos / 32, off = pos % 32;
        u64 window = (u64)x[byte] >> off;
        if (byte + 1 < 9) window |= (u64)x[byte + 1] << (32 - off);
        int d = (int)(window & ((1u << (w + 1)) - 1));
        if (d > (1 << w)) d -= (1 << (w + 1));
        out[pos] = (int8_t)d;
        /* subtract d*2^pos from x */
        if (d > 0) {
            u64 borrow = 0;
            u64 sub = (u64)d << off;
            for (int i = byte; i < 9 && (sub || borrow); i++) {
                u64 t = (u64)x[i] - (sub & 0xFFFFFFFFu) - borrow;
                x[i] = (u32)t;
                borrow = (t >> 32) & 1;
                sub >>= 32;
            }
        } else {
            u64 carry = 0;
            u64 add = (u64)(-d) << off;
            for (int i = byte; i < 9 && (add || carry); i++) {
                u64 t = (u64)x[i] + (add & 0xFFFFFFFFu) + carry;
                x[i] = (u32)t;
                carry = t >> 32;
                add >>= 32;
            }
        }
        if (pos + 1 > len) len = pos + 1;
        pos += w;
        bits = big_bits(x, 9);
    }
    return len ? len : 1;
}

/* ------------------------------------------------------------------ */
/* init: constants + fixed-base tables                                 */
/* ------------------------------------------------------------------ */

#define BTAB_W 7
#define BTAB_N (1 << (BTAB_W - 1)) /* 64 odd multiples of B */
static nielspt B_TAB[BTAB_N];
static nielspt B_NIELS; /* B itself, for Pippenger */

static pthread_once_t INIT_ONCE = PTHREAD_ONCE_INIT;

static void fe_from_small(fe *h, u64 v) { fe_zero(h); h->v[0] = v; }

static void init_tables(void) {
    /* d = -121665 * inv(121666) mod p */
    fe n121665, n121666, inv;
    fe_from_small(&n121665, 121665);
    fe_from_small(&n121666, 121666);
    fe_invert(&inv, &n121666);
    fe_mul(&FE_D, &n121665, &inv);
    fe_neg(&FE_D, &FE_D);
    fe_carry(&FE_D);
    fe_add(&FE_2D, &FE_D, &FE_D);
    fe_carry(&FE_2D);
    /* sqrt(-1) = 2^((p-1)/4); exponent 2^253 - 5 LE bytes */
    u8 exp[32];
    memset(exp, 0xFF, 32);
    exp[0] = 0xFB;
    exp[31] = 0x1F;
    fe two, acc;
    fe_from_small(&two, 2);
    fe_one(&acc);
    for (int i = 255; i >= 0; i--) {
        fe_sq(&acc, &acc);
        if ((exp[i / 8] >> (i % 8)) & 1) fe_mul(&acc, &acc, &two);
    }
    FE_SQRT_M1 = acc;
    /* base point: y = 4/5, sign 0 */
    fe four, five, y;
    fe_from_small(&four, 4);
    fe_from_small(&five, 5);
    fe_invert(&inv, &five);
    fe_mul(&y, &four, &inv);
    u8 yb[32];
    fe_tobytes(yb, &y);
    fe bx, by;
    ed_decompress(&bx, &by, yb);
    GE_BASE.X = bx; GE_BASE.Y = by;
    fe_one(&GE_BASE.Z);
    fe_mul(&GE_BASE.T, &bx, &by);
    /* invsqrt(a - d) = sqrt_ratio_m1(1, -1 - d) for ristretto encode
       (not currently exported, kept for parity/selftest use) */
    fe amd, one;
    fe_one(&one);
    fe_neg(&amd, &FE_D);
    fe_carry(&amd);
    fe_sub(&amd, &amd, &one);
    fe_carry(&amd);
    fe a3, a7, r;
    fe_sq(&a3, &amd); fe_mul(&a3, &a3, &amd);
    fe_sq(&a7, &a3); fe_mul(&a7, &a7, &amd);
    fe_pow22523(&r, &a7);
    fe_mul(&r, &r, &a3);
    fe chk;
    fe_sq(&chk, &r);
    fe_mul(&chk, &chk, &amd);
    fe negone;
    fe_neg(&negone, &one); fe_carry(&negone);
    if (fe_eq(&chk, &negone)) fe_mul(&r, &r, &FE_SQRT_M1);
    if (fe_isneg(&r)) { fe_neg(&r, &r); fe_carry(&r); }
    FE_INVSQRT_A_MINUS_D = r;
    /* scalar-field constants */
    bytes_to_limbs(L_LIMBS, L_BYTES, 32, 8);
    bytes_to_limbs(DELTA_LIMBS, L_BYTES, 16, 4);
    /* 8L and 8*DELTA via limb shifts */
    u64 carry = 0;
    for (int i = 0; i < 8; i++) {
        u64 t = ((u64)L_LIMBS[i] << 3) | carry;
        L8_LIMBS[i] = (u32)t;
        carry = t >> 32;
    }
    carry = 0;
    for (int i = 0; i < 4; i++) {
        u64 t = ((u64)DELTA_LIMBS[i] << 3) | carry;
        DELTA8_LIMBS[i] = (u32)t;
        carry = t >> 32;
    }
    DELTA8_LIMBS[4] = (u32)carry;
    /* odd multiples of B as affine niels (init-time inversions are fine) */
    ge cur = GE_BASE, b2;
    ge_dbl(&b2, &GE_BASE);
    for (int i = 0; i < BTAB_N; i++) {
        ge_to_niels(&B_TAB[i], &cur);
        ge next;
        ge_add(&next, &cur, &b2);
        cur = next;
    }
    ge_to_niels(&B_NIELS, &GE_BASE);
}

/* ------------------------------------------------------------------ */
/* pubkey decompress cache (A points repeat every height)              */
/* ------------------------------------------------------------------ */

#define ACACHE_SLOTS 16384 /* power of two; ~3 MB */
typedef struct {
    u8 key[32];
    u8 state; /* 0 empty, 1 valid point, 2 known-bad key */
    nielspt neg_niels; /* niels of -A (verification always uses -A) */
    fe x, y;           /* affine A */
} acache_entry;

static acache_entry *ACACHE;
static pthread_mutex_t ACACHE_MU = PTHREAD_MUTEX_INITIALIZER;

static u64 fnv1a(const u8 *k, int n) {
    u64 h = 1469598103934665603ULL;
    for (int i = 0; i < n; i++) { h ^= k[i]; h *= 1099511628211ULL; }
    return h;
}

static acache_entry *RCACHE; /* same shape, ristretto-decoded sr25519 keys */

/* decompress A (cached); returns 1 ok (fills affine -A niels + affine A),
 * 0 bad key.  kind 0 = ed25519 RFC 8032 decode, 1 = ristretto255 decode
 * (validator keys repeat every height for both types). */
static int acache_get_kind(const u8 pub[32], nielspt *neg_niels, fe *ax,
                           fe *ay, int kind) {
    pthread_mutex_lock(&ACACHE_MU);
    acache_entry **cachep = kind ? &RCACHE : &ACACHE;
    if (!*cachep) *cachep = (acache_entry *)calloc(ACACHE_SLOTS, sizeof(acache_entry));
    acache_entry *CACHE = *cachep;
    u64 slot = fnv1a(pub, 32) & (ACACHE_SLOTS - 1);
    acache_entry *e = &CACHE[slot];
    if (e->state && memcmp(e->key, pub, 32) == 0) {
        int ok = e->state == 1;
        if (ok) {
            if (neg_niels) *neg_niels = e->neg_niels;
            if (ax) *ax = e->x;
            if (ay) *ay = e->y;
        }
        pthread_mutex_unlock(&ACACHE_MU);
        return ok;
    }
    pthread_mutex_unlock(&ACACHE_MU);
    fe x, y;
    int ok;
    if (kind) {
        ge A;
        ok = ristretto_decode_c(&A, pub);
        x = A.X;
        y = A.Y;
    } else {
        ok = ed_decompress(&x, &y, pub);
    }
    acache_entry ne;
    memset(&ne, 0, sizeof(ne));
    memcpy(ne.key, pub, 32);
    if (ok) {
        ne.state = 1;
        ne.x = x;
        ne.y = y;
        fe nx;
        fe_neg(&nx, &x);
        fe_carry(&nx);
        niels_from_affine(&ne.neg_niels, &nx, &y);
        if (neg_niels) *neg_niels = ne.neg_niels;
        if (ax) *ax = x;
        if (ay) *ay = y;
    } else {
        ne.state = 2;
    }
    pthread_mutex_lock(&ACACHE_MU);
    CACHE[slot] = ne; /* lossy overwrite on collision */
    pthread_mutex_unlock(&ACACHE_MU);
    return ok;
}

static int acache_get(const u8 pub[32], nielspt *neg_niels, fe *ax, fe *ay) {
    return acache_get_kind(pub, neg_niels, ax, ay, 0);
}

/* ------------------------------------------------------------------ */
/* serial verify                                                       */
/* ------------------------------------------------------------------ */

/* Straus: acc = [s]B + [h](-A); shared doublings, wNAF(7) on B table,
 * wNAF(5) on a per-call table of 16 odd multiples of -A. */
static void straus_sb_ha(ge *acc, const fe *ax, const fe *ay,
                         const u8 s[32], const u8 h[32]) {
    /* odd multiples of -A as cached points: T[k] = (2k+1)(-A) */
    cachedpt atab[16];
    ge a0, a2;
    fe nx;
    fe_neg(&nx, ax);
    fe_carry(&nx);
    a0.X = nx;
    a0.Y = *ay;
    fe_one(&a0.Z);
    fe_mul(&a0.T, &nx, ay);
    ge_dbl(&a2, &a0);
    ge_to_cached(&atab[0], &a0);
    for (int k = 1; k < 16; k++) {
        /* (2k+1)(-A) = (2k-1)(-A) + 2(-A) */
        ge tmp;
        ge_add_cached(&tmp, &a2, &atab[k - 1]);
        ge_to_cached(&atab[k], &tmp);
    }
    int8_t sd[257], hd[257];
    int sl = wnaf(sd, s, BTAB_W);
    int hl = wnaf(hd, h, 5);
    int top = sl > hl ? sl : hl;
    ge_identity(acc);
    for (int j = top - 1; j >= 0; j--) {
        ge_dbl(acc, acc);
        int ds = sd[j], dh = hd[j];
        if (ds > 0) ge_madd(acc, acc, &B_TAB[ds >> 1], 1);
        else if (ds < 0) ge_madd(acc, acc, &B_TAB[(-ds) >> 1], -1);
        if (dh > 0) ge_add_cached(acc, acc, &atab[dh >> 1]);
        else if (dh < 0) {
            /* negate cached: swap ypx/ymx, negate t2d */
            cachedpt c = atab[(-dh) >> 1];
            cachedpt nc;
            nc.ypx = c.ymx;
            nc.ymx = c.ypx;
            nc.Z = c.Z;
            fe_neg(&nc.t2d, &c.t2d);
            fe_carry(&nc.t2d);
            ge_add_cached(acc, acc, &nc);
        }
    }
}

/* one ed25519 serial verify; h32 = SHA512(R||A||M) mod L (LE) */
static int ed_verify_one(const u8 pub[32], const u8 h32[32], const u8 s32[32],
                         const u8 r32[32]) {
    if (!sc_is_lt_l(s32)) return 0;
    fe ax, ay;
    if (!acache_get(pub, NULL, &ax, &ay)) return 0;
    ge acc;
    straus_sb_ha(&acc, &ax, &ay, s32, h32);
    u8 enc[32];
    ge_compress(enc, &acc);
    return memcmp(enc, r32, 32) == 0;
}

/* one sr25519 serial verify; c32 = challenge mod L; s32 = sig[32:] with the
 * schnorrkel marker bit already stripped by the caller */
static int sr_verify_one(const u8 pub[32], const u8 c32[32], const u8 s32[32],
                         const u8 r32[32]) {
    if (!sc_is_lt_l(s32)) return 0;
    fe ax, ay;
    ge R;
    if (!acache_get_kind(pub, NULL, &ax, &ay, 1)) return 0;
    if (!ristretto_decode_c(&R, r32)) return 0;
    /* Q = [s]B + [c](-A); accept iff Q ~ R (ristretto coset equality) */
    ge acc;
    straus_sb_ha(&acc, &ax, &ay, s32, c32);
    return ristretto_eq_c(&acc, &R);
}

/* ------------------------------------------------------------------ */
/* Pippenger multi-scalar multiplication                               */
/* ------------------------------------------------------------------ */

typedef struct {
    const nielspt *pt; /* affine niels of the (already negated) point */
    u8 sc[32];         /* scalar, LE */
} msm_term;

static int msm_window_for(long n) {
    if (n < 12) return 4;
    if (n < 48) return 5;
    if (n < 160) return 6;
    if (n < 640) return 7;
    if (n < 4000) return 8;
    return 9;
}

/* acc = sum of terms; scratch must hold 2^(w-1) buckets */
static void msm_run(ge *acc, const msm_term *terms, long n) {
    int w = msm_window_for(n);
    int nb = 1 << (w - 1);
    int ndig = (256 + w - 1) / w + 1;
    int16_t *digs = (int16_t *)malloc((size_t)n * ndig * sizeof(int16_t));
    ge *buckets = (ge *)malloc((size_t)nb * sizeof(ge));
    u8 *used = (u8 *)malloc((size_t)nb);
    for (long i = 0; i < n; i++)
        recode_signed(terms[i].sc, w, digs + i * ndig, ndig);
    ge_identity(acc);
    for (int win = ndig - 1; win >= 0; win--) {
        if (win != ndig - 1)
            for (int k = 0; k < w; k++) ge_dbl(acc, acc);
        memset(used, 0, (size_t)nb);
        for (long i = 0; i < n; i++) {
            int d = digs[i * ndig + win];
            if (!d) continue;
            int idx = (d > 0 ? d : -d) - 1;
            if (!used[idx]) {
                ge_identity(&buckets[idx]);
                used[idx] = 1;
            }
            ge_madd(&buckets[idx], &buckets[idx], terms[i].pt, d > 0 ? 1 : -1);
        }
        /* merge: sum_k (k+1)*bucket[k] via running sums */
        ge run, wsum;
        ge_identity(&run);
        ge_identity(&wsum);
        int any = 0;
        for (int k = nb - 1; k >= 0; k--) {
            if (used[k]) {
                ge_add(&run, &run, &buckets[k]);
                any = 1;
            }
            if (any) ge_add(&wsum, &wsum, &run);
        }
        if (any) ge_add(acc, acc, &wsum);
    }
    free(digs);
    free(buckets);
    free(used);
}

/* ------------------------------------------------------------------ */
/* batch entries                                                       */
/* ------------------------------------------------------------------ */

/* derive n 128-bit coefficients from seed; z[i] full 16 bytes, nonzero */
static void derive_z(const u8 seed[32], long n, u8 *z /* 16n */) {
    u8 buf[40], dig[64];
    memcpy(buf, seed, 32);
    for (long blk = 0; blk * 4 < n; blk++) {
        for (int i = 0; i < 8; i++) buf[32 + i] = (u8)((u64)blk >> (8 * i));
        sha512_short(buf, 40, dig);
        for (int j = 0; j < 4 && blk * 4 + j < n; j++) {
            memcpy(z + (blk * 4 + j) * 16, dig + 16 * j, 16);
            /* force nonzero (an all-zero z would drop the item's equation) */
            int nz = 0;
            for (int b = 0; b < 16; b++) nz |= z[(blk * 4 + j) * 16 + b];
            if (!nz) z[(blk * 4 + j) * 16] = 1;
        }
    }
}

/* shared RLC core.  kind 0 = ed25519 (exact identity), 1 = sr25519
 * ([8]S == identity).  ax/ay and rx/ry carry the already-decoded affine
 * A_i and R_i from the caller's precheck pass (decode once, use twice).
 * Returns 1 if the batch equation holds. */
static int rlc_check(long n, const fe *ax, const fe *ay, const fe *rx,
                     const fe *ry, const u8 *h32, const u8 *s32,
                     const u8 seed[32], int kind,
                     const u8 *item_ok /* per-item prechecks */) {
    /* terms: for each valid item: -A_i with (z_i h_i mod 8L), -R_i with z_i;
     * plus B with sum z_i s_i mod L. */
    u8 *z = (u8 *)malloc((size_t)n * 16);
    derive_z(seed, n, z);
    nielspt *neg_r = (nielspt *)malloc((size_t)n * sizeof(nielspt));
    nielspt *neg_a = (nielspt *)malloc((size_t)n * sizeof(nielspt));
    msm_term *terms = (msm_term *)malloc((size_t)(2 * n + 1) * sizeof(msm_term));
    long nt = 0;
    /* sum z_i s_i accumulator (u64 limbs over u32 values) */
    u64 accsum[13];
    memset(accsum, 0, sizeof(accsum));
    int any = 0;
    for (long i = 0; i < n; i++) {
        if (!item_ok[i]) continue;
        any = 1;
        fe neg;
        fe_neg(&neg, &rx[i]);
        fe_carry(&neg);
        niels_from_affine(&neg_r[i], &neg, &ry[i]);
        fe_neg(&neg, &ax[i]);
        fe_carry(&neg);
        niels_from_affine(&neg_a[i], &neg, &ay[i]);
        /* scalars */
        u32 zl[4], hl_[8], prod[12], red[SC_MAX];
        bytes_to_limbs(zl, z + 16 * i, 16, 4);
        bytes_to_limbs(hl_, h32 + 32 * i, 32, 8);
        big_mul(prod, zl, 4, hl_, 8);
        memset(red, 0, sizeof(red));
        memcpy(red, prod, 4 * 12);
        big_mod_fold(red, SC_MAX, 255, DELTA8_LIMBS, 5, L8_LIMBS, 8);
        msm_term *t = &terms[nt++];
        t->pt = &neg_a[i];
        for (int b = 0; b < 32; b++) t->sc[b] = (u8)(red[b / 4] >> (8 * (b % 4)));
        t = &terms[nt++];
        t->pt = &neg_r[i];
        memset(t->sc, 0, 32);
        memcpy(t->sc, z + 16 * i, 16);
        /* accsum += z_i * s_i */
        u32 sl_[8], prod2[12];
        bytes_to_limbs(sl_, s32 + 32 * i, 32, 8);
        big_mul(prod2, zl, 4, sl_, 8);
        for (int b = 0; b < 12; b++) accsum[b] += prod2[b];
    }
    int result = 1;
    if (any) {
        /* normalize accsum -> u32 limbs, reduce mod L */
        u32 sum[SC_MAX];
        memset(sum, 0, sizeof(sum));
        u64 carry = 0;
        for (int b = 0; b < 13; b++) {
            u64 t = accsum[b] + carry;
            sum[b] = (u32)t;
            carry = t >> 32;
        }
        sum[13] = (u32)carry;
        big_mod_fold(sum, SC_MAX, 252, DELTA_LIMBS, 4, L_LIMBS, 8);
        msm_term *t = &terms[nt++];
        t->pt = &B_NIELS;
        for (int b = 0; b < 32; b++) t->sc[b] = (u8)(sum[b / 4] >> (8 * (b % 4)));
        ge S;
        msm_run(&S, terms, nt);
        if (kind == 1) {
            ge_dbl(&S, &S);
            ge_dbl(&S, &S);
            ge_dbl(&S, &S);
        }
        result = ge_is_identity(&S);
    }
    free(z);
    free(neg_r);
    free(neg_a);
    free(terms);
    return result;
}

/* mode: 0 serial, 1 RLC (serial fallback on mismatch), 2 auto */
void ed25519h_verify(long n, const u8 *pubs, const u8 *h32, const u8 *s32,
                     const u8 *r32, const u8 *valid, const u8 *seed32,
                     int mode, u8 *out) {
    pthread_once(&INIT_ONCE, init_tables);
    if (n <= 0) return;
    u8 *item_ok = (u8 *)malloc((size_t)n);
    fe *ax = (fe *)malloc((size_t)n * 4 * sizeof(fe));
    fe *ay = ax + n, *rx = ax + 2 * n, *ry = ax + 3 * n;
    for (long i = 0; i < n; i++) {
        int ok = valid[i] && sc_is_lt_l(s32 + 32 * i);
        if (ok) ok = acache_get(pubs + 32 * i, NULL, &ax[i], &ay[i]);
        /* serial never decodes R (byte compare), but an R outside the
         * canonical-point set can never equal a compress() output, so
         * "R decodes" is exactly "serial could possibly accept". */
        if (ok) ok = ed_decompress(&rx[i], &ry[i], r32 + 32 * i);
        item_ok[i] = (u8)ok;
    }
    int use_batch = (mode == 1) || (mode == 2 && n >= 8);
    if (use_batch &&
        rlc_check(n, ax, ay, rx, ry, h32, s32, seed32, 0, item_ok)) {
        for (long i = 0; i < n; i++) out[i] = item_ok[i];
    } else {
        for (long i = 0; i < n; i++)
            out[i] = item_ok[i] &&
                     ed_verify_one(pubs + 32 * i, h32 + 32 * i, s32 + 32 * i,
                                   r32 + 32 * i);
    }
    free(item_ok);
    free(ax);
}

void sr25519h_verify(long n, const u8 *pubs, const u8 *c32, const u8 *s32,
                     const u8 *r32, const u8 *valid, const u8 *seed32,
                     int mode, u8 *out) {
    pthread_once(&INIT_ONCE, init_tables);
    if (n <= 0) return;
    u8 *item_ok = (u8 *)malloc((size_t)n);
    fe *ax = (fe *)malloc((size_t)n * 4 * sizeof(fe));
    fe *ay = ax + n, *rx = ax + 2 * n, *ry = ax + 3 * n;
    for (long i = 0; i < n; i++) {
        int ok = valid[i] && sc_is_lt_l(s32 + 32 * i);
        if (ok) ok = acache_get_kind(pubs + 32 * i, NULL, &ax[i], &ay[i], 1);
        if (ok) {
            ge R;
            ok = ristretto_decode_c(&R, r32 + 32 * i);
            if (ok) { rx[i] = R.X; ry[i] = R.Y; }
        }
        item_ok[i] = (u8)ok;
    }
    int use_batch = (mode == 1) || (mode == 2 && n >= 8);
    if (use_batch &&
        rlc_check(n, ax, ay, rx, ry, c32, s32, seed32, 1, item_ok)) {
        for (long i = 0; i < n; i++) out[i] = item_ok[i];
    } else {
        for (long i = 0; i < n; i++)
            out[i] = item_ok[i] &&
                     sr_verify_one(pubs + 32 * i, c32 + 32 * i, s32 + 32 * i,
                                   r32 + 32 * i);
    }
    free(item_ok);
    free(ax);
}

/* sanity: returns 1 when the base point round-trips through compress */
int ed25519h_selftest(void) {
    pthread_once(&INIT_ONCE, init_tables);
    u8 enc[32];
    ge_compress(enc, &GE_BASE);
    fe x, y;
    if (!ed_decompress(&x, &y, enc)) return 0;
    return fe_eq(&x, &GE_BASE.X) && fe_eq(&y, &GE_BASE.Y);
}
