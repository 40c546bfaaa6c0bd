"""Batched Ed25519 verification on device — THE north-star kernel (v3).

Reference behavior being replaced: stp_core/crypto/nacl_wrappers.py:62,212
(libsodium Ed25519, one scalar verify per call, n× per request across the
pool — SURVEY.md §3.2 "Ed25519 HOT SPOT"). Here the expensive part of
verification — the double-scalar multiplication [S]B + [h](-A) and the
compare against R — runs for a whole batch of signatures in ONE device
dispatch.

Split of labor (see plenum_tpu/crypto/ed25519.py for the host side):
  host:   decode/decompress points (pure-Python bigint sqrt, cached per
          verkey together with [2^64k](-A) for k=1..3 — the quarter points
          of the split window ladder, kept in extended coordinates so the
          chain needs NO host inversions),
          h = SHA512(R||A||M) mod L (hashlib, C speed),
          scalars -> window digit arrays
  device: windowed multi-scalar mult over GF(2^255-19) with 20x13-bit limbs
          in int32 lanes; affine comparison against R

Kernel shape (v3; v2 was int64 10x26-bit limbs with a 2-way split):
  [S]B      via an 8-bit fixed-base comb: 32 precomputed constant tables
            T[w][d] = d*256^w*B in affine "niels" form (y+x, y-x, 2d*x*y) —
            32 mixed additions, ZERO doublings. Table selection is a
            one-hot f32 matmul (tables are batch-constant), so it rides
            the MXU instead of burning VPU cycles.
  [h](-A)   split h = h0 + 2^64*h1 + 2^128*h2 + 2^192*h3 with the quarter
            points Qk = [2^64k](-A) cached per verkey on host; four
            16-entry tables are built on device (one batched build), then
            16 iterations of (4 doublings; 4 table additions; 2 comb
            additions). The 4-way split HALVES the doubling chain of the
            classic 2-way layout (64 vs 128 doublings).
  compare   one Fermat inversion (254 squarings as fori_loop pow2k blocks)
            -> affine (x, y) -> limb compare against the raw signature R.

Design notes (TPU-first):
- Field elements are [..., 20] int32 arrays, radix 2^13, SIGNED limbs:
  TPU VPUs have no native int64, so v2's 10x26-bit int64 limbs were
  emulated; 13-bit limbs keep every product sum inside int32. Signed
  carried form ([-2, 2^13+3] per limb) makes subtraction margin-free —
  f_sub is just carry(f - g).
- Squarings (pt_double, inversion) use a symmetric schoolbook (f_sqr,
  ~half the products of f_mul).
- No data-dependent control flow: digit-driven point selection is a
  one-hot contraction, constant trip counts, static shapes. The whole
  batch advances in lockstep; the batch axis maps onto VPU lanes and
  shards cleanly across a device mesh (see plenum_tpu/parallel/).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# --- curve constants (RFC 8032) ------------------------------------------

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P          # -121665/121666 mod p
D2 = (2 * D) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
BX = 15112221349535400772501151409588531511454012693041857206046113283949847762202
BY = 46316835694926478169428394003475163141307993866256225615783033603165251855960

NLIMB = 20
RADIX = 13
MASK = (1 << RADIX) - 1
FOLD = 19 * 32          # 2^260 = 2^5 * 2^255 ≡ 19 * 32 (mod p)

WBITS = 4               # window width for the variable point A
N_WIN = 16              # windows per 64-bit quarter of h
N_QUARTERS = 4
QUARTER_SHIFT = 64      # h = sum_k 2^(64k) * h_k
CBITS = 8               # comb digit width for the fixed base B
N_COMB = 32             # comb positions for the 256-bit S

_I32 = jnp.int32


def int_to_limbs(x: int) -> np.ndarray:
    return np.array([(x >> (RADIX * i)) & MASK for i in range(NLIMB)],
                    dtype=np.int32)


def limbs_to_int(l) -> int:
    arr = np.asarray(l)
    return sum(int(v) << (RADIX * i) for i, v in enumerate(arr))


def _margin_limbs() -> np.ndarray:
    """40p as NLIMB limbs, each with a 2^13 floor — added before strict
    normalization so transiently-negative carried limbs (and values) lift
    to nonnegative without changing the residue mod p."""
    mult = 40
    k = [int((mult * P) >> (RADIX * i)) & MASK for i in range(NLIMB + 1)]
    k[NLIMB - 1] += k[NLIMB] << RADIX
    for i in range(NLIMB - 1):
        k[i] += 1 << RADIX
        k[i + 1] -= 1
    assert sum(v << (RADIX * i) for i, v in enumerate(k[:NLIMB])) == mult * P
    assert all((1 << RADIX) <= v < (1 << 16) for v in k[:NLIMB])
    return np.array(k[:NLIMB], dtype=np.int32)


_K_MARGIN = _margin_limbs()


# --- field ops ------------------------------------------------------------
#
# Bound discipline: "carried" means signed limbs in [-2, 2^13 + 3] (the
# output of _carry). f_mul/f_sqr REQUIRE carried inputs: products are then
# < 2^26.01, and a 20-term accumulation plus the fold contributions stays
# below 2^30.6 — inside int32. Unlike v2 there is NO lazy add/sub level:
# f_add/f_sub carry their output (3 cheap vector passes) so every operand
# everywhere is carried.

def _carry(c):
    """Three vectorized carry passes with the 2^260 -> FOLD wraparound.

    Pass math: c = (c & MASK) + shift(c >> 13), the top limb's carry
    folding to limb 0 via FOLD. Arithmetic >> floors, so transiently
    negative limbs are preserved exactly. |input| < 2^30.6 -> pass1
    < 2^27 (limb 0; others < 2^17.7) -> pass2 < 2^14.6 -> pass3 in
    [-2, 2^13 + 3] ("carried" form).
    """
    for _ in range(3):
        lo = c & MASK
        hi = c >> RADIX
        c = lo + jnp.concatenate(
            [hi[..., NLIMB - 1:] * FOLD, hi[..., :NLIMB - 1]], axis=-1)
    return c


def f_add(f, g):
    return _carry(f + g)


def f_sub(f, g):
    return _carry(f - g)


def _fold_coeffs(c: list):
    """Schoolbook coefficient list [2*NLIMB-1] -> NLIMB limbs via the
    2^260 ≡ FOLD wrap, splitting each high coefficient into 13-bit halves
    so the x608 products stay inside int32."""
    for k in range(2 * NLIMB - 2, NLIMB - 1, -1):
        lo = c[k] & MASK
        hi = c[k] >> RADIX
        c[k - NLIMB] = c[k - NLIMB] + lo * FOLD
        c[k - NLIMB + 1] = c[k - NLIMB + 1] + hi * FOLD
    return _carry(jnp.stack(c[:NLIMB], axis=-1))


def f_mul(f, g):
    # schoolbook convolution: 39 coefficients, 400 int32 products
    c = [jnp.zeros(jnp.broadcast_shapes(f.shape[:-1], g.shape[:-1]), _I32)
         for _ in range(2 * NLIMB - 1)]
    for i in range(NLIMB):
        fi = f[..., i]
        for j in range(NLIMB):
            c[i + j] = c[i + j] + fi * g[..., j]
    return _fold_coeffs(c)


def f_sqr(f):
    """Squaring: symmetric schoolbook, 210 products (~0.55x f_mul)."""
    f2 = f + f                      # limbs < 2^14.01, products < 2^27.02
    c = [jnp.zeros(f.shape[:-1], _I32) for _ in range(2 * NLIMB - 1)]
    for i in range(NLIMB):
        fi = f[..., i]
        c[2 * i] = c[2 * i] + fi * fi
        f2i = f2[..., i]
        for j in range(i + 1, NLIMB):
            c[i + j] = c[i + j] + f2i * f[..., j]
    return _fold_coeffs(c)


def _pow2k(z, k: int):
    """z^(2^k) as a k-iteration squaring loop."""
    return jax.lax.fori_loop(0, k, lambda i, v: f_sqr(v), z)


def _chain_250(z):
    """Shared prefix of the curve25519 exponentiation chains:
    -> (z^(2^250 - 1), z^11)."""
    z2 = f_sqr(z)                                     # 2
    z9 = f_mul(_pow2k(z2, 2), z)                      # 9
    z11 = f_mul(z9, z2)                               # 11
    z_5 = f_mul(f_sqr(z11), z9)                       # 2^5 - 1
    z_10 = f_mul(_pow2k(z_5, 5), z_5)                 # 2^10 - 1
    z_20 = f_mul(_pow2k(z_10, 10), z_10)              # 2^20 - 1
    z_40 = f_mul(_pow2k(z_20, 20), z_20)              # 2^40 - 1
    z_50 = f_mul(_pow2k(z_40, 10), z_10)              # 2^50 - 1
    z_100 = f_mul(_pow2k(z_50, 50), z_50)             # 2^100 - 1
    z_200 = f_mul(_pow2k(z_100, 100), z_100)          # 2^200 - 1
    return f_mul(_pow2k(z_200, 50), z_50), z11        # 2^250 - 1


def f_inv(z):
    """z^(p-2) (Fermat inversion) via the standard curve25519 addition
    chain: 254 squarings (grouped into pow2k fori_loops so the compiled
    graph stays small) + 11 multiplies.

    Needed to compress the recomputed R' on device (affine y = Y/Z), which
    is what lets verification compare raw signature bytes instead of paying
    a pure-Python modular sqrt per signature on host to decompress R.
    """
    z_250, z11 = _chain_250(z)
    return f_mul(_pow2k(z_250, 5), z11)               # 2^255 - 21 = p - 2


def f_pow_p58(z):
    """z^((p-5)/8) = z^(2^252 - 3) — the sqrt-candidate exponent of the
    RFC 8032 §5.1.3 decompression for p = 5 mod 8. (2^250-1)*4 + 1."""
    z_250, _ = _chain_250(z)
    return f_mul(_pow2k(z_250, 2), z)


def _carry_strict(c):
    """Fully normalized limbs in [0, 2^13) via _carry + two sequential
    signed borrow passes (arithmetic >> floors, so borrows propagate).
    Only used on the cold path (f_canon)."""
    c = _carry(c)
    for _ in range(2):
        out = []
        carry = 0
        for i in range(NLIMB):
            v = c[..., i] + carry
            carry = v >> RADIX
            out.append(v & MASK)
        c = jnp.stack(out, axis=-1).at[..., 0].add(carry * FOLD)
    return c


_TOP_BITS = 255 - (NLIMB - 1) * RADIX    # bits of limb 19 below 2^255 (= 8)


def f_canon(f):
    """Canonical form in [0, p).

    Carried limb form encodes values up to ~2^260 ≈ 32p (and transiently
    negative ones), so conditional subtraction alone is NOT enough: add a
    40p margin (limb floors restore positivity), fold the bits at and
    above 2^255 down with weight 19, then subtract p up to two times.
    """
    f = _carry_strict(f + jnp.asarray(_K_MARGIN))
    top = f[..., NLIMB - 1] >> _I32(_TOP_BITS)
    f = f.at[..., NLIMB - 1].set(
        f[..., NLIMB - 1] & _I32((1 << _TOP_BITS) - 1))
    f = f.at[..., 0].add(top * 19)
    f = _carry_strict(f)
    p_limbs = jnp.asarray(int_to_limbs(P))
    for _ in range(2):
        # compare f >= p lexicographically from the top limb
        ge = jnp.ones(f.shape[:-1], dtype=bool)
        gt = jnp.zeros(f.shape[:-1], dtype=bool)
        for i in range(NLIMB - 1, -1, -1):
            gt = gt | (ge & (f[..., i] > p_limbs[i]))
            ge = ge & (f[..., i] >= p_limbs[i])
        take = (gt | ge)
        f = _carry_strict(f - jnp.where(take[..., None], p_limbs, 0))
    return f


# --- point ops: extended twisted Edwards (X:Y:Z:T), a = -1 ----------------
# Identity is (0, 1, 1, 0). Every coordinate in and out is CARRIED.

def pt_add(p1, p2):
    """Unified addition (add-2008-hwcd-3): complete, handles identity & P+P."""
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = f_mul(f_sub(y1, x1), f_sub(y2, x2))
    b = f_mul(f_add(y1, x1), f_add(y2, x2))
    c = f_mul(f_mul(t1, t2), jnp.asarray(int_to_limbs(D2)))
    zz = f_mul(z1, z2)
    d = f_add(zz, zz)
    e = f_sub(b, a)
    f_ = f_sub(d, c)
    g = f_add(d, c)
    h = f_add(b, a)
    return (f_mul(e, f_), f_mul(g, h), f_mul(f_, g), f_mul(e, h))


def pt_add_t2d(p1, q):
    """Addition where the second operand carries a precomputed 2d*T
    coordinate: q = (X2, Y2, Z2, T2D2) — saves the d2 multiply."""
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2d2 = q
    a = f_mul(f_sub(y1, x1), f_sub(y2, x2))
    b = f_mul(f_add(y1, x1), f_add(y2, x2))
    c = f_mul(t1, t2d2)
    zz = f_mul(z1, z2)
    d = f_add(zz, zz)
    e = f_sub(b, a)
    f_ = f_sub(d, c)
    g = f_add(d, c)
    h = f_add(b, a)
    return (f_mul(e, f_), f_mul(g, h), f_mul(f_, g), f_mul(e, h))


def pt_madd(p1, ypx, ymx, t2d):
    """Mixed addition with an affine niels point (y+x, y-x, 2d*x*y),
    Z = 1 implied — the fixed-base comb form (7 multiplies).
    The niels identity is (1, 1, 0)."""
    x1, y1, z1, t1 = p1
    a = f_mul(f_sub(y1, x1), ymx)
    b = f_mul(f_add(y1, x1), ypx)
    c = f_mul(t1, t2d)
    d = f_add(z1, z1)
    e = f_sub(b, a)
    f_ = f_sub(d, c)
    g = f_add(d, c)
    h = f_add(b, a)
    return (f_mul(e, f_), f_mul(g, h), f_mul(f_, g), f_mul(e, h))


def pt_double(p1):
    """dbl-2008-hwcd for a = -1 (ref10 sign convention): 4 squarings +
    4 multiplies."""
    x1, y1, z1, _ = p1
    a = f_sqr(x1)
    b = f_sqr(y1)
    zz = f_sqr(z1)
    c = f_add(zz, zz)
    h = f_add(a, b)
    e = f_sub(h, f_sqr(f_add(x1, y1)))
    g = f_sub(a, b)
    f_ = f_add(c, g)
    return (f_mul(e, f_), f_mul(g, h), f_mul(f_, g), f_mul(e, h))


# --- host-side extended-coordinate helpers (Python ints) ------------------

def _ext_add_int(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = D2 * t1 * t2 % P
    dd = 2 * z1 * z2 % P
    e, f, g, h = b - a, dd - c, dd + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _ext_dbl_int(p):
    x1, y1, z1, _ = p
    a = x1 * x1 % P
    b = y1 * y1 % P
    c = 2 * z1 * z1 % P
    h = a + b
    e = h - (x1 + y1) * (x1 + y1)
    g = a - b
    f = c + g
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def ext_quarters(pt: tuple[int, int]) -> np.ndarray:
    """Affine host point -> int32[4, 4, NLIMB]: the four quarter points
    [2^(64k)]pt for k = 0..3 in extended coordinates (X:Y:Z:T). The chain
    is 192 extended doublings with NO modular inversions (T is tracked
    through _ext_dbl_int), which keeps the per-new-verkey host cost low."""
    x, y = pt
    p = (x, y, 1, x * y % P)
    out = np.zeros((N_QUARTERS, 4, NLIMB), np.int32)
    for k in range(N_QUARTERS):
        for c in range(4):
            out[k, c] = int_to_limbs(p[c])
        if k != N_QUARTERS - 1:
            for _ in range(QUARTER_SHIFT):
                p = _ext_dbl_int(p)
    return out


# --- fixed-base comb table (host-built, one batch inversion) --------------

_B_COMB: np.ndarray | None = None   # float32[N_COMB, 256, 3*NLIMB]


def b_comb_table() -> np.ndarray:
    """32 position tables for the fixed base B: T[w][d] = d*256^w*B as
    affine niels rows (y+x, y-x, 2d*x*y), entry 0 the niels identity
    (1, 1, 0). Stored as float32 so selection is ONE one-hot matmul per
    position (values < 2^13 are exact in f32) riding the MXU."""
    global _B_COMB
    if _B_COMB is not None:
        return _B_COMB
    base = (BX, BY, 1, BX * BY % P)
    ext: list[list[tuple]] = []
    for w in range(N_COMB):
        row = [base]
        for _ in range(2, 256):
            row.append(_ext_add_int(row[-1], base))
        ext.append(row)
        if w != N_COMB - 1:
            for _ in range(CBITS):
                base = _ext_dbl_int(base)
    zs = [p[2] for row in ext for p in row]
    prefix = [1]
    for z in zs:
        prefix.append(prefix[-1] * z % P)
    inv_all = pow(prefix[-1], P - 2, P)
    zinv = [0] * len(zs)
    for i in range(len(zs) - 1, -1, -1):
        zinv[i] = prefix[i] * inv_all % P
        inv_all = inv_all * zs[i] % P
    tab = np.zeros((N_COMB, 256, 3, NLIMB), np.float32)
    for w in range(N_COMB):
        tab[w, 0, 0] = int_to_limbs(1)      # identity niels: (1, 1, 0)
        tab[w, 0, 1] = int_to_limbs(1)
        for d in range(1, 256):
            x, y, _, _ = ext[w][d - 1]
            zi = zinv[w * 255 + d - 1]
            xa, ya = x * zi % P, y * zi % P
            tab[w, d, 0] = int_to_limbs((ya + xa) % P)
            tab[w, d, 1] = int_to_limbs((ya - xa) % P)
            tab[w, d, 2] = int_to_limbs(D2 * xa * ya % P)
    _B_COMB = tab.reshape(N_COMB, 256, 3 * NLIMB)
    return _B_COMB


# --- the kernel -----------------------------------------------------------

def _build_a_tables(qx, qy, qz, qt):
    """16-entry window tables for all four quarters in one batched build.

    q* are [4*n, NLIMB] int32: the stacked quarter points (extended,
    PROJECTIVE — Z need not be 1, which is what lets the host skip
    inversions). Returns 4 arrays [16, 4*n, NLIMB] (x, y, z, t2d) —
    entry d = [d]q, entry 0 = identity.

    Built as a 7-step fori_loop (tab[2k] = dbl(tab[k]);
    tab[2k+1] = tab[2k] + q) so the compiled graph stays small.
    """
    m = qx.shape[0]
    ones = jnp.broadcast_to(jnp.asarray(int_to_limbs(1)), (m, NLIMB))
    tx = jnp.zeros((16, m, NLIMB), _I32).at[1].set(qx)
    ty = jnp.zeros((16, m, NLIMB), _I32).at[0].set(ones).at[1].set(qy)
    tz = jnp.zeros((16, m, NLIMB), _I32).at[0].set(ones).at[1].set(qz)
    tt = jnp.zeros((16, m, NLIMB), _I32).at[1].set(qt)
    q = (qx, qy, qz, qt)

    def body(k, tabs):
        pk = tuple(t[k] for t in tabs)
        dbl = pt_double(pk)
        odd = pt_add(dbl, q)
        k2 = 2 * k
        out = []
        for t, dv, ov in zip(tabs, dbl, odd):
            t = jax.lax.dynamic_update_index_in_dim(t, dv, k2, axis=0)
            t = jax.lax.dynamic_update_index_in_dim(t, ov, k2 + 1, axis=0)
            out.append(t)
        return tuple(out)

    tx, ty, tz, tt = jax.lax.fori_loop(1, 8, body, (tx, ty, tz, tt))
    t2d = f_mul(tt, jnp.asarray(int_to_limbs(D2)))     # one stacked multiply
    return tx, ty, tz, t2d


def stage_on(device, *arrays):
    """Commit staged host arrays to ONE chip of a multi-device pipeline.

    jax.jit executes where its (committed) inputs live, so pinning the
    staged payload is the whole per-lane sharding entry point: lane k's
    verifier stages onto devices[k] and the SAME compiled kernel runs
    there, one executable per device. `device=None` keeps today's
    uncommitted behavior (backend default device)."""
    import jax.numpy as jnp
    if device is None:
        return tuple(jnp.asarray(a) for a in arrays)
    return tuple(jax.device_put(a, device) for a in arrays)


@jax.jit
def verify_kernel_indexed(s_digits, h_digits, aq_unique, idx, ry, r_sign):
    """verify_kernel with the verkey-derived quarter-point rows DEDUPED:
    aq_unique is int32[U, 4, 4, NLIMB] (one row per distinct verkey in
    the batch) and idx int32[N] maps each signature to its row. The
    gather runs on device, so the host->device payload shrinks from
    640 B/signature to 640 B/distinct key + 4 B/signature (aq was 73% of
    the dispatch bytes)."""
    aq = jnp.take(aq_unique, idx, axis=0)
    return verify_kernel(s_digits, h_digits, aq, ry, r_sign)


# --- device-side verkey decompression (the compressed dispatch path) ------

_P_LIMBS = int_to_limbs(P)


def _bytes_to_bits(u8):
    """uint8[..., 32] -> int32[..., 256] little-endian bits."""
    b = u8.astype(_I32)
    bits = (b[..., :, None] >> jnp.arange(8, dtype=_I32)) & _I32(1)
    return bits.reshape(*u8.shape[:-1], 256)


def _bits_to_limbs(bits):
    """int32[..., 256] bits -> int32[..., NLIMB] limbs of the low 255 bits.
    One f32 matmul against the bit->limb weight matrix (weights < 2^13 and
    each limb sums <= 13 bits -> exact in f32); bit 255 has zero weight."""
    w = jnp.asarray(_BIT_TO_LIMB, jnp.float32)
    return jnp.matmul(bits.astype(jnp.float32), w,
                      precision=jax.lax.Precision.HIGHEST).astype(_I32)


def _ge_p(y):
    """Lexicographic y >= p over canonical-limbed y (non-canonical point
    encodings must be REJECTED, matching host _precheck / RFC 8032)."""
    p_limbs = jnp.asarray(_P_LIMBS)
    gt = jnp.zeros(y.shape[:-1], bool)
    eq = jnp.ones(y.shape[:-1], bool)
    for i in range(NLIMB - 1, -1, -1):
        gt = gt | (eq & (y[..., i] > p_limbs[i]))
        eq = eq & (y[..., i] == p_limbs[i])
    return gt | eq


@jax.jit
def decompress_kernel(keys_u8):
    """Batched on-device verkey decompression -> quarter points of -A.

    keys_u8: uint8[U, 32] raw compressed verkeys (32 B each — what the
    host actually has; replaces the 1280 B/key limb rows of the indexed
    dispatch, a 40x transfer cut). Returns ((qx, qy, qz, qt) each int32[4, U, NLIMB] — the
    quarter points [2^64k](-A) stacked quarter-major — plus valid bool[U]).

    Math is RFC 8032 §5.1.3 (p = 5 mod 8): x = uv^3 (uv^7)^((p-5)/8),
    corrected by sqrt(-1) when v x^2 = -u; rejects y >= p, off-curve
    points, and x = 0 with the sign bit set — exactly the host-side
    `decompress` (kept as the differential-test twin). The 192-doubling
    quarter chain that the host used to pay in pure-Python bigints per
    NEW verkey runs here too, batched over the deduped key table.
    """
    bits = _bytes_to_bits(keys_u8)                       # [U, 256]
    sign = bits[..., 255]
    y = _bits_to_limbs(bits)                             # [U, NLIMB]
    noncanon = _ge_p(y)
    u_ = keys_u8.shape[0]
    one = jnp.broadcast_to(jnp.asarray(int_to_limbs(1)), (u_, NLIMB))
    y2 = f_sqr(y)
    u = f_sub(y2, one)
    v = f_add(f_mul(y2, jnp.asarray(int_to_limbs(D))), one)
    v3 = f_mul(f_sqr(v), v)
    v7 = f_mul(f_sqr(v3), v)
    x = f_mul(f_mul(u, v3), f_pow_p58(f_mul(u, v7)))
    vxx = f_mul(v, f_sqr(x))
    ok1 = jnp.all(f_canon(f_sub(vxx, u)) == 0, axis=-1)   # v x^2 =  u
    ok2 = jnp.all(f_canon(f_add(vxx, u)) == 0, axis=-1)   # v x^2 = -u
    x = jnp.where(ok1[..., None], x,
                  f_mul(x, jnp.asarray(int_to_limbs(SQRT_M1))))
    on_curve = ok1 | ok2
    xc = f_canon(x)
    x_zero = jnp.all(xc == 0, axis=-1)
    neg_xc = f_canon(f_sub(jnp.asarray(_P_LIMBS), xc))
    flip = (xc[..., 0] & _I32(1)) != sign
    # A = (x flipped to the sign bit, y); the kernel wants -A = (-x, y)
    negx = jnp.where(flip[..., None], xc, neg_xc)
    valid = on_curve & ~noncanon & ~(x_zero & (sign == 1))
    p0 = (negx, y, one, f_mul(negx, y))

    def _dbl64(p):
        return jax.lax.fori_loop(
            0, QUARTER_SHIFT, lambda i, a: pt_double(a), p)

    p1 = _dbl64(p0)
    p2 = _dbl64(p1)
    p3 = _dbl64(p2)
    qx, qy, qz, qt = (jnp.stack([p0[c], p1[c], p2[c], p3[c]])
                      for c in range(4))
    return (qx, qy, qz, qt), valid


def unpack_scalars_kernel(s_u8, h_u8, r_u8):
    """Raw per-signature byte payloads -> the kernel's digit/limb arrays.

    s_u8: uint8[N, 32] little-endian S (host-checked < L) -> the 8-bit
          comb digits ARE the bytes.
    h_u8: uint8[N, 32] little-endian h = SHA512(R||A||M) mod L; bytes
          8q..8q+7 are quarter q, split into 16 nibble windows each.
    r_u8: uint8[N, 32] raw R encoding -> (y limbs, sign bit).
    Replaces 468 B/signature of host-staged int32 digit arrays with
    100 B (s + h + R + idx) and moves the unpacking onto the device.
    """
    n = s_u8.shape[0]
    s_digits = s_u8.astype(_I32).T                       # [32, N]
    hb = h_u8.astype(_I32).reshape(n, N_QUARTERS, 8)
    nib = jnp.stack([hb & _I32(0xF), hb >> _I32(4)], axis=-1)
    h_digits = jnp.transpose(nib.reshape(n, N_QUARTERS, N_WIN),
                             (2, 1, 0))                  # [16, 4, N]
    rbits = _bytes_to_bits(r_u8)
    ry = _bits_to_limbs(rbits)
    return s_digits, h_digits, ry, rbits[..., 255]


@jax.jit
def verify_kernel_bytes(s_u8, h_u8, keys_u8, idx, r_u8):
    """THE compressed dispatch: every payload in raw bytes, everything
    else computed on device.

    Host ships 32 B S + 32 B h + 32 B R + 4 B key index per signature
    and 32 B per DISTINCT verkey; the device decompresses the keys,
    builds the window tables ONCE PER KEY (the indexed path built them
    per signature: 4N rows -> 4U rows, an N/U compute cut on top of the
    transfer cut), gathers per-signature table banks, and runs the
    double-scalar ladder. Signatures under an invalid key verify False.
    """
    n = idx.shape[0]
    u_ = keys_u8.shape[0]
    s_digits, h_digits, ry, r_sign = unpack_scalars_kernel(s_u8, h_u8, r_u8)
    (qx, qy, qz, qt), valid = decompress_kernel(keys_u8)
    tx, ty, tz, t2d = _build_a_tables(
        qx.reshape(-1, NLIMB), qy.reshape(-1, NLIMB),
        qz.reshape(-1, NLIMB), qt.reshape(-1, NLIMB))
    tab = jnp.stack([tx, ty, tz, t2d])                   # [4c, 16, 4U, L]
    tab = tab.reshape(4, 16, N_QUARTERS, u_, NLIMB)
    tabf = jnp.transpose(tab, (2, 3, 1, 0, 4)).astype(jnp.float32)
    tabf = tabf.reshape(N_QUARTERS, u_, 16, 4 * NLIMB)   # [q, U, d, 4L]
    tabf = jnp.take(tabf, idx, axis=1)                   # [q, N, d, 4L]
    ok = _banks_and_ladder(s_digits, h_digits, tabf, ry, r_sign, n)
    return ok & jnp.take(valid, idx)


@jax.jit
def verify_kernel(s_digits, h_digits, aq, ry, r_sign):
    """Batched check compress([S]B + [h](-A)) == R-bytes.

    This is the ref10/OpenSSL verification shape: recompute
    R' = [S]B - [h]A, compress it, and compare against the first 32
    signature bytes — the host never decompresses R (no per-signature
    modular sqrt; non-canonical or off-curve R encodings simply fail the
    compare, the same verdict OpenSSL gives).

    s_digits: int32[N_COMB, N] little-endian 8-bit comb digits of S.
    h_digits: int32[N_WIN, N_QUARTERS, N] little-endian 4-bit windows of
              the 64-bit quarters of h.
    aq:       int32[N, 4, 4, NLIMB] extended quarter points [2^64k](-A)
              (host-prepped; projective — Z need not be 1).
    ry:       int32[N, NLIMB] limbs of the low 255 bits of the R encoding.
    r_sign:   int32[N] top bit of the R encoding (x parity).
    Returns bool[N].
    """
    if s_digits.dtype != jnp.int32:
        raise TypeError("verify_kernel v3 takes int32 inputs")
    n = aq.shape[0]
    # quarter-major stacking: row k*n + i is quarter k of signature i
    qrows = jnp.moveaxis(aq, 0, 1)                     # [4, N, 4, NLIMB]
    tx, ty, tz, t2d = _build_a_tables(
        qrows[:, :, 0].reshape(-1, NLIMB), qrows[:, :, 1].reshape(-1, NLIMB),
        qrows[:, :, 2].reshape(-1, NLIMB), qrows[:, :, 3].reshape(-1, NLIMB))
    tab = jnp.stack([tx, ty, tz, t2d])                 # [4c, 16, 4N, L]
    tab = tab.reshape(4, 16, N_QUARTERS, n, NLIMB)
    tabf = jnp.transpose(tab, (2, 3, 1, 0, 4)).astype(jnp.float32)
    tabf = tabf.reshape(N_QUARTERS, n, 16, 4 * NLIMB)  # [q, N, d, 4L]
    return _banks_and_ladder(s_digits, h_digits, tabf, ry, r_sign, n)


def _banks_and_ladder(s_digits, h_digits, tabf, ry, r_sign, n):
    """The shared back half of both kernels: select operand banks from
    per-signature window tables (tabf [q, N, 16, 4L] f32), run the
    split-window + comb ladder, compress, compare against R."""
    ones = jnp.broadcast_to(jnp.asarray(int_to_limbs(1)), (n, NLIMB))
    zeros = jnp.zeros((n, NLIMB), _I32)
    # ---- operand banks: table selections precomputed outside the loop
    # (they depend only on digits, never on the accumulator).
    # A-tables vary per signature -> f32 one-hot einsum on the VPU
    # (exact: carried limbs < 2^14 << 2^24). B comb tables are batch
    # constants -> one-hot MATMUL on the MXU.
    oh_h = (h_digits[..., None] == jnp.arange(16, dtype=_I32)
            ).astype(jnp.float32)                      # [W, q, N, 16]
    bank_a = jnp.einsum('wqnd,qndl->wqnl', oh_h, tabf,
                        precision=jax.lax.Precision.HIGHEST)
    bank_a = bank_a.astype(_I32)                       # [W, q, N, 4L]

    oh_s = (s_digits[..., None] == jnp.arange(256, dtype=_I32)
            ).astype(jnp.float32)                      # [N_COMB, N, 256]
    cb = jnp.asarray(b_comb_table())                   # [N_COMB, 256, 3L]
    bank_b = jnp.einsum('wnd,wdl->wnl', oh_s, cb,
                        precision=jax.lax.Precision.HIGHEST)
    bank_b = bank_b.astype(_I32)                       # [N_COMB, N, 3L]

    def win_body(i, acc):
        t = N_WIN - 1 - i                  # MSB-first windows
        acc = jax.lax.fori_loop(0, WBITS, lambda _, a: pt_double(a), acc)
        qsel = jax.lax.dynamic_index_in_dim(bank_a, t, 0, keepdims=False)

        def add_q(k, a):
            row = qsel[k].reshape(n, 4, NLIMB)
            return pt_add_t2d(a, (row[:, 0], row[:, 1], row[:, 2],
                                  row[:, 3]))

        return jax.lax.fori_loop(0, N_QUARTERS, add_q, acc)

    acc = jax.lax.fori_loop(0, N_WIN, win_body, (zeros, ones, ones, zeros))

    def add_comb(w, a):
        # comb entries carry ABSOLUTE scale 256^w, so they must be added
        # after the doubling ladder has finished (zero remaining doublings)
        row = jax.lax.dynamic_index_in_dim(
            bank_b, w, 0, keepdims=False).reshape(n, 3, NLIMB)
        return pt_madd(a, row[:, 0], row[:, 1], row[:, 2])

    acc = jax.lax.fori_loop(0, N_COMB, add_comb, acc)
    px, py, pz, _ = acc
    # compress on device: affine (x, y) via one shared inversion of Z
    # (complete Edwards formulas keep Z != 0 for all valid inputs)
    zinv = f_inv(pz)
    x_aff = f_canon(f_mul(px, zinv))
    y_aff = f_canon(f_mul(py, zinv))
    ok_y = jnp.all(y_aff == ry, axis=-1)
    ok_sign = (x_aff[..., 0] & _I32(1)) == r_sign
    return ok_y & ok_sign


# --- host-side affine helpers (shared with tests & tools) -----------------

def edwards_add(p1: tuple[int, int], p2: tuple[int, int]) -> tuple[int, int]:
    """Affine Edwards addition over Python ints (host-side, no deps)."""
    x1, y1 = p1
    x2, y2 = p2
    dd = D * x1 * x2 * y1 * y2 % P
    x3 = (x1 * y2 + x2 * y1) * pow(1 + dd, P - 2, P) % P
    y3 = (y1 * y2 + x1 * x2) * pow(1 - dd + P, P - 2, P) % P
    return (x3, y3)


def edwards_mul(k: int, pt: tuple[int, int]) -> tuple[int, int]:
    acc = None
    add = pt
    while k:
        if k & 1:
            acc = add if acc is None else edwards_add(acc, add)
        add = edwards_add(add, add)
        k >>= 1
    return acc if acc is not None else (0, 1)


def compress(pt: tuple[int, int]) -> bytes:
    x, y = pt
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


_EXT_IDENTITY = (0, 1, 1, 0)


def ext_scalar_mul(k: int, pt: tuple[int, int]) -> tuple[int, int]:
    """[k]pt over Python ints in extended coordinates (one inversion at
    the end, vs one PER ADD in edwards_mul — ~30x faster; this is the
    ladder behind the no-deps sign/verify fallback)."""
    acc = _EXT_IDENTITY
    add = (pt[0], pt[1], 1, pt[0] * pt[1] % P)
    while k:
        if k & 1:
            acc = _ext_add_int(acc, add)
        add = _ext_dbl_int(add)
        k >>= 1
    return ext_to_affine(acc)


def ext_to_affine(p) -> tuple[int, int]:
    x, y, z, _t = p
    zi = pow(z, P - 2, P)
    return (x * zi % P, y * zi % P)


def ext_double_scalar_mul(s: int, p1: tuple[int, int],
                          h: int, p2: tuple[int, int]) -> tuple[int, int]:
    """[s]p1 + [h]p2 (Shamir interleave, MSB first) -> affine."""
    e1 = (p1[0], p1[1], 1, p1[0] * p1[1] % P)
    e2 = (p2[0], p2[1], 1, p2[0] * p2[1] % P)
    e12 = _ext_add_int(e1, e2)
    acc = _EXT_IDENTITY
    for i in range(max(s.bit_length(), h.bit_length()) - 1, -1, -1):
        acc = _ext_dbl_int(acc)
        b1, b2 = (s >> i) & 1, (h >> i) & 1
        if b1 and b2:
            acc = _ext_add_int(acc, e12)
        elif b1:
            acc = _ext_add_int(acc, e1)
        elif b2:
            acc = _ext_add_int(acc, e2)
    return ext_to_affine(acc)


def pure_python_verify(msg: bytes, sig: bytes, vk: bytes) -> bool:
    """RFC 8032 verification without external deps (ref10 semantics: the
    recomputed R' = [s]B - [h]A must BYTE-match the signature's R, no
    cofactor multiplication) — the cpu-backend fallback in environments
    without `cryptography`. Strict: rejects S >= L and non-canonical A."""
    import hashlib
    try:
        msg, sig, vk = bytes(msg), bytes(sig), bytes(vk)
    except Exception:
        return False
    if len(sig) != 64 or len(vk) != 32:
        return False
    A = decompress(vk)
    if A is None:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    h = int.from_bytes(hashlib.sha512(sig[:32] + vk + msg).digest(),
                       "little") % L
    neg_a = ((P - A[0]) % P, A[1])
    return compress(ext_double_scalar_mul(s, (BX, BY), h, neg_a)) == sig[:32]


def pure_python_sign(seed: bytes, msg: bytes) -> tuple[bytes, bytes]:
    """RFC 8032 signing without external deps -> (signature, verkey).
    For tools/tests/the graft entry in environments without `cryptography`."""
    import hashlib
    h = hashlib.sha512(seed).digest()
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    a |= 1 << 254
    prefix = h[32:]
    A = ext_scalar_mul(a, (BX, BY))
    vk = compress(A)
    r = int.from_bytes(hashlib.sha512(prefix + msg).digest(), "little") % L
    R = ext_scalar_mul(r, (BX, BY))
    r_enc = compress(R)
    k = int.from_bytes(hashlib.sha512(r_enc + vk + msg).digest(),
                       "little") % L
    s = (r + k * a) % L
    return r_enc + s.to_bytes(32, "little"), vk


def decompress(comp: bytes):
    """Verkey/R bytes -> affine point, or None if not on curve."""
    if len(comp) != 32:
        return None
    y = int.from_bytes(comp, "little")
    sign = y >> 255
    y &= (1 << 255) - 1
    if y >= P:
        return None
    y2 = y * y % P
    u = (y2 - 1) % P
    v = (D * y2 + 1) % P
    # sqrt(u/v) for p = 5 mod 8 (RFC 8032 §5.1.3)
    x = u * pow(v, 3, P) % P * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P) % P
    vxx = v * x * x % P
    if vxx == u % P:
        pass
    elif vxx == (P - u) % P:
        x = x * SQRT_M1 % P
    else:
        return None
    if x == 0 and sign:
        return None
    if x & 1 != sign:
        x = P - x
    return (x, y)


def scalar_windows(values: list[int], n_windows: int,
                   bits: int = WBITS) -> np.ndarray:
    """[n_windows, N] little-endian `bits`-wide digits (int32).

    Vectorized: one to_bytes per value (C speed), then numpy byte/nibble
    splitting — this runs on the per-dispatch host hot path."""
    nbytes = (n_windows * bits + 7) // 8
    raw = np.frombuffer(
        b"".join(v.to_bytes(nbytes, "little") for v in values),
        dtype=np.uint8).reshape(len(values), nbytes)
    if bits == 8:
        out = raw[:, :n_windows].astype(np.int32)
    elif bits == 4:
        nib = np.empty((len(values), 2 * nbytes), np.uint8)
        nib[:, 0::2] = raw & 0x0F
        nib[:, 1::2] = raw >> 4
        out = nib[:, :n_windows].astype(np.int32)
    else:
        raise ValueError(f"unsupported window width {bits}")
    return np.ascontiguousarray(out.T)


# bit b of a 255-bit little-endian value belongs to limb b//13, weight
# 2^(b%13); bit 255 is the sign bit (excluded)
_BIT_TO_LIMB = np.zeros((256, NLIMB), np.int32)
for _b in range(255):
    _BIT_TO_LIMB[_b, _b // RADIX] = 1 << (_b % RADIX)


def r_bytes_to_limbs(r_encodings: Sequence[bytes]) -> tuple[np.ndarray, np.ndarray]:
    """Raw 32-byte R encodings -> (y limbs int32[N, NLIMB], sign int32[N]).
    Vectorized: unpack bits little-endian, matmul against the bit->limb
    weight matrix (per-dispatch host hot path)."""
    raw = np.frombuffer(b"".join(bytes(e) for e in r_encodings),
                        dtype=np.uint8).reshape(len(r_encodings), 32)
    bits = np.unpackbits(raw, axis=1, bitorder="little")   # [N, 256]
    ry = bits.astype(np.int32) @ _BIT_TO_LIMB
    return ry, bits[:, 255].astype(np.int32)


def points_to_limbs(points: list[tuple[int, int]]) -> tuple[np.ndarray, ...]:
    """Affine points -> (x, y, z=1, t=x*y) limb arrays int32[N, NLIMB]."""
    n = len(points)
    arrs = tuple(np.zeros((n, NLIMB), np.int32) for _ in range(4))
    for i, (x, y) in enumerate(points):
        arrs[0][i] = int_to_limbs(x)
        arrs[1][i] = int_to_limbs(y)
        arrs[2][i] = int_to_limbs(1)
        arrs[3][i] = int_to_limbs(x * y % P)
    return arrs
