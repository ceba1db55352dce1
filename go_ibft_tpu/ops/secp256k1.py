"""secp256k1 curve arithmetic and batched ECDSA on TPU (JAX/XLA).

This is the data plane behind the reference's ``Verifier`` predicates
(go-ibft core/backend.go:37-56): where the reference calls
``IsValidValidator`` / ``IsValidCommittedSeal`` once per message under the
store lock (messages/messages.go:183-198), this module verifies or recovers
a whole round's signatures in one ``jit``-compiled, fixed-shape batch.

Design notes (TPU-first, not a port — the reference has no crypto at all):

* Field elements are radix-2**13 limb vectors (:mod:`.fields`), batched by
  broadcasting over leading axes; every op here is shape-static and
  branch-free so ``vmap``/``jit`` see one straight-line program.
* Points are Jacobian ``(X, Y, Z)`` with infinity encoded as ``Z == 0`` —
  exceptional cases (infinity operands, P == Q, P == -Q) are resolved with
  branchless selects, never Python control flow.
* Double-scalar multiplication ``k1*G + k2*Q`` uses Shamir's trick inside a
  single ``lax.scan`` of 256 fixed steps, so ECDSA verify and recovery cost
  one interleaved ladder instead of two.
* All public entry points accept/return limb arrays; host packing helpers
  live in :mod:`go_ibft_tpu.crypto`.

Curve: y**2 = x**3 + 7 over GF(P), group order N (both primes close under
2**256, so the pseudo-Mersenne folding path of :mod:`.fields` applies).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import fields, pallas_ladder
from .fields import LIMB_BITS, LIMB_MASK, Modulus

__all__ = [
    "P",
    "N",
    "GX",
    "GY",
    "FIELD",
    "ORDER",
    "JacobianPoint",
    "point_infinity",
    "point_double",
    "point_add",
    "to_affine",
    "is_infinity",
    "on_curve",
    "ecmul2_base",
    "glv_split",
    "ecdsa_verify",
    "ecdsa_recover",
]

# Curve constants (SEC 2 v2, "Recommended Parameters secp256k1").
P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

FIELD = Modulus(P)
ORDER = Modulus(N)
_L = FIELD.nlimbs  # == ORDER.nlimbs == 20


class JacobianPoint(NamedTuple):
    """Batched Jacobian point; each coordinate is an ``(..., 20)`` limb array."""

    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray


def point_infinity(batch_shape: Tuple[int, ...] = ()) -> JacobianPoint:
    one = jnp.broadcast_to(jnp.asarray(FIELD.const(1)), batch_shape + (_L,))
    zero = jnp.zeros(batch_shape + (_L,), dtype=jnp.int32)
    return JacobianPoint(one, one, zero)


def is_infinity(p: JacobianPoint) -> jnp.ndarray:
    return fields.is_zero_fast(FIELD, p.z)


def _sel_pt(cond: jnp.ndarray, a: JacobianPoint, b: JacobianPoint) -> JacobianPoint:
    return JacobianPoint(
        fields.select(cond, a.x, b.x),
        fields.select(cond, a.y, b.y),
        fields.select(cond, a.z, b.z),
    )


@jax.jit
def point_double(p: JacobianPoint) -> JacobianPoint:
    """Jacobian doubling, a = 0 case ("dbl-2009-l" shape).

    Safe for infinity (Z=0 stays Z=0); secp256k1 has no 2-torsion so Y=0
    never occurs on-curve.
    """
    f = FIELD
    a = fields.sqr(f, p.x)
    b = fields.sqr(f, p.y)
    c = fields.sqr(f, b)
    # D = 2*((X+B)^2 - A - C)
    t = fields.sqr(f, fields.add(f, p.x, b))
    d = fields.muli(f, fields.sub(f, fields.sub(f, t, a), c), 2)
    e = fields.muli(f, a, 3)
    ff = fields.sqr(f, e)
    x3 = fields.sub(f, ff, fields.muli(f, d, 2))
    y3 = fields.sub(f, fields.mul(f, e, fields.sub(f, d, x3)), fields.muli(f, c, 8))
    z3 = fields.muli(f, fields.mul(f, p.y, p.z), 2)
    return JacobianPoint(x3, y3, z3)


@jax.jit
def _point_add_core(p: JacobianPoint, q: JacobianPoint) -> JacobianPoint:
    """INCOMPLETE Jacobian addition: the generic formula (4 squarings + 12
    products) and the two infinity selects.

    Right for every pair except ``P == Q`` off infinity: there ``H == R ==
    0`` and the formula gives ``(0, 0, 0)`` where the answer is ``2P``.
    ``P == -Q`` needs nothing: ``H == 0, R != 0`` gives ``Z3 == 0``.  Two
    callers only (``tests/test_ladder_structure.py`` holds the tree to it):
    :func:`point_add`, which overlays the doubling, and the scan body of
    :func:`ecmul2_base`, whose operands provably never coincide.  Both call
    it at the ladder's batch (``(4,) + batch``, folded where
    :func:`ecmul2_base` folds it) and use all of what it returns, so a program
    instantiates it once (an output one caller drops is pruned from that
    caller's copy, and the copies no longer dedup).
    """
    f = FIELD
    z1s = fields.sqr(f, p.z)
    z2s = fields.sqr(f, q.z)
    u1 = fields.mul(f, p.x, z2s)
    u2 = fields.mul(f, q.x, z1s)
    s1 = fields.mul(f, p.y, fields.mul(f, z2s, q.z))
    s2 = fields.mul(f, q.y, fields.mul(f, z1s, p.z))
    h = fields.sub(f, u2, u1)
    r = fields.sub(f, s2, s1)
    hs = fields.sqr(f, h)
    hc = fields.mul(f, hs, h)
    u1hs = fields.mul(f, u1, hs)
    x3 = fields.sub(f, fields.sub(f, fields.sqr(f, r), hc), fields.muli(f, u1hs, 2))
    y3 = fields.sub(
        f, fields.mul(f, r, fields.sub(f, u1hs, x3)), fields.mul(f, s1, hc)
    )
    z3 = fields.mul(f, fields.mul(f, p.z, q.z), h)
    out = _sel_pt(is_infinity(p), q, JacobianPoint(x3, y3, z3))
    return _sel_pt(is_infinity(q), p, out)


@jax.jit
def point_add(p: JacobianPoint, q: JacobianPoint) -> JacobianPoint:
    """Complete Jacobian addition via branchless selects.

    Handles all exceptional cases: either operand at infinity, P == Q
    (falls back to doubling), and P == -Q (returns infinity, which the
    generic formula produces naturally since H == 0, R != 0 => Z3 == 0).
    It is :func:`_point_add_core` with the doubling laid over the lanes
    where ``P == Q``, read off the core's own result: with neither operand
    at infinity ``Z3 = Z1*Z2*H`` is zero exactly where ``H`` is, and there
    ``X3 = R**2`` is zero exactly where ``R`` is.  With ``P`` alone at
    infinity the core returned ``Q`` (``Z != 0``), with ``Q`` alone ``P``;
    with both it returned ``P``, which ``~is_infinity(p)`` leaves alone.
    """
    out = _point_add_core(p, q)
    same = fields.is_zero_fast(FIELD, out.z) & fields.is_zero_fast(FIELD, out.x)
    return _sel_pt(same & ~is_infinity(p), point_double(p), out)


def _inv_lanes(m: fields.Modulus, a: jnp.ndarray) -> jnp.ndarray:
    """Inverse, amortized across a single batch axis when one exists.

    A ``(B, L)`` input takes the Montgomery product tree (ONE Fermat scan
    for the whole batch); any other shape falls back to per-lane Fermat.
    Trace-time decision — shapes are static under jit."""
    if a.ndim == 2 and a.shape[0] >= 2:
        return fields.batch_inv(m, a)
    return fields.inv(m, a)


@jax.jit
def to_affine(p: JacobianPoint) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Canonical affine ``(x, y)``; infinity maps to ``(0, 0)``.

    Where :func:`pallas_ladder.kind` says ``pallas`` every lane is inverted
    by its own Fermat chain in one kernel (``to_affine_inv``): the product
    tree amortizes a scan that costs a millisecond whatever its width, and a
    329-product chain on a whole tile costs what the root's alone would.
    The coordinates are ``canon``ed, so both paths give the same bits."""
    f = FIELD
    # inv(0) == 0 either way (0 ** (P - 2)), so infinity folds to (0, 0)
    if pallas_ladder.kind(math.prod(p.z.shape[:-1])) == pallas_ladder.PALLAS:
        (zinv,) = pallas_ladder.fixed_powers("to_affine_inv", (f, p.z, P - 2))
    else:
        zinv = _inv_lanes(f, p.z)
    zi2 = fields.sqr(f, zinv)
    x = fields.mul(f, p.x, zi2)
    y = fields.mul(f, p.y, fields.mul(f, zi2, zinv))
    return fields.canon(f, x), fields.canon(f, y)


@jax.jit
def on_curve(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Affine on-curve test: y^2 == x^3 + 7 (mod P)."""
    f = FIELD
    lhs = fields.sqr(f, y)
    rhs = fields.add(
        f, fields.mul(f, fields.sqr(f, x), x), jnp.asarray(f.const(7))
    )
    return fields.eq_mod(f, lhs, rhs)


def point_add_mixed(
    p: JacobianPoint, qx: jnp.ndarray, qy: jnp.ndarray
) -> JacobianPoint:
    """Complete mixed addition (affine addend, Z2 == 1): ~11 field muls.

    Exceptional cases handled branchlessly: P at infinity -> Q; P == Q ->
    doubling; P == -Q -> infinity (Z3 == 0 falls out of the formula)."""
    f = FIELD
    z1s = fields.sqr(f, p.z)
    u2 = fields.mul(f, qx, z1s)
    s2 = fields.mul(f, qy, fields.mul(f, z1s, p.z))
    h = fields.sub(f, u2, p.x)
    r = fields.sub(f, s2, p.y)
    hs = fields.sqr(f, h)
    hc = fields.mul(f, hs, h)
    u1hs = fields.mul(f, p.x, hs)
    x3 = fields.sub(f, fields.sub(f, fields.sqr(f, r), hc), fields.muli(f, u1hs, 2))
    y3 = fields.sub(
        f, fields.mul(f, r, fields.sub(f, u1hs, x3)), fields.mul(f, p.y, hc)
    )
    z3 = fields.mul(f, p.z, h)
    generic = JacobianPoint(x3, y3, z3)

    same_x = fields.is_zero_fast(f, h)
    same_y = fields.is_zero_fast(f, r)
    out = _sel_pt(same_x & same_y, point_double(p), generic)
    one = jnp.broadcast_to(jnp.asarray(f.const(1)), p.z.shape)
    return _sel_pt(is_infinity(p), JacobianPoint(qx, qy, one), out)


_WINDOW = 4
_NWIN = 64  # 256 / 4


def _double4(p: JacobianPoint) -> JacobianPoint:
    """Four successive doublings as a length-4 inner scan.

    Four INLINE ``point_double`` calls instantiate the doubling body four
    times inside the ladder's scan body — ~7k stablehlo lines that are pure
    compile time (the XLA:CPU budget tracks trace size,
    ``scripts/compile_budget.py``).  A fixed-length inner scan traces it ONCE; the
    cost is 3 extra While iterations per ladder window (~100 per recover),
    noise against the ~40 field muls each iteration already runs.
    """
    return jax.lax.scan(lambda q, _: (point_double(q), None), p, None, length=4)[0]

# ---------------------------------------------------------------------------
# GLV endomorphism (secp256k1 has CM discriminant -3): phi(x, y) = (BETA*x, y)
# acts as scalar multiplication by LAMBDA, where BETA**3 == 1 (mod P) and
# LAMBDA**3 == 1 (mod N).  Splitting a scalar k = k1 + k2*LAMBDA with
# |k1|, |k2| < 2**129 halves the ladder length: 33 four-bit windows over
# FOUR half-length digit streams (G, phi(G), Q, phi(Q)) instead of 64
# windows over two full-length ones — 132 shared doublings instead of 256.
# Constants derived via extended Euclid on (N, LAMBDA) (GLV method; see
# /tmp-free derivation in tests/test_secp256k1.py::test_glv_constants).
# ---------------------------------------------------------------------------
_LAMBDA = 0x5363AD4CC05C30E0A5261C028812645A122E22EA20816678DF02967C1B23BD72
_BETA = 0x7AE96A2B657C07106E64479EAC3434E99CF0497512F58995C1396C28719501EE
# Short lattice basis v1 = (A1, B1), v2 = (A2, B2) of
# {(x, y) : x + y*LAMBDA === 0 (mod N)}.
_GLV_A1 = 0x3086D221A7D46BCDE86C90E49284EB15
_GLV_B1 = -0xE4437ED6010E88286F547FA90ABFE4C3
_GLV_A2 = 0x114CA50F7A8E2F3F657C1108D9D44CFD8
_GLV_B2 = 0x3086D221A7D46BCDE86C90E49284EB15
# Rounding multipliers: round(k * Gi / 2**384) == round(k * |b_i| / N)
# exactly for all k < N (384 bits of precision leave the rounded quotient
# off by at most 1, which the +-2**129 half-scalar bound absorbs; validated
# exhaustively against the exact-rational formula in tests).
_GLV_SHIFT = 384
_GLV_G1 = (_GLV_B2 * (1 << _GLV_SHIFT) + N // 2) // N
_GLV_G2 = (-_GLV_B1 * (1 << _GLV_SHIFT) + N // 2) // N

assert pow(_LAMBDA, 3, N) == 1 and _LAMBDA != 1
assert pow(_BETA, 3, P) == 1 and _BETA != 1
assert (_GLV_A1 + _GLV_B1 * _LAMBDA) % N == 0
assert (_GLV_A2 + _GLV_B2 * _LAMBDA) % N == 0

_GLV_HL = 11  # half-scalar limb count: 143 bits >= 129-bit magnitude + sign
_GLV_NWIN = 33  # 4-bit windows covering 132 bits
_GLV_G1_L = fields.to_limbs([_GLV_G1], _L)[0]
_GLV_G2_L = fields.to_limbs([_GLV_G2], _L)[0]
_GLV_A1_L = fields.to_limbs([_GLV_A1], _GLV_HL)[0]
_GLV_A2_L = fields.to_limbs([_GLV_A2], _GLV_HL)[0]
_GLV_NB1_L = fields.to_limbs([-_GLV_B1], _GLV_HL)[0]
_GLV_B2_L = fields.to_limbs([_GLV_B2], _GLV_HL)[0]
# k*G fits 512 bits; + the 2**383 rounding addend stays under 13*41 bits.
_GLV_PROD_LEN = 41
_GLV_ROUND = np.zeros(_GLV_PROD_LEN, dtype=np.int32)
_GLV_ROUND[_GLV_SHIFT // LIMB_BITS] = 1 << (_GLV_SHIFT % LIMB_BITS - 1)


def _glv_round_shift(k: jnp.ndarray, g_limbs: np.ndarray) -> jnp.ndarray:
    """``round((k * g) / 2**384)`` exactly, as an ``(..., 11)`` limb vector.

    ``k`` canonical ``(..., 20)``; ``g`` a static 256-bit constant.  The
    full 533-bit product is normalized (lazy carries + Kogge-Stone exact
    pass — no sequential limb scan), then bits >= 384 are re-packed into
    13-bit limbs."""
    z = fields._conv(k, jnp.asarray(g_limbs), _GLV_PROD_LEN)
    z = z + jnp.asarray(_GLV_ROUND)
    z = fields._carry(z, 4)
    z = fields._ks_carry(z)
    base = _GLV_SHIFT // LIMB_BITS  # 29, shift-within-limb 7
    lo = z[..., base : base + _GLV_HL] >> 7
    hi = (z[..., base + 1 : base + 1 + _GLV_HL] << 6) & LIMB_MASK
    return lo | hi


def _q_window_table(
    batch: Tuple[int, ...], qx: jnp.ndarray, qy: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-batch window table ``T[d] = d*Q`` (Jacobian; T[0] = infinity),
    stacked as ``(16, ..., L)`` coordinate arrays.

    Built with a 14-step ``lax.scan`` rather than 14 unrolled mixed adds:
    each unrolled add is ~2*10^3 HLO ops and the table sits inside the
    repo's largest fused programs — on XLA:CPU trace size IS compile time
    (an unrolled table pushed the fused certify compile past 25 minutes).
    """
    one = jnp.asarray(FIELD.const(1))
    q_pt = JacobianPoint(qx, qy, jnp.broadcast_to(one, batch + (_L,)))
    inf = point_infinity(batch)

    def tab_body(prev, _):
        nxt = point_add_mixed(prev, qx, qy)
        return nxt, nxt

    _, tail = jax.lax.scan(tab_body, q_pt, None, length=14)  # 2Q .. 15Q
    qtx = jnp.concatenate([inf.x[None], q_pt.x[None], tail.x])
    qty = jnp.concatenate([inf.y[None], q_pt.y[None], tail.y])
    qtz = jnp.concatenate([inf.z[None], q_pt.z[None], tail.z])
    return qtx, qty, qtz


def _conv_lo(a: jnp.ndarray, b: np.ndarray, n: int) -> jnp.ndarray:
    """Low ``n`` limb-columns of the schoolbook product (mod-2**(13n)).

    The GLV signed combinations only need the value mod 2**143; columns
    >= n fall off :func:`fields._conv`'s truncating slice (every retained
    column sum stays < 2**31 in int32)."""
    return fields._conv(a, jnp.asarray(b), n)


def _glv_neg143(r: jnp.ndarray) -> jnp.ndarray:
    """``2**143 - r`` for ``0 < r < 2**143`` in 11 canonical limbs."""
    flipped = LIMB_MASK - r
    flipped = flipped.at[..., 0].add(1)
    return fields._exact_carry(flipped)


def glv_split(
    k: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Decompose canonical ``k < N`` into ``k === s1*|k1| + s2*|k2|*LAMBDA``.

    Returns ``(abs1, neg1, abs2, neg2)``: magnitudes as ``(..., 11)`` limb
    vectors < 2**129 and sign flags (True = negative).  All arithmetic is
    exact: the signed combinations are evaluated mod 2**143 in int32 limb
    space (conv columns stay < 2**31) and the sign read off bit 142.
    """
    c1 = _glv_round_shift(k, _GLV_G1_L)
    c2 = _glv_round_shift(k, _GLV_G2_L)

    def signed(parts):
        s = parts[0]
        for term in parts[1:]:
            s = s + term
        r = fields._exact_carry(s)  # >> and & floor correctly on negatives
        neg = (r[..., _GLV_HL - 1] >> 12) == 1
        return fields.select(neg, _glv_neg143(r), r), neg

    t1 = _conv_lo(c1, _GLV_A1_L, _GLV_HL)
    t2 = _conv_lo(c2, _GLV_A2_L, _GLV_HL)
    abs1, neg1 = signed([k[..., :_GLV_HL], -t1, -t2])  # k - c1*a1 - c2*a2
    u1 = _conv_lo(c1, _GLV_NB1_L, _GLV_HL)
    u2 = _conv_lo(c2, _GLV_B2_L, _GLV_HL)
    abs2, neg2 = signed([u1, -u2])  # -c1*b1 - c2*b2
    return abs1, neg1, abs2, neg2


def _precompute_g_table() -> Tuple[np.ndarray, np.ndarray]:
    """Fixed-base window table: entry [d] = d * G, affine, d in 1..15.

    Computed once at import with host integer arithmetic; the table is tiny
    ((16, 20) int32 x 2) and closes over the jit as a constant.  The
    ladder's four shared doublings per scan step supply the ``16**j``
    scaling for BOTH scalar terms, so the G entries must NOT be pre-scaled
    by ``16**j`` — a pre-scaled comb riding the same ladder would scale the
    G term by ``16**j`` twice (regression: ``ecmul2_base(16, 0, G)`` must
    equal ``16*G``, not ``256*G``).
    """
    from ..crypto import ecdsa as _host

    from .fields import to_limbs

    gx_tab = np.zeros((16, _L), dtype=np.int32)
    gy_tab = np.zeros((16, _L), dtype=np.int32)
    pt = None
    for d in range(1, 16):
        pt = _host._add(pt, (GX, GY))
        gx_tab[d] = to_limbs([pt[0]], _L)[0]
        gy_tab[d] = to_limbs([pt[1]], _L)[0]
    return gx_tab, gy_tab


_G_TAB_X, _G_TAB_Y = _precompute_g_table()


def _precompute_glv_g_table() -> np.ndarray:
    """GLV companion to the fixed-base window table.

    ``phi`` maps affine ``(x, y)`` to ``(BETA*x, y)`` and commutes with
    scalar multiplication, so the ``d*phi(G)`` table is the ``d*G`` table
    with x scaled by BETA (shared y; sign flips happen at gather time in
    the ladder, so no negated table is stored).
    """
    from .fields import from_limbs, to_limbs

    gpx = np.zeros((16, _L), dtype=np.int32)
    xs = from_limbs(_G_TAB_X)
    for d in range(1, 16):
        gpx[d] = to_limbs([(_BETA * xs[d]) % P], _L)[0]
    return gpx


_GP_TAB_X = _precompute_glv_g_table()

# Static nibble-extraction indices: bit position 4j may straddle a 13-bit
# limb boundary; precompute (limb, shift, need-hi) per window.
_NIB_POS = np.arange(_NWIN - 1, -1, -1) * _WINDOW  # MSB-first
_NIB_LIMB = _NIB_POS // LIMB_BITS
_NIB_OFF = _NIB_POS % LIMB_BITS
_NIB_HI = np.minimum(_NIB_LIMB + 1, 19)
_NIB_NEEDHI = (_NIB_OFF > LIMB_BITS - _WINDOW).astype(np.int32)


def _scalar_nibbles_msb(k: jnp.ndarray) -> jnp.ndarray:
    """4-bit windows of canonical scalar ``k``, MSB first: ``(64,) + batch``."""
    lo = jnp.take(k, jnp.asarray(_NIB_LIMB), axis=-1) >> jnp.asarray(
        _NIB_OFF.astype(np.int32)
    )
    hi = jnp.take(k, jnp.asarray(_NIB_HI), axis=-1) << jnp.asarray(
        (LIMB_BITS - _NIB_OFF).astype(np.int32)
    )
    nib = (lo | hi * jnp.asarray(_NIB_NEEDHI)) & 0xF
    return jnp.moveaxis(nib, -1, 0)


# Branchless 16-way gather (4-level where tree, NOT a one-hot einsum — an
# int32 dot_general per scan step lowers poorly on TPU; measured r03,
# scripts/ab_ladder_select.py).  Now shared with fields.pow_fixed.
_one_hot_select = fields.select16

# Static nibble tables for GLV half-scalars: 33 MSB-first 4-bit windows of
# an 11-limb (143-bit) magnitude (bits 132..142 are provably zero).
_GNIB_POS = np.arange(_GLV_NWIN - 1, -1, -1) * _WINDOW
_GNIB_LIMB = _GNIB_POS // LIMB_BITS
_GNIB_OFF = _GNIB_POS % LIMB_BITS
_GNIB_HI = np.minimum(_GNIB_LIMB + 1, _GLV_HL - 1)
_GNIB_NEEDHI = (_GNIB_OFF > LIMB_BITS - _WINDOW).astype(np.int32)


def _glv_nibbles_msb(k: jnp.ndarray) -> jnp.ndarray:
    """4-bit windows of an 11-limb magnitude, MSB first: ``(33,) + batch``."""
    lo = jnp.take(k, jnp.asarray(_GNIB_LIMB), axis=-1) >> jnp.asarray(
        _GNIB_OFF.astype(np.int32)
    )
    hi = jnp.take(k, jnp.asarray(_GNIB_HI), axis=-1) << jnp.asarray(
        (LIMB_BITS - _GNIB_OFF).astype(np.int32)
    )
    nib = (lo | hi * jnp.asarray(_GNIB_NEEDHI)) & 0xF
    return jnp.moveaxis(nib, -1, 0)


@jax.jit
def _ecmul2_base_shamir(
    k1: jnp.ndarray, k2: jnp.ndarray, qx: jnp.ndarray, qy: jnp.ndarray
) -> JacobianPoint:
    """Pre-GLV double-scalar multiply: ``k1*G + k2*Q`` (Shamir/Straus).

    4-bit interleaved windows over a 64-step ``lax.scan``: 4 shared
    doublings per step, one *mixed* add from the precomputed fixed-base
    ``d*G`` window table (the shared doublings supply the ``16**j``
    scaling), and one Jacobian add from the per-batch 16-entry Q table.

    Kept as the A/B baseline for :func:`ecmul2_base` (the GLV ladder) and
    as an independent oracle in the parity tests — it shares no
    decomposition code with the GLV path.
    """
    batch = jnp.broadcast_shapes(k1.shape[:-1], k2.shape[:-1], qx.shape[:-1])
    qx = jnp.broadcast_to(qx, batch + (_L,))
    qy = jnp.broadcast_to(qy, batch + (_L,))
    qtx, qty, qtz = _q_window_table(batch, qx, qy)  # (16, ..., L)

    n1 = jnp.broadcast_to(
        _scalar_nibbles_msb(fields.canon(ORDER, k1)), (_NWIN,) + batch
    )
    n2 = jnp.broadcast_to(
        _scalar_nibbles_msb(fields.canon(ORDER, k2)), (_NWIN,) + batch
    )

    g_tab_x = jnp.asarray(_G_TAB_X)  # (16, L) d*G entries, constant
    g_tab_y = jnp.asarray(_G_TAB_Y)

    def body(acc, inp):
        d1, d2 = inp
        # 4 shared doublings (doubling infinity is safe: Z stays 0)
        acc = _double4(acc)
        # G term: mixed add of d1*G from the fixed window table (skip when
        # digit == 0)
        with_g = point_add_mixed(
            acc, _one_hot_select(d1, g_tab_x), _one_hot_select(d1, g_tab_y)
        )
        acc = _sel_pt(d1 == 0, acc, with_g)
        # Q term: full Jacobian add from the per-batch table (T[0] = inf is
        # handled by point_add's completeness)
        addq = JacobianPoint(
            _one_hot_select(d2, qtx), _one_hot_select(d2, qty), _one_hot_select(d2, qtz)
        )
        acc = point_add(acc, addq)
        return acc, None

    acc, _ = jax.lax.scan(body, point_infinity(batch), (n1, n2))
    return acc


@jax.jit
def ecmul2_base(
    k1: jnp.ndarray, k2: jnp.ndarray, qx: jnp.ndarray, qy: jnp.ndarray
) -> JacobianPoint:
    """GLV double-scalar multiply: ``k1*G + k2*Q`` in a 33-step ladder.

    Both scalars are lambda-split (:func:`glv_split`) into signed
    half-scalars, giving FOUR 4-bit digit streams over 129-bit magnitudes:
    ``k1*G = s11*|a|*G + s12*|b|*phi(G)`` and likewise for ``Q`` (phi(Q)
    table entries reuse the Q table with x scaled by BETA — phi commutes
    with scalar multiplication).  Signs are applied at gather time by
    negating y, so tables are built once.

    Accumulation is the TPU-shaped variant of Straus interleaving: the
    four digit streams accumulate into four INDEPENDENT lanes of one
    ``(4,) + batch`` Jacobian point (``acc_i = sum_j 16**j * T_i[d_ij]``
    — doublings distribute over the final sum), combined by two batched
    adds after the scan.  A scan step is therefore 4 batched doublings +
    ONE batched add over the stacked ``(16, 4, ...)`` tables —
    the per-step sequential chain drops from 8 point ops (4 dbl + 4
    serial adds, the r04 shape) to 5, and the traced body roughly halves,
    which is compile time on XLA:CPU (VERDICT r04 weak #3).  Table entry
    0 is the point at infinity, so zero digits need no select — the add's
    infinity selects absorb them.  Net: 132 sequential doublings instead
    of the Shamir ladder's 256 (this is the hottest loop of the framework
    — the per-message ``Verifier`` work of reference
    messages/messages.go:183-198 rides entirely on it).

    **The add in the scan is the incomplete one** (:func:`_point_add_core`:
    no ``P == Q`` fallback), 28 + 16 = 44 field products a step where the
    complete add made it 51, because its operands cannot coincide.  Each
    accumulator lane carries ONE digit stream of ONE base point ``B`` in
    {G, phi(G), Q, phi(Q)}, and the half-scalar's sign is a per-lane flag
    (``neg``) that is constant over the scan.  After ``j`` steps the lane
    holds ``+-m*B``, ``m`` the top ``j`` nibbles of a half-scalar, ``m <
    2**132``; after ``_double4`` it holds ``+-16m*B`` and the addend is
    ``+-d*B``, ``0 <= d < 16``, with the SAME sign.  secp256k1 has prime
    order ``N > 2**255`` and cofactor 1, so every ``B`` on the curve other
    than infinity has order ``N``: ``16m*B == d*B`` needs ``16m === d (mod
    N)``, and ``0 <= 16m, d < 2**136 < N`` leaves ``16m == d``, that is
    ``m == d == 0``, both operands infinity, which the infinity selects
    answer.  (``16m*B == -d*B`` is as impossible, and would give ``Z3 ==
    0`` from the formula anyway.)  The two places where operands CAN
    coincide are outside the scan and keep the complete add: the Q window
    table (``2Q = Q + Q``; :func:`_q_window_table`, ``point_add_mixed``)
    and ``recover.combine`` below (the caller chooses ``k1``, ``k2``, so
    ``a*G + b*phi(G)`` can equal ``c*Q + d*phi(Q)``).  What would break
    the invariant: an accumulator shared between streams (as
    :func:`_ecmul2_base_shamir`'s is, which keeps the complete add),
    digit streams of ``log2(N) - 4`` bits or more (today 132 of 256), or a
    curve with a cofactor (a base point of small order).

    **The batch is folded** where that fills the TPU's vector registers.
    A v5e register is one ``T(8,128)`` tile, 8 sublanes x 128 lanes, and
    XLA lays the tile over the two minor batch axes (the limb axes go
    major).  On ``(4, lanes)`` that is ``T(4,128)``: every load, multiply
    and store of the scan runs with 4 of its 8 sublanes masked off
    (``sm:$0xf`` in the LLO dump; PERF.md section 6, PR 30).  Every op of
    the scan body and of ``recover.combine`` is elementwise over the batch
    axes, so ``(4,) + batch`` is reshaped, before the scan, to
    ``(lanes // 32, 128)`` with ``lanes = prod(batch)``: the same values in
    the same row-major order, hence the same arithmetic and bit-identical
    results, on full tiles.  The rule reads the shape alone: fold when
    ``lanes % 256 == 0`` (whole 128-lane rows, and ``4 * lanes // 128`` rows
    a multiple of 8), else keep ``(4,) + batch``; of the verifier's lane
    buckets 512, 1,024 and 2,048 fold, and 8, 32 and 128 lower to the
    program they always did (``(4, 128)`` is half a register whatever its
    shape).  **The stream index is the major part of the folded axis**:
    stream ``i`` is rows ``i * lanes // 128 ...`` of it, i.e. index ``i`` of
    the ``(4, lanes // 128, 128)`` view.  The stacking of ``digits``,
    ``neg`` and the tables, and ``_pad4``'s stream slices, rely on that;
    the combine stays in the folded shape so that its two adds share the
    ladder body's one instance of :func:`_point_add_core`.

    **Where the batch folds and the backend is a TPU the 33 steps are ONE
    Pallas kernel** (:mod:`.pallas_ladder`, whose ``kind`` is the rule): the
    same steps on the same folded operands, a tile's accumulator and tables
    resident in VMEM, the accumulator it returns the scan's limb for limb.
    The scan below stays for every other shape and backend, and is what the
    kernel is checked against.

    ``k1``/``k2`` are semi-reduced scalars mod N; ``qx``/``qy`` affine
    field elements.  ``(qx, qy)`` is on the curve, or the lane's result is
    unspecified: off it the group law, and with it the invariant above,
    does not hold.
    """
    batch = jnp.broadcast_shapes(k1.shape[:-1], k2.shape[:-1], qx.shape[:-1])
    with jax.named_scope("recover.q_table"):
        qx = jnp.broadcast_to(qx, batch + (_L,))
        qy = jnp.broadcast_to(qy, batch + (_L,))
        qtx, qty, qtz = _q_window_table(batch, qx, qy)  # (16, ..., L)
        # phi(Q) table: x scaled by BETA across the table axis (one batched mul).
        qptx = fields.mul(FIELD, qtx, jnp.asarray(FIELD.const(_BETA)))

    with jax.named_scope("recover.glv_split"):
        a1, s1, a2, s2 = glv_split(fields.canon(ORDER, k1))  # G half-scalars
        b1, t1, b2, t2 = glv_split(fields.canon(ORDER, k2))  # Q half-scalars
        # Digit streams stacked on a leading term axis: (33, 4) + batch.
        digits = jnp.stack(
            [
                jnp.broadcast_to(_glv_nibbles_msb(a), (_GLV_NWIN,) + batch)
                for a in (a1, a2, b1, b2)
            ],
            axis=1,
        )

        # Stacked per-term Jacobian tables, (16, 4) + batch + (L,).  G/phi(G)
        # entries are compile-time constants with z = 1 (z = 0 at digit 0);
        # Q/phi(Q) come from the per-batch window table.
        ones = jnp.broadcast_to(jnp.asarray(FIELD.const(1)), batch + (_L,))

        def bc(tab):  # (16, L) constant -> (16,) + batch + (L,)
            return jnp.broadcast_to(
                jnp.asarray(tab)[(slice(None),) + (None,) * len(batch)],
                (16,) + batch + (_L,),
            )

        g_z = jnp.concatenate(
            [jnp.zeros_like(ones)[None], jnp.broadcast_to(ones, (15,) + batch + (_L,))]
        )
        tx = jnp.stack([bc(_G_TAB_X), bc(_GP_TAB_X), qtx, qptx], axis=1)
        ty = jnp.stack([bc(_G_TAB_Y), bc(_G_TAB_Y), qty, qty], axis=1)
        tz = jnp.stack([g_z, g_z, qtz, qtz], axis=1)
        # Per-term negation flags, (4,) + batch: negate y at gather time.
        neg = jnp.stack([s1, s2, t1, t2], axis=0)

        # The ladder's batch (docstring, "The batch is folded"): ``view``
        # keeps the stream axis apart, ``ladder`` is what the scan carries.
        # Where the two are ``(4,) + batch`` every reshape is the identity.
        lanes = math.prod(batch)
        folded = lanes % 256 == 0
        view = (4, lanes // 128, 128) if folded else (4,) + batch
        ladder = (lanes // 32, 128) if folded else view

        def fold(a, lead):  # lead + (4,) + batch [+ (L,)] -> lead + ladder [+ (L,)]
            return a.reshape(a.shape[:lead] + ladder + a.shape[lead + 1 + len(batch) :])

        def unfold(c):  # a coordinate on ``ladder`` -> on ``view``
            return c.reshape(view + (_L,))

        digits, neg = fold(digits, 1), fold(neg, 0)
        tx, ty, tz = fold(tx, 1), fold(ty, 1), fold(tz, 1)

    def body(acc, d):
        # 4 doublings of all four accumulator lanes (infinity-safe)
        acc = _double4(acc)
        y = _one_hot_select(d, ty)
        y = fields.select(neg, fields.sub(FIELD, jnp.zeros_like(y), y), y)
        addend = JacobianPoint(_one_hot_select(d, tx), y, _one_hot_select(d, tz))
        # Incomplete on purpose: acc == addend only at infinity (docstring).
        return _point_add_core(acc, addend), None

    with jax.named_scope("recover.glv_ladder"):
        if pallas_ladder.kind(lanes) == pallas_ladder.PALLAS:
            acc = JacobianPoint(
                *pallas_ladder.glv_ladder(digits, neg, tx, ty, tz, field=FIELD)
            )
        else:
            acc, _ = jax.lax.scan(body, point_infinity(ladder), digits)
    # Combine the four lanes with two COMPLETE adds (these operands can
    # coincide) at the SAME batch as the ladder body's core (``ladder``),
    # padding spent lanes with infinity.  Nested-jit point ops dedup per
    # input shape: a (2,)+batch pair-add plus a batch-shaped final add each
    # instantiate their own add core AND embedded point_double functions
    # (~13k stablehlo lines — a third of the fused 8-lane certify program),
    # while two wasted infinity lanes cost a few VPU ops.  Trace size is
    # compile time on XLA:CPU.
    def _pad4(pt: JacobianPoint, streams: slice) -> JacobianPoint:
        def pad(c, inf):  # (op order kept: the unfolded program is PR 28's HLO)
            return jnp.concatenate([c, inf]).reshape(ladder + (_L,))

        x = unfold(pt.x)[streams]
        pinf = point_infinity((4 - x.shape[0],) + view[1:])
        return JacobianPoint(
            pad(x, pinf.x),
            pad(unfold(pt.y)[streams], pinf.y),
            pad(unfold(pt.z)[streams], pinf.z),
        )

    with jax.named_scope("recover.combine"):
        half = point_add(_pad4(acc, slice(0, None, 2)), _pad4(acc, slice(1, None, 2)))
        out = point_add(_pad4(half, slice(0, 1)), _pad4(half, slice(1, 2)))
        return JacobianPoint(*(unfold(c)[0].reshape(batch + (_L,)) for c in out))


def _in_scalar_range(v: jnp.ndarray) -> jnp.ndarray:
    """``0 < v < N`` for a raw (possibly unreduced 256-bit) limb vector."""
    c = fields.exact_carry(v)
    nonzero = jnp.any(c != 0, axis=-1)
    below = ~fields.ge_const(c, ORDER.limbs)
    return nonzero & below


# N mod P as a field constant, and the canonical limbs of P - N, for the
# "second solution" branch of the x == r (mod N) check in verify.
_N_AS_FIELD = FIELD.const(N)
_P_MINUS_N = fields.to_limbs([P - N], _L)[0]


@jax.jit
def ecdsa_verify(
    qx: jnp.ndarray,
    qy: jnp.ndarray,
    z: jnp.ndarray,
    r: jnp.ndarray,
    s: jnp.ndarray,
) -> jnp.ndarray:
    """Batched ECDSA verification; returns a boolean mask.

    Inputs are limb vectors broadcast over leading batch axes: affine public
    key ``(qx, qy)``, digest-as-scalar ``z`` (already reduced mod N by the
    packing layer), and signature ``(r, s)`` as raw 256-bit values (range
    checks happen here, on device).  A key off the curve is rejected, as
    Go's ``ecdsa.Verify`` rejects it: :func:`ecmul2_base` promises nothing
    for one.
    """
    ok_range = _in_scalar_range(r) & _in_scalar_range(s)
    ok_key = on_curve(qx, qy)
    # raw 256-bit s is semi-reduced for ORDER (s < 2**256 < 2N), so the
    # tree/Fermat inverse applies directly.
    w = _inv_lanes(ORDER, s)
    u1 = fields.mul(ORDER, z, w)
    u2 = fields.mul(ORDER, r, w)
    pt = ecmul2_base(u1, u2, qx, qy)
    not_inf = ~is_infinity(pt)
    # x-coordinate equality mod N: affine x < P, r < N, and P < 2N, so the
    # only candidates are x == r and (when r + N < P) x == r + N.
    zinv = _inv_lanes(FIELD, pt.z)
    x_aff = fields.mul(FIELD, pt.x, fields.sqr(FIELD, zinv))
    r_canon = fields.canon(ORDER, r)
    eq1 = fields.eq_mod(FIELD, x_aff, r_canon)
    r_small = ~fields.ge_const(r_canon, _P_MINUS_N)
    eq2 = fields.eq_mod(
        FIELD, x_aff, fields.add(FIELD, r_canon, jnp.asarray(_N_AS_FIELD))
    )
    return ok_range & ok_key & not_inf & (eq1 | (r_small & eq2))


# (P + 1) // 4: square-root exponent for P === 3 (mod 4).
_SQRT_EXP = (P + 1) // 4


@jax.jit
def ecdsa_recover(
    z: jnp.ndarray,
    r: jnp.ndarray,
    s: jnp.ndarray,
    v: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched public-key recovery (Ethereum-style ecrecover).

    ``v`` is the recovery id (0 or 1 — the y-parity bit; ids 2/3, i.e.
    r >= P - N overflow, are rejected as Ethereum consensus does in
    practice).  Returns ``(x, y, ok)`` with canonical affine coordinates;
    lanes with ``ok == False`` have unspecified coordinates.

    This is the engine's ``IsValidValidator`` hot path: sender identity is
    *recovered* from the signature and compared against the claimed address,
    exactly one ladder per message.
    """
    with jax.named_scope("recover.range_check"):
        ok = _in_scalar_range(r) & _in_scalar_range(s)
        ok = ok & ((v == 0) | (v == 1))

    with jax.named_scope("recover.lift_x"):
        f = FIELD
        x = fields.canon(ORDER, r)  # r < N < P: also a canonical field element
        # y = sqrt(x^3 + 7); P === 3 (mod 4) so sqrt = pow((P+1)/4).  The
        # square root (mod P) and r^-1 (mod N) are data-independent, so they
        # ride ONE merged scan — two sequential ~64-window chains would double
        # the pre-ladder latency (fields.pow_fixed2).
        y2 = fields.add(f, fields.mul(f, fields.sqr(f, x), x), jnp.asarray(f.const(7)))
        # Where the ladder is the Pallas kernel the two chains are one too
        # (``lift_x_pow``), limb for limb the scan's: one rule, ``kind``.
        if pallas_ladder.kind(math.prod(x.shape[:-1])) == pallas_ladder.PALLAS:
            y, rinv = pallas_ladder.fixed_powers(
                "lift_x_pow", (f, y2, _SQRT_EXP), (ORDER, x, N - 2)
            )
        else:
            y, rinv = fields.pow_fixed2(f, y2, _SQRT_EXP, ORDER, x, N - 2)
        # r was a valid x-coord: (x, y_sel) is on the curve exactly where this
        # holds, which is what ecmul2_base's incomplete ladder add asks for.
        ok = ok & fields.eq_mod(f, fields.sqr(f, y), y2)
        y_canon = fields.canon(f, y)
        parity = (y_canon[..., 0] & 1).astype(jnp.int32)
        y_neg = fields.canon(f, fields.sub(f, jnp.zeros_like(y_canon), y_canon))
        y_sel = fields.select(parity == v.astype(jnp.int32), y_canon, y_neg)

        # Q = r^-1 * (s*R - z*G)  ==  (-z * r^-1)*G + (s * r^-1)*R
        u1 = fields.mul(
            ORDER, fields.sub(ORDER, jnp.zeros_like(z), z), rinv
        )
        u2 = fields.mul(ORDER, s, rinv)
    q = ecmul2_base(u1, u2, x, y_sel)
    with jax.named_scope("recover.to_affine"):
        ok = ok & ~is_infinity(q)
        qx, qy = to_affine(q)
    return qx, qy, ok
