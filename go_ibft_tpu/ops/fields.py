"""Limbed modular big-integer arithmetic on TPU (JAX/XLA).

TPUs have no native wide-integer types, so 256-bit field elements are
represented as vectors of radix-2**13 limbs held in ``int32`` lanes
(SURVEY.md §7 hard part (a)).  Two invariants hold, one at an op's edge
and one inside it.

**At an op's edge** (what ``add`` / ``sub`` / ``mul`` / ``sqr`` / ``muli``
take and return) values are **unsigned and "semi-reduced"**: limbs lie in
``[0, 2**13]`` (the upper bound is *inclusive* — a lazy carry may leave a
limb at exactly 2**13) and the represented value lies in ``[0, 2*p)``.
Subtraction never produces negative limbs: ``a - b`` is computed as
``a + F - b`` where ``F`` is a precomputed *fat* representation of ``K*p``
whose every limb is >= 2**13, so the subtrahend's limbs must be <= 2**13:
the edge invariant.  Exact canonicalization to ``[0, p)`` (sequential
carry scans) happens only at the edges — final comparisons and host I/O —
never inside hot loops.

**Inside an op** limbs are unsigned and under a bound that is TRACKED, as
a plain Python integer at trace time, beside the value bound that sizes
the fold schedule; nothing in between is normalised further than its
consumer needs.  A carry pass takes limbs <= b to limbs
<= 2**13 - 1 + (b >> 13) (:func:`_pass_bound`) and leaves the value as it
was; :func:`_passes` counts the passes from one bound to another, and every
product site asserts ``a_max * (the other operand's limbs that meet in a
column, summed) + what is added < 2**31`` through :func:`_column_bound`
(for two edge operands that is ``rows * 2**13 * 2**13``), so a modulus or
a caller that breaks the arithmetic fails to TRACE and never computes
wrongly.  For the two secp256k1 moduli that reads:

* a limb product is <= 2**26 and a column of the 20 x 20 product sums 20 of
  them: <= 20 * 2**26 < 2**31.  One pass leaves limbs <= 172,031, two
  <= 8,211;
* a column of a fold round's ``hi * c_fold`` is at most ``hi``'s limb bound
  times the SUM of ``c_fold``'s limbs (8,465 for P's 3 rows, 34,145 for N's
  11), so ``hi`` and ``lo`` may carry limbs up to (2**31 - 1) // (sum + 1)
  (253,659 / 62,891: ONE pass after the product mod P, two mod N); the
  round's product is added to ``lo`` uncarried, and the sum is carried only
  as far as the NEXT round's ceiling (one pass: 185,975 / 42,416);
* the final fold reads ``hi`` by shifts and ``lo`` by a mask, exact for
  limbs of any size; what it asks of them is that ``lo + hi * c_fb`` stay
  under 2p with ``lo``'s limbs at their bound (a ceiling of 4,201,982: one
  pass after the last round, NONE for the sums of ``add`` / ``sub`` /
  ``muli``, whose limbs are <= 2**14 / 3 * 2**13 / 2**17), and two passes
  after it restore ``[0, 2**13]``.

A ``mul`` is 5 passes mod P and 6 mod N (19 until PR 46, at fixed
4 / 2 / 3 counts a site); ``add`` / ``sub`` / ``muli`` are 2 (5 / 6 / 6).

Reduction uses generalized pseudo-Mersenne folding: ``2**(13*L) === c_fold``
and ``2**bits === c_fb (mod p)``, with the fold schedule derived statically
from value bounds at :class:`Modulus` construction time.  This requires the
modulus to sit close under a power of two (``128 * c_fb < p``) — true for
both secp256k1 moduli; BLS12-381 uses the Montgomery path instead (see
``bls12_381.py``).

Everything here is shape-static, branch-free, and batched by broadcasting
over leading axes, so a whole round's worth of signatures reduces in one
``jit`` — replacing the reference's per-message sequential verifies
(go-ibft messages/messages.go:183-198, core/backend.go:37-56).
"""

from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LIMB_BITS = 13
LIMB_MASK = (1 << LIMB_BITS) - 1

__all__ = [
    "LIMB_BITS",
    "LIMB_MASK",
    "Modulus",
    "to_limbs",
    "from_limbs",
    "add",
    "sub",
    "mul",
    "sqr",
    "muli",
    "pow_fixed",
    "pow_fixed2",
    "select16",
    "inv",
    "batch_inv",
    "canon",
    "is_zero",
    "eq_mod",
    "select",
    "exact_carry",
    "ge_const",
]


def to_limbs(values: Sequence[int], nlimbs: int) -> np.ndarray:
    """Host-side: pack python ints into an ``(N, nlimbs)`` int32 limb array."""
    out = np.zeros((len(values), nlimbs), dtype=np.int32)
    for row, v in enumerate(values):
        if v < 0:
            raise ValueError("limb representation is unsigned")
        for i in range(nlimbs):
            out[row, i] = v & LIMB_MASK
            v >>= LIMB_BITS
        if v:
            raise ValueError(f"value does not fit in {nlimbs} limbs")
    return out


def from_limbs(arr) -> List[int]:
    """Host-side: unpack an ``(..., nlimbs)`` limb array into python ints."""
    a = np.asarray(arr, dtype=np.int64)
    flat = a.reshape(-1, a.shape[-1])
    out = []
    for row in flat:
        v = 0
        for i in range(a.shape[-1] - 1, -1, -1):
            v = (v << LIMB_BITS) + int(row[i])
        out.append(v)
    return out


def _single_limbs(value: int, nlimbs: int) -> np.ndarray:
    return to_limbs([value], nlimbs)[0]


def _fat_rep(value: int, nlimbs: int) -> np.ndarray:
    """Limb rep of ``value`` with every limb in [2**13, 3 * 2**13).

    Adding it makes limb-wise subtraction borrow-free.  Exists whenever
    ``value`` >= sum(2**13 * 2**(13 i)) and fits the per-limb cap.
    """
    lo_floor = LIMB_MASK + 1
    cap = 3 * lo_floor - 1
    rem = value - sum(lo_floor << (LIMB_BITS * i) for i in range(nlimbs))
    if rem < 0:
        raise ValueError("value too small for fat representation")
    out = np.zeros(nlimbs, dtype=np.int32)
    for i in range(nlimbs - 1, -1, -1):
        unit = 1 << (LIMB_BITS * i)
        extra = min(rem // unit, cap - lo_floor)
        out[i] = lo_floor + extra
        rem -= extra * unit
    if rem:
        raise ValueError("fat representation infeasible")
    return out


class Modulus:
    """Static per-modulus data: limbs, fold constants, fat K*p rep.

    Construction precomputes everything the traced ops need as numpy
    constants, so a ``Modulus`` instance can be closed over inside ``jit``.
    """

    def __init__(self, p: int):
        if p <= 0:
            raise ValueError("modulus must be positive")
        self.p = p
        self.bits = p.bit_length()
        self.nlimbs = -(-self.bits // LIMB_BITS)
        L = self.nlimbs
        self.limbs = _single_limbs(p, L)
        # Fold constants: 2**(13 L) and 2**bits mod p.
        self.c_fold = (1 << (LIMB_BITS * L)) % p
        self.c_fb = (1 << self.bits) % p  # == 2**bits - p
        if self.c_fb == 0 or 128 * self.c_fb >= p:
            raise ValueError(
                "modulus too far below a power of two for folding; "
                "use the Montgomery path"
            )
        self.c_fold_limbs = _single_limbs(self.c_fold, -(-self.c_fold.bit_length() // LIMB_BITS))
        self.c_fb_limbs = _single_limbs(self.c_fb, -(-max(self.c_fb.bit_length(), 1) // LIMB_BITS))
        self.fb_limb, self.fb_shift = divmod(self.bits, LIMB_BITS)
        # Fat K*p for borrow-free subtraction of any semi-reduced (< 2p) value.
        k = 3
        while k * p < sum((LIMB_MASK + 1) << (LIMB_BITS * i) for i in range(L)):
            k += 1
        self.fat_kp = _fat_rep(k * p, L)
        self.fat_k = k
        self.sub_bound = 2 * p + k * p  # value bound of a + K p - b

    def const(self, value: int) -> np.ndarray:
        """Limbs of ``value mod p`` as a broadcastable ``(nlimbs,)`` array."""
        return _single_limbs(value % self.p, self.nlimbs)


_INT32 = 1 << 31
_EDGE = LIMB_MASK + 1  # the limb bound at an op's edge, inclusive


def _pass_bound(limb: int) -> int:
    """Bound of the limbs one carry pass leaves of unsigned limbs <= ``limb``."""
    return LIMB_MASK + (limb >> LIMB_BITS)


def _passes(b_in: int, b_out: int) -> Tuple[int, int]:
    """The fewest carry passes that take unsigned limbs <= ``b_in`` to limbs
    <= ``b_out``, and the bound they reach.  2**13 is the passes' fixed
    point: no count brings a limb under it."""
    if b_out < _EDGE:
        raise ValueError(
            f"no carry pass brings limbs under 2**{LIMB_BITS}: asked for <= {b_out} "
            "(the other operand of a product site is too heavy for this radix)"
        )
    if b_in >= _INT32:
        raise ValueError(f"limb bound {b_in} does not fit int32")
    n = 0
    while b_in > b_out:
        b_in, n = _pass_bound(b_in), n + 1
    return n, b_in


def _column_bound(site: str, a_max: int, b_sum: int, plus: int = 0) -> int:
    """Bound of a product column: limbs <= ``a_max`` against limbs of the
    other operand that sum to at most ``b_sum`` over the rows that meet in
    one column, and ``plus`` added to it.  Raises at trace time where an
    int32 column could overflow."""
    cols = a_max * b_sum + plus
    if cols >= _INT32:
        raise ValueError(
            f"{site}: limbs <= {a_max} x {b_sum} a column + {plus} = {cols} "
            "overflows an int32 column (>= 2**31)"
        )
    return cols


def _limb_ceiling(c_sum: int) -> int:
    """Largest limb bound b with ``b * c_sum + b < 2**31``: what a fold
    round's ``lo + hi * c_fold`` admits of the limbs it is given, ``c_sum``
    the sum of ``c_fold``'s limbs (every column's products together)."""
    return (_INT32 - 1) // (c_sum + 1)


def _limb_value(limb: int, n: int) -> int:
    """Largest value of ``n`` unsigned limbs each <= ``limb``."""
    return limb * (((1 << (LIMB_BITS * n)) - 1) // LIMB_MASK)


def _carry(z: jnp.ndarray, passes: int) -> jnp.ndarray:
    """Lazy parallel carry: each pass moves carries one limb up.

    A pass takes unsigned limbs <= b to limbs <= 2**13 - 1 + (b >> 13) and
    leaves the value unchanged; the callers in this module derive ``passes``
    from the bound they hold and the bound their consumer needs
    (:func:`_passes`).  From any bound < 2**31 three passes end in
    ``[0, 2**13]``.  The caller must size ``z`` so the top limb never
    produces a carry (value < 2**(13 * len)).
    """
    if not passes:
        return z
    zero = jnp.zeros(z.shape[:-1] + (1,), dtype=z.dtype)
    for _ in range(passes):
        c = z >> LIMB_BITS
        z = (z & LIMB_MASK) + jnp.concatenate([zero, c[..., :-1]], axis=-1)
    return z


def _conv(a: jnp.ndarray, b: jnp.ndarray, out_len: int) -> jnp.ndarray:
    """Schoolbook product columns (no carries): ``out[k] = sum_i a_i*b_(k-i)``.

    ONE outer product, merged to a flat buffer, and the sum of ``la``
    windows of it — with the limb axes LEADING and the batch axes
    trailing from the product to the column sum.  Row ``i`` of the outer
    product is ``a_i * b`` with ``b`` zero-padded to the product's
    ``w = la + lb - 1`` columns.  In the flat ``la*w`` buffer the element
    ``f[k + i*(w-1)]`` is row ``i``, column ``k - i``: the window that
    starts at ``i*(w-1)`` is row ``i`` shifted right by ``i`` (the shear),
    its first ``i`` elements the zero tail of row ``i - 1``.  So the
    windows add up to the convolution, and XLA fuses the ``la`` slices
    into the adds: the product is written once and read once.  The
    shorter operand gives the rows, whatever the order of the arguments.

    ~2*la + 10 HLO ops; ``la`` shifted pad-adds of products (no outer
    product at all) are ~5*la, and with ~50 muls in every ladder-scan
    body trace size is XLA:CPU compile time.

    Why limbs lead: the TPU keeps the batch lanes in the minor (tiled)
    dimensions whatever the logical order, so merging the two MAJOR axes
    is a bitcast.  The form of rounds 5-24 kept the batch leading, merged
    and re-split the TRAILING axes, and XLA transposed every padded
    product for it: on the v5e the copies of ``s32[4,lanes,1200]`` /
    ``[.,945]`` alone were a third of the recover program and data
    movement two thirds of it.  PERF.md section 6 (PR 25) has the chip
    numbers of the forms tried, and why the windows are not ONE dilated
    ``reduce_window`` (this libtpu computes it wrongly).

    ``b`` may be a constant ``(lb,)`` with no batch axes and ``a`` may
    carry any leading axes; limbs are on the last axis going in and
    coming out.  Exact for ANY ``out_len``: columns at or beyond it are
    never summed, columns beyond ``la + lb - 2`` are zero (int32 column
    sums <= min(la, lb) * 2**26 < 2**31, in any order).
    """
    if a.shape[-1] > b.shape[-1]:
        a, b = b, a
    la, lb = a.shape[-1], b.shape[-1]
    batch = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    ones = (1,) * len(batch)
    still = [(0, 0)] * len(batch)

    def limbs_first(x):  # (..., l) -> (l,) + one axis per batch axis
        x = jnp.moveaxis(x, -1, 0)
        return x.reshape(x.shape[:1] + ones[: len(batch) + 1 - x.ndim] + x.shape[1:])

    w = la + lb - 1
    n = min(out_len, w)
    rows = limbs_first(a)[:, None] * jnp.pad(limbs_first(b), [(0, la - 1)] + still)[None]
    flat = rows.reshape((la * w,) + batch)
    cols = flat[:n]
    for i in range(1, la):
        cols = cols + flat[i * (w - 1) : i * (w - 1) + n]
    return _pad_to(jnp.moveaxis(cols, 0, -1), out_len)


def _pad_to(z: jnp.ndarray, n: int) -> jnp.ndarray:
    if z.shape[-1] >= n:
        return z
    pad = [(0, 0)] * (z.ndim - 1) + [(0, n - z.shape[-1])]
    return jnp.pad(z, pad)


class _FoldRound(NamedTuple):
    """One round of ``lo + hi * c_fold``."""

    passes: int  # carry passes before the split, to the product's ceiling
    hi_len: int  # limbs of ``hi`` that can be non-zero
    out_len: int  # limbs of the round's sum


class _FoldSchedule(NamedTuple):
    """What :func:`_fold_semi` does to a limb vector, as plain integers: the
    one derivation that every executor of the reduction follows (the XLA
    form below, limbs on the last axis; ``pallas_ladder``'s, limbs leading)."""

    rounds: Tuple[_FoldRound, ...]
    passes: int  # carry passes before the final fold
    width: int  # limbs the final fold reads
    hi_max: int  # bound of the final fold's ``hi``
    last_passes: int  # carry passes after it, to the edge


def _fold_schedule(m: Modulus, width: int, bound: int, limb: int) -> _FoldSchedule:
    """The static schedule that reduces ``width`` unsigned limbs <= ``limb``
    of value < ``bound`` to the edge invariant.  The fold rounds come from
    the value bound; the carry passes from the limb bound: before each
    product the limbs are carried to the ceiling that product's int32
    columns admit and no further, ``hi * c_fold`` is added to ``lo`` as the
    columns it is, and only the passes after the final fold go all the way
    to 2**13.  Raises where an int32 column could overflow."""
    L = m.nlimbs
    lw = LIMB_BITS * L
    c_len, c_sum = len(m.c_fold_limbs), int(m.c_fold_limbs.sum())

    def carry_to(ceiling: int, bound: int, width: int) -> int:
        nonlocal limb
        passes, limb = _passes(limb, ceiling)
        if passes and bound > 1 << (LIMB_BITS * width):
            raise ValueError(
                f"a value < 2**{bound.bit_length()} can carry out of {width} limbs"
            )
        return passes

    rounds = []
    while bound >= (1 << (lw + 6)):
        hi_bound = (bound >> lw) + 1
        # Truncate provably-zero top limbs of hi (unsigned => value-bounded).
        hi_len = min(width - L, -(-hi_bound.bit_length() // LIMB_BITS) + 1)
        passes = carry_to(_limb_ceiling(c_sum), bound, width)
        width = max(L, hi_len + c_len) + 1
        rounds.append(_FoldRound(passes, hi_len, width))
        # lo alone may exceed 2**lw by the carries its limbs still hold.
        bound = min(bound, _limb_value(limb, L) + 1) + hi_bound * m.c_fold
        limb = _column_bound("fold", limb, c_sum, plus=limb)
    # Final fold at bit position m.bits: v = lo + hi * 2**bits === lo + hi*c_fb,
    # exact for limbs of any size (hi by shifts, lo by a mask).  hi <= v >> bits.
    fbl, fbs = m.fb_limb, m.fb_shift
    hi_max = (bound - 1) >> m.bits
    cf_max = int(m.c_fb_limbs.max())
    # What 2p leaves for lo's fbl whole limbs, beside hi * c_fb and the fbs
    # bits lo keeps of limb fbl.
    room = 2 * m.p - 1 - hi_max * m.c_fb - (((1 << fbs) - 1) << (LIMB_BITS * fbl))
    ceiling = min(_INT32 - 1 - hi_max * cf_max, room // _limb_value(1, fbl))
    passes = carry_to(ceiling, bound, width)
    # Limbs whose weight is over the value are zero: not read.
    live = ((bound - 1).bit_length() - 1) // LIMB_BITS + 1
    width = min(max(width, fbl + 1), max(fbl + 1, live))
    limb = _column_bound("final fold", hi_max, cf_max, plus=limb)
    return _FoldSchedule(
        tuple(rounds), passes, width, hi_max, carry_to(_EDGE, 2 * m.p, L)
    )


def _fold_semi(m: Modulus, z: jnp.ndarray, bound: int, limb: int) -> jnp.ndarray:
    """Reduce an unsigned limb vector of value < ``bound`` and limbs <=
    ``limb`` (NOT carried: product columns, or a sum of edge operands) to
    the edge invariant: ``nlimbs`` limbs in ``[0, 2**13]``, value < 2p, by
    :func:`_fold_schedule`."""
    L = m.nlimbs
    plan = _fold_schedule(m, z.shape[-1], bound, limb)
    c_fold = jnp.asarray(m.c_fold_limbs)
    for rnd in plan.rounds:
        z = _carry(z, rnd.passes)
        lo, hi = z[..., :L], z[..., L : L + rnd.hi_len]
        z = _pad_to(lo, rnd.out_len) + _conv(hi, c_fold, rnd.out_len)
    fbl, fbs = m.fb_limb, m.fb_shift
    z = _pad_to(_carry(z, plan.passes), fbl + 1)[..., : plan.width]
    hi = z[..., fbl] >> fbs
    for j in range(fbl + 1, z.shape[-1]):
        hi = hi + (z[..., j] << (LIMB_BITS * (j - fbl) - fbs))
    keep_bits = jnp.asarray(
        [(1 << fbs) - 1 if i == fbl else _INT32 - 1 for i in range(L)],
        dtype=jnp.int32,
    )
    lo = z[..., :L] & keep_bits  # fbl + 1 >= L limbs are there
    prod = hi[..., None] * jnp.asarray(m.c_fb_limbs)
    return _carry(lo + _pad_to(prod, L), plan.last_passes)


def add(m: Modulus, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(a + b) mod-class, semi-reduced output."""
    return _fold_semi(m, a + b, 4 * m.p, 2 * _EDGE)


def sub(m: Modulus, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(a - b) mod-class via the borrow-free fat K*p trick."""
    z = a + jnp.asarray(m.fat_kp) - b
    return _fold_semi(m, z, m.sub_bound, _EDGE + int(m.fat_kp.max()))


def mul(m: Modulus, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(a * b) mod-class: schoolbook conv + static fold schedule."""
    bound = (2 * m.p) ** 2
    out_len = -(-bound.bit_length() // LIMB_BITS) + 1
    cols = _column_bound("mul", _EDGE, min(a.shape[-1], b.shape[-1]) * _EDGE)
    return _fold_semi(m, _conv(a, b, out_len), bound, cols)


def sqr(m: Modulus, a: jnp.ndarray) -> jnp.ndarray:
    return mul(m, a, a)


def muli(m: Modulus, a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small constant 1 <= k <= 16."""
    if not 1 <= k <= 16:
        raise ValueError("k out of range")
    return _fold_semi(m, a * k, 2 * m.p * k, k * _EDGE)


def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Branchless limb-vector select; ``cond`` broadcasts over the limb axis."""
    return jnp.where(cond[..., None], a, b)


def select16(sel: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Branchless 16-way gather: ``table`` is ``(16, ..., L)`` (leading
    table axis), ``sel`` integer in [0, 16); returns ``(..., L)``.

    A 4-level tree of pure ``where`` ops (15 selects), NOT a one-hot
    ``einsum``: an int32 ``dot_general`` inside a scan body lowers poorly
    on TPU (no MXU int path — each becomes a serialized VPU contraction
    with layout shuffles; measured r03, scripts/ab_ladder_select.py)."""
    b0 = (sel & 1).astype(bool)[..., None]
    b1 = (sel & 2).astype(bool)[..., None]
    b2 = (sel & 4).astype(bool)[..., None]
    b3 = (sel & 8).astype(bool)[..., None]
    t = [jnp.where(b0, table[i + 1], table[i]) for i in range(0, 16, 2)]
    t = [jnp.where(b1, t[i + 1], t[i]) for i in range(0, 8, 2)]
    t = [jnp.where(b2, t[i + 1], t[i]) for i in range(0, 4, 2)]
    return jnp.where(b3, t[1], t[0])


def pow_fixed(m: Modulus, a: jnp.ndarray, exponent: int) -> jnp.ndarray:
    """a**exponent with a fixed public exponent: 4-bit windowed scan.

    Left-to-right 2**4-ary exponentiation — per window 4 squarings + ONE
    table multiply (14 table-build muls up front), ~5.1 sequential muls
    per 4 bits vs the bit-serial square-and-multiply's 8 (that variant
    computes the conditional multiply unconditionally under a ``select``
    every step).  The scan body closes over the batch power table; window
    digits are static scan inputs, gathered via :func:`select16`.  This is
    the latency shape of the three per-recover Fermat scans the VERDICT r03
    flagged (s⁻¹, √, affine inverse).
    """
    if exponent < 0:
        raise ValueError("exponent must be non-negative")
    if exponent == 0:
        return jnp.broadcast_to(jnp.asarray(m.const(1)), a.shape)
    nwin = -(-exponent.bit_length() // 4)
    digits = _pow_digits(exponent, nwin)  # MSB-first
    table = _pow_table(m, a)  # (16, ..., L); scan-built (trace-compact)

    def body(acc, digit):
        for _ in range(4):
            acc = mul(m, acc, acc)
        acc = mul(m, acc, select16(digit, table))
        return acc, None

    acc, _ = jax.lax.scan(body, select16(jnp.asarray(digits[0]), table), jnp.asarray(digits[1:]))
    return acc


def inv(m: Modulus, a: jnp.ndarray) -> jnp.ndarray:
    """Modular inverse by Fermat (modulus must be prime); inv(0) == 0."""
    return pow_fixed(m, a, m.p - 2)


def _pow_digits(exponent: int, nwin: int) -> np.ndarray:
    return np.asarray(
        [(exponent >> (4 * j)) & 0xF for j in range(nwin - 1, -1, -1)],
        dtype=np.int32,
    )


def _pow_table(m: Modulus, a: jnp.ndarray) -> jnp.ndarray:
    """Window table a^0..a^15, built with a 14-step scan (trace-compact)."""
    one = jnp.broadcast_to(jnp.asarray(m.const(1)), a.shape)

    def tab_body(prev, _):
        nxt = mul(m, prev, a)
        return nxt, nxt

    _, tail = jax.lax.scan(tab_body, a, None, length=14)  # a^2 .. a^15
    return jnp.concatenate([one[None], a[None], tail])


def pow_fixed2(
    m1: Modulus,
    a1: jnp.ndarray,
    e1: int,
    m2: Modulus,
    a2: jnp.ndarray,
    e2: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """TWO independent fixed-exponent powers in ONE windowed scan.

    The recover hot path needs a square root mod P and an inverse mod N —
    two data-independent ~64-window chains.  Two separate ``lax.scan``s
    execute strictly one after the other (XLA runs While loops
    sequentially), doubling the latency; one scan whose body advances both
    chains lets the VPU interleave them, so the pair costs barely more
    wall-clock than one (VERDICT r04 ask #2: the per-recover scan stack is
    the measured floor after the ladder).  Exponents are padded to a
    common window count with leading zero digits (digit 0 multiplies by
    table[0] == 1 — a wasted-but-harmless mul keeps the body branch-free).
    """
    if e1 <= 0 or e2 <= 0:
        raise ValueError("pow_fixed2 requires positive exponents")
    nwin = max(-(-e1.bit_length() // 4), -(-e2.bit_length() // 4))
    d1 = _pow_digits(e1, nwin)
    d2 = _pow_digits(e2, nwin)
    t1 = _pow_table(m1, a1)
    t2 = _pow_table(m2, a2)

    def body(carry, digits):
        acc1, acc2 = carry
        g1, g2 = digits
        for _ in range(4):
            acc1 = mul(m1, acc1, acc1)
            acc2 = mul(m2, acc2, acc2)
        acc1 = mul(m1, acc1, select16(g1, t1))
        acc2 = mul(m2, acc2, select16(g2, t2))
        return (acc1, acc2), None

    init = (select16(jnp.asarray(d1[0]), t1), select16(jnp.asarray(d2[0]), t2))
    (acc1, acc2), _ = jax.lax.scan(
        body, init, (jnp.asarray(d1[1:]), jnp.asarray(d2[1:]))
    )
    return acc1, acc2


def batch_inv(m: Modulus, a: jnp.ndarray) -> jnp.ndarray:
    """Montgomery product-tree inverse over the LEADING axis.

    One Fermat scan total — on the 1-lane root product — plus one batched
    mul per tree level in each direction (~2*log2(B)), instead of B
    parallel 329-mul scans: the VERDICT r04 ask #2 amortization.  Lanes
    that are 0 (mod p) are masked to 1 through the tree and forced back to
    0 on output, preserving the ``inv(0) == 0`` contract (infinity maps to
    (0, 0) in ``to_affine``).  Inputs semi-reduced; outputs semi-reduced.

    Both tree sweeps run as ``lax.scan``s over fixed ``(B/2, L)``-padded
    levels (valid entries keep a prefix-contiguous layout; pad lanes hold
    exact ones, which multiply through harmlessly): unrolled, the 2*log2(B)
    shrinking-shape muls each inline ~270 stablehlo lines — 5.2k lines at
    the 128-lane bucket — and trace size is compile time on XLA:CPU.
    """
    n = a.shape[0]
    if n == 1:
        return inv(m, a)
    zero = is_zero_fast(m, a)
    base = select(zero, jnp.broadcast_to(jnp.asarray(m.const(1)), a.shape), a)
    if n & (n - 1):  # pad to a power of two with exact ones
        pad = (1 << n.bit_length()) - n
        ones = jnp.broadcast_to(
            jnp.asarray(m.const(1)), (pad,) + base.shape[1:]
        )
        base = jnp.concatenate([base, ones])
    np2 = base.shape[0]
    if np2 == 2:
        root_inv = pow_fixed(m, mul(m, base[0:1], base[1:2]), m.p - 2)
        sib = jnp.stack([base[1::2], base[0::2]], axis=1).reshape(base.shape)
        invs = mul(m, jnp.repeat(root_inv, 2, axis=0), sib)
        return select(zero, jnp.zeros_like(a), invs[: a.shape[0]])

    half = np2 // 2
    ones_h = jnp.broadcast_to(jnp.asarray(m.const(1)), (half,) + base.shape[1:])

    def up_body(state, _):
        nxt = mul(m, state[0::2], state[1::2])  # valid prefix halves
        nxt = jnp.concatenate([nxt, ones_h[: half - nxt.shape[0]]])
        return nxt, nxt

    lvl1 = mul(m, base[0::2], base[1::2])  # (half, L), fully valid
    _, ups = jax.lax.scan(up_body, lvl1, None, length=np2.bit_length() - 2)
    root = ups[-1][0:1]  # (1, L) product of every lane
    root_inv = pow_fixed(m, root, m.p - 2)  # the ONE Fermat scan

    def down_body(invs, lvl):
        expanded = jnp.repeat(invs, 2, axis=0)[:half]
        sib = jnp.stack([lvl[1::2], lvl[0::2]], axis=1).reshape(lvl.shape)
        return mul(m, expanded, sib), None

    # Walk the stored levels back down: ups[:-1] reversed, then lvl1.
    down_levels = jnp.concatenate([ups[:-1][::-1], lvl1[None]])
    invs0 = jnp.concatenate([root_inv, ones_h[: half - 1]])
    invs, _ = jax.lax.scan(down_body, invs0, down_levels)
    # Final level: the padded inputs themselves, at full width.
    expanded = jnp.repeat(invs, 2, axis=0)[:np2]
    sib = jnp.stack([base[1::2], base[0::2]], axis=1).reshape(base.shape)
    out = mul(m, expanded, sib)
    return select(zero, jnp.zeros_like(a), out[: a.shape[0]])


def _exact_carry(z: jnp.ndarray) -> jnp.ndarray:
    """Sequential exact carry propagation (lax.scan over the limb axis)."""

    def step(carry, x):
        t = x + carry
        return t >> LIMB_BITS, t & LIMB_MASK

    xs = jnp.moveaxis(z, -1, 0)
    _, ys = jax.lax.scan(step, jnp.zeros(z.shape[:-1], dtype=z.dtype), xs)
    return jnp.moveaxis(ys, 0, -1)


def _ge_const(a: jnp.ndarray, ref: np.ndarray) -> jnp.ndarray:
    """a >= ref, both canonical; unrolled lexicographic compare from the top."""
    res = jnp.zeros(a.shape[:-1], dtype=jnp.int32)
    for i in range(a.shape[-1] - 1, -1, -1):
        d = jnp.sign(a[..., i] - int(ref[i]))
        res = jnp.where(res != 0, res, d)
    return res >= 0


def canon(m: Modulus, a: jnp.ndarray) -> jnp.ndarray:
    """Exact canonical form in [0, p) with limbs < 2**13.

    Input must be semi-reduced (< 2p).  Only used at the edges (final
    equality checks, host I/O): it contains sequential limb scans, which
    would serialize the hot loop.
    """
    z = _exact_carry(a)
    ge = _ge_const(z, m.limbs)
    # _sub_exact is only meaningful where z >= p (no final borrow); the
    # other lanes keep z.
    return select(ge, _sub_exact(z, m.limbs), z)


def _sub_exact(a: jnp.ndarray, ref: np.ndarray) -> jnp.ndarray:
    """a - ref for canonical a >= ref: sequential borrow scan."""

    def step(borrow, x):
        t = x + borrow
        b = t >> LIMB_BITS
        return b, t - (b << LIMB_BITS)

    xs = jnp.moveaxis(a - jnp.asarray(ref, dtype=jnp.int32), -1, 0)
    _, ys = jax.lax.scan(step, jnp.zeros(a.shape[:-1], dtype=a.dtype), xs)
    return jnp.moveaxis(ys, 0, -1)


# Public aliases: raw (non-modular) exact carry + lexicographic compare, used
# by curve code for range checks like ``r < n`` on unreduced 256-bit inputs.
exact_carry = _exact_carry
ge_const = _ge_const


def _ks_carry(a: jnp.ndarray) -> jnp.ndarray:
    """Exact carry for limbs in ``[0, 2**13]`` via Kogge-Stone prefix OR.

    The lazy-carry passes leave limbs with at most a single overflow bit, so
    carry propagation is binary and resolves in log2(nlimbs) *vector* steps —
    unlike :func:`_exact_carry`'s sequential ``lax.scan``, this keeps the
    256-step EC ladder free of inner serial chains (the single biggest
    runtime cost of the complete-addition exception tests).
    """
    g0 = a >> LIMB_BITS  # generate in {0, 1}
    base = a & LIMB_MASK
    zero = jnp.zeros(a.shape[:-1] + (1,), dtype=a.dtype)
    s = base + jnp.concatenate([zero, g0[..., :-1]], axis=-1)  # in [0, 2**13]
    gen = s >> LIMB_BITS
    prop = (s == LIMB_MASK).astype(a.dtype)
    # inclusive prefix: carry_out[i] = gen[i] | (prop[i] & carry_out[i-1])
    nl = a.shape[-1]
    pad_axes = [(0, 0)] * (a.ndim - 1)
    d = 1
    while d < nl:
        gen = gen | (prop & jnp.pad(gen[..., :-d], pad_axes + [(d, 0)]))
        prop = prop & jnp.pad(prop[..., :-d], pad_axes + [(d, 0)])
        d *= 2
    carry_in = jnp.concatenate([zero, gen[..., :-1]], axis=-1)
    return (s + carry_in) & LIMB_MASK


def canon_value(m: Modulus, a: jnp.ndarray) -> jnp.ndarray:
    """Unique canonical limbs of the *value* of a semi-reduced input.

    Input limbs must lie in ``[0, 2**13]`` (true for every op output here);
    the value stays in ``[0, 2p)`` — NOT reduced mod p.  Branch-free,
    scan-free (see :func:`_ks_carry`)."""
    return _ks_carry(a)


def is_zero_fast(m: Modulus, a: jnp.ndarray) -> jnp.ndarray:
    """``a === 0 (mod p)`` for semi-reduced ``a`` (< 2p): value 0 or p.

    Scan-free: canonical limbs are unique, so two vector compares decide."""
    c = _ks_carry(a)
    p_limbs = jnp.asarray(m.limbs)
    return jnp.all(c == 0, axis=-1) | jnp.all(c == p_limbs, axis=-1)


def is_zero(m: Modulus, a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canon(m, a) == 0, axis=-1)


def eq_mod(m: Modulus, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return is_zero(m, sub(m, a, b))
