"""The recover program's Pallas TPU kernels: the GLV ladder's 33 steps as
ONE kernel, and each fixed-exponent Fermat chain as one.

``secp256k1.ecmul2_base`` runs its ladder as a ``lax.scan`` whose body XLA
cuts into some hundred fusions a step, every field product written to memory
once and read back once: on the v5e that scan was four fifths of the recover
program (``PERF.md`` section 5).  Here the same 33 steps (4 doublings, the
16-way table select with the sign applied to ``y``, one incomplete add: 44
field products) run inside one ``pallas_call``: the grid walks the folded
ladder batch in ``(8, 128)`` row tiles, and a tile's accumulator, digits and
``(16, ...)`` tables stay in VMEM for all 33 steps.  Same mathematics, same
int32 limbs, same carry schedule (:func:`fields._fold_schedule`, derived from
limb bounds: nothing here restates its numbers), so the accumulator that
leaves the kernel equals the scan's limb for limb.

**The powers** (:func:`fixed_powers`).  With the ladder in a kernel the two
``while`` loops left were most of a dispatch: ``recover.lift_x``'s square
root mod P and ``r**-1`` mod N (``fields.pow_fixed2``) and
``recover.to_affine``'s inversion, 329 products a chain, each a handful of
fusions with a ``moveaxis`` into and out of it.  :func:`power_call` runs such
chains whole: a tile's accumulators and 16-entry window tables stay in VMEM
for all 63 windows, the public digit indexes the table's leading axis (no
select tree), two chains advance in the same iteration.  Same table build,
same windows, same schedule: limb for limb ``pow_fixed2``'s accumulators.

**Limbs lead.**  A field element is ``(..., 20, 8, 128)``: one vector
register a limb, the limb axis third from last, any axes before it a stack
of independent elements.  Every op is a ``jax.lax`` primitive on such slabs:
a product is ONE outer-product multiply and one sum (``_conv``), a carry
pass five equations, never 400 register-sized operations in Python, because
a leading axis is only a choice of registers to Mosaic and an add of padded
zeros is folded by it.  The point formulas are LEVELLED for the same reason:
products that do not depend on each other are stacked and multiplied by one
call (the doubling's 7 products are 3 calls, the add's 16 are 6), and the
four doublings are a ``fori_loop`` of one.  What this buys is set-up, not
speed: a process traces these bodies in Python and lowers them to Mosaic MLIR
before the compile cache's key exists, so those seconds are paid by every
process, cache or no cache (PR 47's register-by-register body cost six of
them and was refused for it; the ladder's is some 1,500 equations, the
powers' 900 and 300; ``scripts/setup_budget.py`` measures them,
``tests/test_pallas_ladder.py`` holds the counts).

**Two paths, chosen by what the code can see** (:func:`kind`): the ladder
batch's shape, the backend, and whether a mapped axis is in scope; one rule
for the ladder and the powers.  Mosaic does not run on XLA:CPU and a
``(4, 128)`` batch is half a tile whatever holds it, so the scans stay for
the unfolded programs (8, 32 and 128 lanes lower to the text they always did)
and for every backend that is not a TPU, and they stay the reference these
kernels are checked against (``tests/test_pallas_ladder.py``; on the chip
``tests/test_secp256k1.py``).  Under ``shard_map`` the scans stay as well: no
four-chip run has checked the kernels there (``PERF.md`` section 7).

``jax.experimental.pallas`` is imported where a kernel is first traced,
not with this module.
"""

from __future__ import annotations

import functools
import math
import sys
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import fields
from .fields import LIMB_BITS, LIMB_MASK, Modulus

__all__ = ["kind", "glv_ladder", "fixed_powers", "PALLAS", "SCAN"]

PALLAS, SCAN = "pallas", "scan"
_TILE = (8, 128)  # one int32 vector register
_AXIS = -3  # the limb axis of an in-kernel field element


def supported() -> bool:
    """True where the kernel can run compiled: a TPU backend (the same
    predicate ``pallas_keccak`` dispatches on)."""
    return jax.default_backend() == "tpu"


def kind(lanes: int) -> str:
    """Which ladder a program of ``lanes`` signatures runs: ``"pallas"``
    where the ladder batch folds to whole ``(8, 128)`` tiles, the backend is
    a TPU and no mapped axis is in scope, else ``"scan"``.  The one rule:
    ``secp256k1.ecmul2_base`` traces by it, ``ecdsa_recover`` and
    ``to_affine`` choose their Fermat chains by it, and the verifiers'
    dispatch spans report it."""
    from jax._src import core  # no public reader of the axis environment

    mapped = bool(core.get_axis_env().axis_sizes)
    return PALLAS if lanes % 256 == 0 and supported() and not mapped else SCAN


# ---------------------------------------------------------------------------
# Field arithmetic on limb-leading slabs.  Plain jax on values: it traces
# inside the kernel and runs as it is on any backend (the tier-1 tests hold
# each op to Python integers and to ``fields``' own result).
# ---------------------------------------------------------------------------


# The ops below are ``jax.lax`` primitives called directly, scalars as numpy
# int32 (jaxpr literals): a ``jnp`` function or an operator on a tracer is a
# jitted wrapper that is traced and inlined call by call, and that Python is
# set-up every process pays.


def _i32(v: int):
    return np.int32(v)


def _limbs(z, start: int, stop: int):
    return lax.slice_in_dim(z, start, stop, axis=z.ndim + _AXIS)


def _pad(z, below: int, above: int):
    """``z`` with ``below`` zero limbs under it and ``above`` over it."""
    if not (below or above):
        return z
    config = [(0, 0, 0)] * z.ndim
    config[z.ndim + _AXIS] = (below, above, 0)
    return lax.pad(z, _i32(0), config)


def _width(z) -> int:
    return z.shape[_AXIS]


def _stack(elements):
    return lax.concatenate([lax.expand_dims(e, (0,)) for e in elements], 0)


def _unstack(z):
    return [lax.index_in_dim(z, i, 0, keepdims=False) for i in range(z.shape[0])]


def _like(x, z):
    """``x`` broadcast over the leading (stack) axes of ``z``."""
    if x.ndim == z.ndim:
        return x
    lead = z.ndim - x.ndim
    return lax.broadcast_in_dim(x, z.shape[:lead] + x.shape, tuple(range(lead, z.ndim)))


def _carry(z, passes: int):
    """:func:`fields._carry` with the limbs leading."""
    for _ in range(passes):
        c = _limbs(lax.shift_right_arithmetic(z, _i32(LIMB_BITS)), 0, _width(z) - 1)
        z = lax.add(lax.bitwise_and(z, _i32(LIMB_MASK)), _pad(c, 1, 0))
    return z


def _sheared_sum(rows):
    """``sum_i rows[i] * 2**(13 i)`` as limb columns, no carries: row ``i``
    sits ``i`` limbs up (Mosaic folds the adds of the padding's zeros)."""
    if len(rows) == 1:
        return rows[0]
    mid = len(rows) // 2
    lo, hi = _sheared_sum(rows[:mid]), _sheared_sum(rows[mid:])
    width = max(_width(lo), mid + _width(hi))
    return lax.add(_pad(lo, 0, width - _width(lo)), _pad(hi, mid, width - mid - _width(hi)))


def _conv(a, b):
    """Schoolbook product columns of two elements, ``fields._conv``'s way:
    ONE outer product (every limb of ``a`` against every limb of ``b``), each
    row padded to twice its width and the rows merged, so that the flat
    buffer re-split one limb narrower holds row ``i`` sheared ``i`` limbs up
    (``f[i * 2L + j]`` is ``f[i * (2L - 1) + (i + j)]``), and the rows are
    summed.  Eight equations a product; Mosaic sees choices of registers up
    to the sum, and folds the adds of zeros (400 multiplies and 361 adds a
    product in its dump: the minimum)."""
    L, at = _width(a), a.ndim + _AXIS
    lead, tile = a.shape[:at], a.shape[at + 1 :]
    outer = lead + (L, L) + tile
    dims = tuple(range(at)) + (at + 1, at + 2, at + 3)  # b: limbs on the inner axis
    prod = lax.mul(
        lax.broadcast_in_dim(a, outer, tuple(range(at + 1)) + (at + 2, at + 3)),
        lax.broadcast_in_dim(b, outer, dims),
    )
    config = [(0, 0, 0)] * prod.ndim
    config[at + 1] = (0, L, 0)
    flat = lax.reshape(lax.pad(prod, _i32(0), config), lead + (2 * L * L,) + tile)
    sheared = lax.reshape(_limbs(flat, 0, L * (2 * L - 1)), lead + (L, 2 * L - 1) + tile)
    return lax.reduce_sum(sheared, (at,))


def _conv_const(a, limbs: Sequence[int]):
    """Product columns of ``a`` and a constant given by its limbs."""
    return _sheared_sum([a if c == 1 else lax.mul(a, _i32(c)) for c in map(int, limbs)])


def _fold_semi(m: Modulus, z, bound: int, limb: int):
    """:func:`fields._fold_semi` with the limbs leading: the same schedule,
    executed on slabs."""
    L = m.nlimbs
    plan = fields._fold_schedule(m, _width(z), bound, limb)
    for rnd in plan.rounds:
        z = _carry(z, rnd.passes)
        hi = _conv_const(_limbs(z, L, L + rnd.hi_len), m.c_fold_limbs)
        z = lax.add(
            _pad(_limbs(z, 0, L), 0, rnd.out_len - L), _pad(hi, 0, rnd.out_len - _width(hi))
        )
    fbl, fbs = m.fb_limb, m.fb_shift
    z = _carry(z, plan.passes)
    z = _limbs(_pad(z, 0, max(0, fbl + 1 - _width(z))), 0, plan.width)
    top = _limbs(z, fbl, fbl + 1)
    hi = lax.shift_right_arithmetic(top, _i32(fbs))
    for j in range(fbl + 1, plan.width):
        hi = lax.add(hi, lax.shift_left(_limbs(z, j, j + 1), _i32(LIMB_BITS * (j - fbl) - fbs)))
    # Limbs under ``fbl`` are kept whole (fields masks them with 2**31 - 1:
    # they are unsigned), limb ``fbl`` keeps its low ``fbs`` bits; ``hi *
    # c_fb`` is added a non-zero limb of ``c_fb`` at a time.
    parts = [_limbs(z, 0, fbl), lax.bitwise_and(top, _i32((1 << fbs) - 1))]
    z = lax.concatenate(parts, z.ndim + _AXIS)
    for j, c in enumerate(map(int, m.c_fb_limbs)):
        if c:
            z = lax.add(z, _pad(lax.mul(hi, _i32(c)), j, L - j - 1))
    return _carry(z, plan.last_passes)


def _const(values: Sequence[int], tile: Tuple[int, ...] = _TILE):
    """A constant element from its limbs, built from scalars (a kernel
    captures no array)."""
    return lax.concatenate([lax.full((1,) + tile, int(v), jnp.int32) for v in values], 0)


class SlabField:
    """``fields``' ops mod ``m`` on ``(..., L, 8, 128)`` elements.  An
    instance holds the two constant elements the ops need (``K*p`` in its fat
    form, ``p``); make it where they may live: inside the kernel, or outside
    any trace."""

    def __init__(self, m: Modulus, tile: Tuple[int, ...] = _TILE):
        self.m = m
        self.tile = tile
        self.fat_kp = _const(m.fat_kp, tile)
        self.p = _const(m.limbs, tile)

    def const(self, value: int):
        return _const(self.m.const(value), self.tile)

    def zero(self):
        return lax.full((self.m.nlimbs,) + self.tile, 0, jnp.int32)

    def add(self, a, b):
        return _fold_semi(self.m, lax.add(a, b), 4 * self.m.p, 2 * fields._EDGE)

    def sub(self, a, b):
        m = self.m
        z = lax.sub(lax.add(a, _like(self.fat_kp, a)), b)
        return _fold_semi(m, z, m.sub_bound, fields._EDGE + int(m.fat_kp.max()))

    def muli(self, a, k: int):
        return _fold_semi(self.m, lax.mul(a, _i32(k)), 2 * self.m.p * k, k * fields._EDGE)

    def mul(self, a, b):
        m = self.m
        bound = (2 * m.p) ** 2
        out_len = -(-bound.bit_length() // LIMB_BITS) + 1
        cols = fields._column_bound("mul", fields._EDGE, m.nlimbs * fields._EDGE)
        z = _conv(a, b)
        return _fold_semi(m, _pad(z, 0, out_len - _width(z)), bound, cols)

    def sqr(self, a):
        return self.mul(a, a)

    def is_zero(self, a):
        """:func:`fields.is_zero_fast`: ``a === 0 (mod p)`` for a semi-reduced
        ``a``, by the same Kogge-Stone carry; the limb axis is summed out."""
        L = self.m.nlimbs
        mask, bits = _i32(LIMB_MASK), _i32(LIMB_BITS)

        def up(x, d):  # limb i -> limb i + d
            return _pad(_limbs(x, 0, L - d), d, 0)

        s = lax.add(lax.bitwise_and(a, mask), up(lax.shift_right_arithmetic(a, bits), 1))
        gen = lax.shift_right_arithmetic(s, bits)
        prop = lax.convert_element_type(lax.eq(s, mask), jnp.int32)
        d = 1
        while d < L:
            gen = lax.bitwise_or(gen, lax.bitwise_and(prop, up(gen, d)))
            prop = lax.bitwise_and(prop, up(prop, d))
            d *= 2
        c = lax.bitwise_and(lax.add(s, up(gen, 1)), mask)
        # Canonical limbs are unsigned: a sum is zero where every term is.
        is_p = _limb_sum(lax.bitwise_xor(c, _like(self.p, c)))
        return lax.bitwise_or(lax.eq(_limb_sum(c), _i32(0)), lax.eq(is_p, _i32(0)))


def _limb_sum(c):
    """The limbs of ``c`` added up, the limb axis dropped: halves added to
    halves (a reduction over a leading axis, written as slab adds)."""
    while _width(c) > 1:
        half = _width(c) // 2
        s = lax.add(_limbs(c, 0, half), _limbs(c, half, 2 * half))
        if _width(c) % 2:  # the odd limb out rides along
            s = lax.concatenate([s, _limbs(c, 2 * half, 2 * half + 1)], c.ndim + _AXIS)
        c = s
    return lax.squeeze(c, (c.ndim + _AXIS,))


def _where(cond, a, b):
    """``cond`` (one flag a lane) chooses between two elements or points."""
    return lax.select(lax.broadcast_in_dim(cond, a.shape, tuple(range(a.ndim - 2, a.ndim))), a, b)


# ---------------------------------------------------------------------------
# The point formulas of ``secp256k1.point_double`` / ``_point_add_core`` on a
# ``(3, L, 8, 128)`` point (x, y, z), levelled: each ``f.mul`` / ``f.sqr``
# below is ONE product call on a stack of independent operands.
# ---------------------------------------------------------------------------


def _double(f: SlabField, p):
    x, y, z = _unstack(p)
    a, b, yz = _unstack(f.mul(_stack([x, y, y]), p))  # x*x, y*y, y*z
    e = f.muli(a, 3)
    c, t, ff = _unstack(f.sqr(_stack([b, f.add(x, b), e])))
    d = f.muli(f.sub(f.sub(t, a), c), 2)
    x3 = f.sub(ff, f.muli(d, 2))
    y3 = f.sub(f.mul(e, f.sub(d, x3)), f.muli(c, 8))
    return _stack([x3, y3, f.muli(yz, 2)])


def _add_core(f: SlabField, p, q):
    """INCOMPLETE Jacobian addition and the two infinity selects: see
    ``secp256k1._point_add_core`` for what it leaves out and why the ladder
    may."""
    (x1, y1, z1), (x2, y2, z2) = _unstack(p), _unstack(q)
    zs = _stack([z1, z2])
    z1s, z2s = _unstack(f.sqr(zs))
    u1, u2, t1, t2, zz = _unstack(
        f.mul(_stack([x1, x2, z2s, z1s, z1]), _stack([z2s, z1s, z2, z1, z2]))
    )
    s1, s2 = _unstack(f.mul(_stack([y1, y2]), _stack([t1, t2])))
    h = f.sub(u2, u1)
    r = f.sub(s2, s1)
    hs, rs, z3 = _unstack(f.mul(_stack([h, r, zz]), _stack([h, r, h])))
    hc, u1hs = _unstack(f.mul(_stack([hs, u1]), _stack([h, hs])))
    x3 = f.sub(f.sub(rs, hc), f.muli(u1hs, 2))
    ry, s1hc = _unstack(f.mul(_stack([r, s1]), _stack([f.sub(u1hs, x3), hc])))
    out = _stack([x3, f.sub(ry, s1hc), z3])
    inf_p, inf_q = _unstack(f.is_zero(zs))
    return _where(inf_q, p, _where(inf_p, q, out))


def _select16(d, table):
    """:func:`fields.select16`'s entry ``d`` of a ``(16, ...)`` table (an
    array or a ref), ``d`` one digit a lane: the table is read once and
    halved by a bit of ``d`` four times."""
    t = table[...]
    for j in (3, 2, 1, 0):
        half = 1 << j
        bit = lax.ne(lax.bitwise_and(d, _i32(half)), _i32(0))
        t = _where(bit, lax.slice_in_dim(t, half, 2 * half), lax.slice_in_dim(t, 0, half))
    return lax.squeeze(t, (0,))


def _step(f: SlabField, acc, d, neg, tx, ty, tz):
    """One ladder step, ``body`` of ``secp256k1.ecmul2_base``'s scan: four
    doublings, the digit's table entry with ``y`` negated where the stream's
    half-scalar is, one incomplete add."""
    acc = lax.fori_loop(0, 4, lambda _, q: _double(f, q), acc)
    y = _select16(d, ty)
    y = _where(neg, f.sub(f.zero(), y), y)
    return _add_core(f, acc, _stack([_select16(d, tx), y, _select16(d, tz)]))


def _infinity(f: SlabField):
    one = f.const(1)
    return _stack([one, one, f.zero()])


def _kernel(steps, field, digits_ref, neg_ref, tx_ref, ty_ref, tz_ref, out_ref):
    f = SlabField(field)
    neg = lax.ne(neg_ref[...], _i32(0))

    def step(i, acc):
        return _step(f, acc, digits_ref[i], neg, tx_ref, ty_ref, tz_ref)

    out_ref[...] = lax.fori_loop(0, steps, step, _infinity(f))


def _pallas():
    """``jax.experimental.pallas`` and its ``tpu``, imported on first use and
    without Pallas's GPU interpreter.  ``pallas_call.py`` imports that
    interpreter, and with it the Mosaic GPU dialects, in a ``try`` whose
    ``except ImportError`` leaves a placeholder: it serves GPU interpret mode
    alone, and is 0.7 s of the 1.2 s this import costs a process on the chip
    machine's host (``PERF.md`` section 6, PR 48).  A ``None`` entry in
    ``sys.modules`` is Python's way to make one import raise ImportError."""
    gpu_interpreter = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"
    placed = gpu_interpreter not in sys.modules
    if placed:
        sys.modules[gpu_interpreter] = None
    try:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    except ImportError:  # a jax whose import no longer tolerates the gap
        sys.modules.pop(gpu_interpreter, None)
        placed = False
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    finally:
        if placed:
            del sys.modules[gpu_interpreter]
    return pl, pltpu


# A tile's three tables are 3 x 16 x 20 registers (3.9 MB), double-buffered;
# the products' intermediates live in VMEM beside them.
_VMEM_LIMIT = 64 << 20


def ladder_call(digits, neg, tx, ty, tz, *, field: Modulus, interpret: bool = False):
    """The kernel on limb-leading operands: ``digits`` ``(steps, rows, 128)``,
    ``neg`` ``(rows, 128)`` int32, tables ``(16, L, rows, 128)``; returns the
    accumulator ``(3, L, rows, 128)``.  ``rows`` a multiple of 8: one grid
    step a row tile."""
    pl, pltpu = _pallas()
    steps, rows, _ = digits.shape
    L = field.nlimbs
    sub, lane = _TILE
    table = pl.BlockSpec((16, L, sub, lane), lambda i: (0, 0, i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, steps, field),
        name="glv_ladder",
        out_shape=jax.ShapeDtypeStruct((3, L, rows, lane), jnp.int32),
        grid=(rows // sub,),
        in_specs=[
            pl.BlockSpec((steps, sub, lane), lambda i: (0, i, 0)),
            pl.BlockSpec((sub, lane), lambda i: (i, 0)),
            table,
            table,
            table,
        ],
        out_specs=pl.BlockSpec((3, L, sub, lane), lambda i: (0, 0, i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
    )(digits, neg, tx, ty, tz)


def glv_ladder(digits, neg, tx, ty, tz, *, field: Modulus):
    """``ecmul2_base``'s ladder on its folded operands (``digits``
    ``(33, rows, 128)``, ``neg`` ``(rows, 128)`` bool, tables ``(16, rows,
    128, L)``): the accumulator's ``(x, y, z)``, each ``(rows, 128, L)``.
    The limb axis is moved to the front going in and back coming out."""
    acc = ladder_call(
        digits,
        neg.astype(jnp.int32),
        *(jnp.moveaxis(t, -1, 1) for t in (tx, ty, tz)),
        field=field,
    )
    return tuple(jnp.moveaxis(acc[i], 0, -1) for i in range(3))


# ---------------------------------------------------------------------------
# Fixed-exponent powers: ``fields.pow_fixed`` / ``pow_fixed2`` (the square
# root and r**-1 of ``recover.lift_x``, the inversion of
# ``recover.to_affine``), a whole chain in one kernel.
# ---------------------------------------------------------------------------


def _power_kernel(moduli, nwin, digits_ref, a_ref, out_ref, table_ref):
    """``a_ref[c] ** e_c mod moduli[c]`` for every chain ``c`` of a tile, the
    way ``fields.pow_fixed2`` goes: the window table ``a**0 .. a**15`` by 14
    products, then a window at a time four squarings and one product by the
    table's entry, every chain advanced in the same iteration.  The exponents
    are public and one for all lanes: ``digits_ref`` (SMEM, chain ``c``'s
    ``nwin`` digits from ``c * nwin``, most significant first) indexes the
    leading axis of the chain's table in VMEM."""
    chains = range(len(moduli))
    fs = [SlabField(m) for m in moduli]

    def entry(c, i):
        return table_ref[c * 16 + digits_ref[c * nwin + i]]

    def build(d, prev):
        nxt = tuple(fs[c].mul(prev[c], a_ref[c]) for c in chains)
        for c in chains:
            table_ref[c * 16 + d] = nxt[c]
        return nxt

    def window(i, accs):
        accs = lax.fori_loop(
            0, 4, lambda _, q: tuple(fs[c].sqr(q[c]) for c in chains), accs
        )
        return tuple(fs[c].mul(accs[c], entry(c, i)) for c in chains)

    for c in chains:
        table_ref[c * 16] = fs[c].const(1)
        table_ref[c * 16 + 1] = a_ref[c]
    lax.fori_loop(2, 16, build, tuple(a_ref[c] for c in chains))
    accs = lax.fori_loop(1, nwin, window, tuple(entry(c, 0) for c in chains))
    for c in chains:
        out_ref[c] = accs[c]


def power_call(a, *, moduli: Sequence[Modulus], exponents: Sequence[int], name: str, interpret: bool = False):
    """The kernel on limb-leading operands: ``a`` ``(chains, L, rows, 128)``,
    chain ``c`` an element mod ``moduli[c]`` raised to the public
    ``exponents[c]`` (4-bit windows, most significant first, the shorter
    exponent padded with leading zero digits as ``pow_fixed2`` pads it);
    returns the powers, shaped as ``a``.  One grid step a row tile, a tile's
    ``16 * chains`` table entries (1.3 MB a chain) in VMEM scratch."""
    if min(exponents) <= 0:
        raise ValueError("power_call requires positive exponents")
    pl, pltpu = _pallas()
    chains, L, rows, lane = a.shape
    sub = _TILE[0]
    nwin = max(-(-e.bit_length() // 4) for e in exponents)
    digits = np.concatenate([fields._pow_digits(e, nwin) for e in exponents])
    block = pl.BlockSpec((chains, L, sub, lane), lambda i, _: (0, 0, i, 0))
    return pl.pallas_call(
        functools.partial(_power_kernel, tuple(moduli), nwin),
        name=name,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // sub,),
            in_specs=[block],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((16 * chains, L, sub, lane), jnp.int32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
    )(digits, a)


def fixed_powers(name: str, *chains: Tuple[Modulus, jnp.ndarray, int]):
    """``fields.pow_fixed2`` of every lane as one kernel called ``name``:
    each chain is ``(modulus, a, exponent)``, the ``a`` of one shape
    ``batch + (L,)``; returns each ``a ** exponent``, semi-reduced and limb
    for limb what the scan gives (one chain: ``fields.pow_fixed``'s).  The
    lanes are padded with zeros (``0 ** e == 0``) to whole ``(8, 128)`` tiles
    and the limb axis leads inside; both relayouts are made here, once."""
    moduli, elements, exponents = zip(*chains)
    batch, L = elements[0].shape[:-1], elements[0].shape[-1]
    lanes = math.prod(batch)
    per_tile = _TILE[0] * _TILE[1]
    a = jnp.stack([e.reshape(lanes, L) for e in elements])
    a = jnp.pad(a, ((0, 0), (0, -lanes % per_tile), (0, 0)))
    a = jnp.moveaxis(a.reshape(len(chains), -1, _TILE[1], L), -1, 1)
    out = power_call(a, moduli=moduli, exponents=exponents, name=name)
    out = jnp.moveaxis(out, 1, -1).reshape(len(chains), -1, L)[:, :lanes]
    return tuple(out[c].reshape(batch + (L,)) for c in range(len(chains)))
