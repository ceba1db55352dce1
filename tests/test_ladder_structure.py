"""The GLV ladder's scan body carries the add it needs and no more.

``ecmul2_base``'s 33-step scan adds with ``_point_add_core`` (no ``P == Q``
fallback): 28 + 16 = 44 field products a step where the complete add made
it 51.  That is sound only while an accumulator lane and its addend cannot
be the same point off infinity; the integer walk below states that invariant
over the scalars most likely to break it.  Everything here traces or counts
— nothing compiles — so it runs in tier-1 (the compiled ladder is held to
Python integers in ``test_secp256k1.py``, slow tier).
"""

import ast
import collections
import math
import os
import random
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from go_ibft_tpu.ops import fields as F
from go_ibft_tpu.ops import secp256k1 as sec

import ladder_cases as lc

LANES = 8
L = sec.FIELD.nlimbs
OPS_DIR = os.path.dirname(os.path.abspath(sec.__file__))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        inner = getattr(v, "jaxpr", v)
        if hasattr(inner, "eqns"):
            yield inner


def _count(jaxpr, pred) -> int:
    """Equations matching ``pred``, an inner scan's counted once per
    iteration."""
    total = 0
    for eqn in jaxpr.eqns:
        total += bool(pred(eqn))
        times = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        total += times * sum(_count(j, pred) for j in _sub_jaxprs(eqn))
    return total


def _calls(jaxpr, under_scan=False) -> collections.Counter:
    """``(jitted function's name, inside an inner scan)`` per call site."""
    seen = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            seen[(eqn.params["name"], under_scan)] += 1
        inner = under_scan or eqn.primitive.name == "scan"
        for j in _sub_jaxprs(eqn):
            seen += _calls(j, inner)
    return seen


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        else:
            for j in _sub_jaxprs(eqn):
                yield from _scans(j)


def _trace(lanes):
    s = jax.ShapeDtypeStruct((lanes, L), jnp.int32)
    return jax.make_jaxpr(sec.ecmul2_base)(s, s, s, s).jaxpr


def _ladder_scan(jaxpr):
    """The one ``recover.glv_ladder`` scan of a traced ``ecmul2_base``."""
    ladders = [
        e
        for e in _scans(jaxpr)
        if "recover.glv_ladder" in str(e.source_info.name_stack)
    ]
    assert len(ladders) == 1 and ladders[0].params["length"] == sec._GLV_NWIN
    return ladders[0]


@pytest.fixture(scope="module")
def ladder_body():
    """The body of the ``recover.glv_ladder`` scan, traced at 8 lanes."""
    return _ladder_scan(_trace(LANES)).params["jaxpr"].jaxpr


def _is_field_product(eqn, batch=(4, LANES)) -> bool:
    """``fields._conv``'s outer product of two 20-limb elements on the
    ladder's batch (the fold's product has 3 rows)."""
    return (
        eqn.primitive.name == "mul"
        and eqn.outvars[0].aval.shape == (L, 2 * L - 1) + batch
    )


def test_scan_step_is_44_field_products(ladder_body):
    # 4 doublings x (5 sqr + 2 mul) + one generic add (4 sqr + 12 mul); the
    # complete add's embedded doubling made it 51.
    assert _count(ladder_body, _is_field_product) == 4 * 7 + 16


def _is_reshape(eqn) -> bool:
    return eqn.primitive.name == "reshape"


def _core_shapes(jaxpr):
    """Operand shapes of every ``_point_add_core`` call site, any depth."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit" and eqn.params["name"] == "_point_add_core":
            yield tuple(v.aval.shape for v in eqn.invars)
        for j in _sub_jaxprs(eqn):
            yield from _core_shapes(j)


# The ladder's batch is folded so that the TPU tiles it T(8,128), where that
# fills whole tiles (PR 30): lanes % 256 == 0.  8 is every budget program's
# shape, 128 the PREPARE flood's; 256 (the joint COMMIT dispatch at 100
# validators, PR 32: exactly one tile a row), 512, 1,024 and 2,048 fold.
@pytest.mark.parametrize(
    "lanes,batch",
    [
        (8, (4, 8)),
        (128, (4, 128)),
        (256, (8, 128)),
        (512, (16, 128)),
        (1024, (32, 128)),
        (2048, (64, 128)),
    ],
)
def test_ladder_batch_fills_whole_tiles_where_it_can(ladder_body, lanes, batch):
    jaxpr = _trace(lanes)
    scan = _ladder_scan(jaxpr)
    carry = scan.invars[scan.params["num_consts"] :][: scan.params["num_carry"]]
    assert [v.aval.shape for v in carry] == [batch + (L,)] * 3
    # Tables, digits and neg enter folded as well: nothing in the body is
    # left on (4, lanes) for XLA to re-tile each step.
    body = scan.params["jaxpr"].jaxpr
    shapes = {v.aval.shape for v in body.invars if v.aval.ndim > 1}  # not constants
    assert shapes == {batch + (L,), (16,) + batch + (L,), batch}
    assert _count(body, lambda e: _is_field_product(e, batch)) == 4 * 7 + 16
    assert _count(body, _is_reshape) == _count(ladder_body, _is_reshape)
    # One instance of the core for the body and recover.combine's two
    # complete adds: same operand shapes at all three call sites.
    assert list(_core_shapes(jaxpr)) == [(batch + (L,),) * 6] * 3
    assert [v.aval.shape for v in jaxpr.outvars] == [(lanes, L)] * 3


def test_scan_step_calls_the_core_and_only_double4_doubles(ladder_body):
    calls = _calls(ladder_body)
    points = {k: n for k, n in calls.items() if k[0].startswith(("point_", "_point_"))}
    assert points == {("_point_add_core", False): 1, ("point_double", True): 1}
    # is_zero_fast is two ``jnp.all`` (value 0 or p): the two infinity
    # selects of the core and nothing else (H == 0, R == 0 are not asked).
    assert _count(ladder_body, lambda e: e.primitive.name == "reduce_and") == 4


def test_core_is_called_from_the_ladder_body_and_the_complete_add_only():
    repo = os.path.dirname(os.path.dirname(OPS_DIR))
    callers = collections.Counter()
    for top in ("go_ibft_tpu", "scripts", "benchmark"):
        for dirpath, _, names in os.walk(os.path.join(repo, top)):
            for name in (n for n in names if n.endswith(".py")):
                with open(os.path.join(dirpath, name)) as fh:
                    tree = ast.parse(fh.read())
                for fn in ast.walk(tree):
                    if not isinstance(fn, ast.FunctionDef):
                        continue
                    for node in ast.walk(fn):
                        if "_point_add_core" in (
                            getattr(node, "id", None),
                            getattr(node, "attr", None),
                        ):
                            callers[(name, fn.name)] += 1
    # ``body`` is ecmul2_base's scan body: ast.walk reports it under both.
    assert callers == {
        ("secp256k1.py", "point_add"): 1,
        ("secp256k1.py", "ecmul2_base"): 1,
        ("secp256k1.py", "body"): 1,
    }
    with open(os.path.join(OPS_DIR, "secp256k1.py")) as fh:
        src = fh.read()
    shamir = src[src.index("def _ecmul2_base_shamir") : src.index("def ecmul2_base")]
    assert "_point_add_core" not in shamir and "point_add(acc, addq)" in shamir


def _assert_never_coincide(k: int) -> None:
    steps = list(lc.ladder_steps(k))
    assert len(steps) == 2 * lc.NWIN
    for m, d in steps:
        assert m < 1 << 132 and 16 * m + d < lc.N
        same = (16 * m - d) % lc.N == 0  # 16m*B == d*B
        opposite = (16 * m + d) % lc.N == 0  # 16m*B == -d*B
        assert (same or opposite) == (m == 0 and d == 0), (hex(k), m, d)


EDGE_SCALARS = lc.edge_scalars()


@pytest.mark.parametrize(
    "k", [k for _, k in EDGE_SCALARS], ids=[n for n, _ in EDGE_SCALARS]
)
def test_accumulator_never_meets_its_addend(k):
    _assert_never_coincide(k)


def test_accumulator_never_meets_its_addend_random():
    rng = random.Random(2828)
    for _ in range(2000):
        _assert_never_coincide(rng.randrange(lc.N))


def _is_prime(n: int) -> bool:
    """Miller-Rabin over fixed bases."""
    d, r = n - 1, 0
    while d % 2 == 0:
        d, r = d // 2, r + 1
    for a in (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37):
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def test_what_the_invariant_leans_on():
    """Streams of 132 bits and digits under 16, so ``16m + d < 2**136 < N``;
    and every point but infinity has order N: G has (N prime, N*G is
    infinity), and N is the only multiple of N in Hasse's interval around
    P + 1, so the group has N points and no cofactor."""
    assert 4 * sec._GLV_NWIN == 132 and sec._WINDOW == 4
    assert 1 << 136 < sec.N
    assert _is_prime(sec.N)
    assert lc.host.scalar_mul(sec.N - 1, lc.G) == lc.neg(lc.G)
    hasse = 2 * (math.isqrt(sec.P) + 1)
    assert abs(sec.N - (sec.P + 1)) <= hasse and sec.N > 2 * hasse


# ---- the carry schedule of ``ops/fields.py`` (PR 46): pass counts come from
# limb bounds tracked at trace time, so the jaxpr is where they are read.

FIELD_OPS = {
    "mul": (F.mul, 2),
    "sqr": (F.sqr, 1),
    "add": (F.add, 2),
    "sub": (F.sub, 2),
    "muli2": (partial(F.muli, k=2), 1),
    "muli3": (partial(F.muli, k=3), 1),
    "muli8": (partial(F.muli, k=8), 1),
    "muli16": (partial(F.muli, k=16), 1),
}
# A product's passes, each site carried as far as its consumer's int32 columns
# admit: after the 20 x 20 columns one mod P (limbs <= 172,031 against a
# ceiling of 253,659: the sum of c_fold's 3 limbs is 8,465) and two mod N
# (ceiling 62,891: 11 limbs that sum to 34,145), one after each fold round,
# two after the final fold.  19 until PR 46.
CARRY_PASSES = {"mul": {"p": 5, "n": 6}, "sqr": {"p": 5, "n": 6}}
MODULI = {"p": sec.FIELD, "n": sec.ORDER}


def _is_carry_pass(eqn, batch_rank=1) -> bool:
    """``z >> 13`` over a limb axis (the final fold's ``hi`` is a shift of
    ONE limb: the batch's rank)."""
    return (
        eqn.primitive.name == "shift_right_arithmetic"
        and eqn.outvars[0].aval.ndim == batch_rank + 1
    )


@pytest.mark.parametrize("modulus", MODULI)
@pytest.mark.parametrize("op", FIELD_OPS)
def test_field_op_carries_as_often_as_its_bounds_ask(op, modulus):
    fn, nargs = FIELD_OPS[op]
    x = jax.ShapeDtypeStruct((LANES, L), jnp.int32)
    jaxpr = jax.make_jaxpr(partial(fn, MODULI[modulus]))(*(x,) * nargs).jaxpr
    want = CARRY_PASSES.get(op, {}).get(modulus, 2)
    assert _count(jaxpr, _is_carry_pass) == want
    assert [v.aval.shape for v in jaxpr.outvars] == [(LANES, L)]


def test_ladder_step_carries_by_the_same_schedule(ladder_body):
    """Every pass of the scan body belongs to a field op mod P: 5 a product
    and at most 2 an additive op (``is_zero_fast``'s Kogge-Stone carry
    shifts once a call: the four infinity tests)."""
    passes = _count(ladder_body, lambda e: _is_carry_pass(e, 2))
    products = _count(ladder_body, _is_field_product)
    assert products == 44
    additive = (passes - 5 * products - 4) / 2
    assert additive == int(additive) and 40 <= additive <= 60, passes


@pytest.mark.parametrize(
    "b_in,b_out,want",
    [
        (20 << 26, 253_659, (1, 172_031)),  # the 20 x 20 columns to P's first fold round
        (20 << 26, 62_891, (2, 8_211)),  # ... and to N's
        (172_031 * 8_465 + 172_031, 253_659, (1, 185_975)),  # P: round 1's sum to round 2
        (8_211 * 34_145 + 8_211, 62_891, (1, 42_416)),  # N: the same
        (1 << 14, 4_201_982, (0, 1 << 14)),  # a sum of two edge operands to the final fold
        (8_192 + 8_191, 8_192, (1, 8_192)),
        ((1 << 31) - 1, 8_192, (3, 8_192)),  # from anything int32 holds, three
        (8_192, 8_192, (0, 8_192)),
    ],
)
def test_passes_between_two_limb_bounds(b_in, b_out, want):
    assert F._passes(b_in, b_out) == want


def test_limb_ceilings_of_the_two_moduli():
    for m, rows, c_sum, ceiling in ((sec.FIELD, 3, 8_465, 253_659), (sec.ORDER, 11, 34_145, 62_891)):
        assert len(m.c_fold_limbs) == rows and int(m.c_fold_limbs.sum()) == c_sum
        assert F._limb_ceiling(c_sum) == ceiling
        assert F._column_bound("fold", ceiling, c_sum, plus=ceiling) < 1 << 31


def test_a_modulus_whose_columns_overflow_fails_to_trace():
    """41 limbs make 41 products a column: 41 * 2**26 >= 2**31.  Until PR 46
    this traced, compiled and summed wrongly."""
    m = F.Modulus(2**521 - 1)
    x = jax.ShapeDtypeStruct((LANES, m.nlimbs), jnp.int32)
    with pytest.raises(ValueError, match=r"mul: limbs <= 8192 x 335872 a column .* overflows an int32 column"):
        jax.eval_shape(partial(F.mul, m), x, x)
    # the additive ops have no product of many rows: they still trace
    assert jax.eval_shape(partial(F.add, m), x, x).shape == (LANES, m.nlimbs)


@pytest.mark.parametrize(
    "call,message",
    [
        (lambda: F._column_bound("fold", 70_000, 34_145, plus=70_000), "fold: limbs <= 70000 x 34145"),
        (lambda: F._passes(1 << 20, 8_191), "no carry pass brings limbs under"),
        (lambda: F._passes(1 << 31, 8_192), "does not fit int32"),
        (lambda: F._passes(8_192, F._limb_ceiling(40 * 8_191)), "too heavy for this radix"),
    ],
)
def test_bound_helpers_refuse_what_int32_cannot_hold(call, message):
    with pytest.raises(ValueError, match=message):
        call()
