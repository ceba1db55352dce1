"""Oracle tests for the limbed modular arithmetic (ops/fields.py).

Every op is checked against python-int arithmetic over both secp256k1
moduli (field prime and group order) on randomized batches, including
adversarial boundary values (0, 1, p-1, p, 2p-1 pre-reduction classes).
Ops are exercised under ``jax.jit`` — the only way they run in production.
"""

import random
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_ibft_tpu.ops import fields as F

P_SECP = 2**256 - 2**32 - 977
N_SECP = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141

MODULI = [pytest.param(P_SECP, id="p"), pytest.param(N_SECP, id="n")]

_CACHE = {}


def _ops(p):
    """Modulus + jitted ops, cached so each jit compiles once per session."""
    if p not in _CACHE:
        m = F.Modulus(p)
        _CACHE[p] = {
            "m": m,
            "add": jax.jit(partial(F.add, m)),
            "sub": jax.jit(partial(F.sub, m)),
            "mul": jax.jit(partial(F.mul, m)),
            "sqr": jax.jit(partial(F.sqr, m)),
            "canon": jax.jit(partial(F.canon, m)),
            "inv": jax.jit(partial(F.inv, m)),
            "is_zero": jax.jit(partial(F.is_zero, m)),
            "eq_mod": jax.jit(partial(F.eq_mod, m)),
            "muli": {k: jax.jit(partial(F.muli, m, k=k)) for k in (1, 2, 3, 8, 16)},
        }
    return _CACHE[p]


def _samples(p, rng, count=32):
    edge = [0, 1, 2, p - 1, p - 2, 2**255, 2 * p - 1]
    vals = edge + [rng.randrange(2 * p) for _ in range(count - len(edge))]
    return [v % (2 * p) for v in vals]


@pytest.mark.parametrize("p", MODULI)
def test_roundtrip(p):
    m = _ops(p)["m"]
    rng = random.Random(1)
    vals = _samples(p, rng)
    limbs = F.to_limbs(vals, m.nlimbs)
    assert F.from_limbs(limbs) == vals


@pytest.mark.parametrize("p", MODULI)
def test_add_sub_mul(p):
    ops = _ops(p)
    m = ops["m"]
    rng = random.Random(2)
    a_int = _samples(p, rng)
    b_int = list(reversed(_samples(p, rng)))
    a = jnp.asarray(F.to_limbs(a_int, m.nlimbs))
    b = jnp.asarray(F.to_limbs(b_int, m.nlimbs))
    for name, ref in [
        ("add", lambda x, y: (x + y) % p),
        ("sub", lambda x, y: (x - y) % p),
        ("mul", lambda x, y: (x * y) % p),
    ]:
        out = ops[name](a, b)
        # semi-reduced invariant: limbs in [0, 2**13], value < 2p
        arr = np.asarray(out)
        assert arr.min() >= 0 and arr.max() <= 1 << F.LIMB_BITS
        got = F.from_limbs(ops["canon"](out))
        want = [ref(x, y) for x, y in zip(a_int, b_int)]
        assert got == want, name


@pytest.mark.parametrize("p", MODULI)
def test_muli(p):
    ops = _ops(p)
    m = ops["m"]
    rng = random.Random(3)
    a_int = _samples(p, rng)
    a = jnp.asarray(F.to_limbs(a_int, m.nlimbs))
    for k, fn in ops["muli"].items():
        got = F.from_limbs(ops["canon"](fn(a)))
        assert got == [(x * k) % p for x in a_int]


@pytest.mark.parametrize("p", MODULI)
def test_pow_inv(p):
    ops = _ops(p)
    m = ops["m"]
    rng = random.Random(4)
    a_int = _samples(p, rng, 12)
    a = jnp.asarray(F.to_limbs(a_int, m.nlimbs))
    e = rng.randrange(1, p)
    got = F.from_limbs(ops["canon"](jax.jit(partial(F.pow_fixed, m, exponent=e))(a)))
    assert got == [pow(x, e, p) for x in a_int]
    inv = F.from_limbs(ops["canon"](ops["inv"](a)))
    assert inv == [pow(x, p - 2, p) for x in a_int]


@pytest.mark.parametrize("p", MODULI)
def test_predicates(p):
    ops = _ops(p)
    m = ops["m"]
    vals = [0, p, 1, p - 1, p + 1]  # semi-reduced representatives
    a = jnp.asarray(F.to_limbs(vals, m.nlimbs))
    assert list(np.asarray(ops["is_zero"](a))) == [True, True, False, False, False]
    b = jnp.asarray(F.to_limbs([p, 0, p + 1, p - 1, 1], m.nlimbs))
    assert list(np.asarray(ops["eq_mod"](a, b))) == [True] * 5


def test_chained_ops_stay_semi_reduced():
    """Long dependency chains must preserve the invariant (lazy carries)."""
    p = P_SECP
    m = _ops(p)["m"]
    rng = random.Random(5)
    a_int = [rng.randrange(p) for _ in range(8)]
    a = jnp.asarray(F.to_limbs(a_int, m.nlimbs))

    @jax.jit
    def chain(a):
        acc = a
        for i in range(25):
            acc = F.mul(m, acc, a)
            acc = F.sub(m, acc, a) if i % 2 else F.add(m, acc, a)
        return acc

    acc_int = a_int[:]
    for i in range(25):
        acc_int = [
            ((x * y) + (y if i % 2 == 0 else -y)) % p
            for x, y in zip(acc_int, a_int)
        ]
    out = chain(a)
    arr = np.asarray(out)
    assert arr.min() >= 0 and arr.max() <= 1 << F.LIMB_BITS
    assert F.from_limbs(_ops(p)["canon"](out)) == acc_int


@pytest.mark.parametrize("p", MODULI)
@pytest.mark.parametrize("n_lanes", [1, 2, 7, 8])
def test_batch_inv_matches_fermat(p, n_lanes):
    """Montgomery product-tree inverse == per-lane Fermat, including zero
    lanes (inv(0) == 0 contract) and non-power-of-two batches."""
    m = _ops(p)["m"]
    rng = random.Random(7)
    vals = [0] + [rng.randrange(p) for _ in range(n_lanes - 1)]
    vals = vals[:n_lanes]
    a = jnp.asarray(F.to_limbs(vals, m.nlimbs))
    out = jax.jit(partial(F.batch_inv, m))(a)
    got = F.from_limbs(_ops(p)["canon"](out))
    assert got == [pow(v, p - 2, p) if v else 0 for v in vals]


def test_pow_fixed2_matches_two_pow_fixed():
    """One merged dual-modulus scan == two independent windowed scans."""
    mp = _ops(P_SECP)["m"]
    mn = _ops(N_SECP)["m"]
    rng = random.Random(9)
    va = [rng.randrange(P_SECP) for _ in range(4)]
    vb = [rng.randrange(N_SECP) for _ in range(4)]
    e1 = (P_SECP + 1) // 4
    e2 = N_SECP - 2
    a = jnp.asarray(F.to_limbs(va, mp.nlimbs))
    b = jnp.asarray(F.to_limbs(vb, mn.nlimbs))
    r1, r2 = jax.jit(
        lambda x, y: F.pow_fixed2(mp, x, e1, mn, y, e2)
    )(a, b)
    assert F.from_limbs(_ops(P_SECP)["canon"](r1)) == [
        pow(v, e1, P_SECP) for v in va
    ]
    assert F.from_limbs(_ops(N_SECP)["canon"](r2)) == [
        pow(v, e2, N_SECP) for v in vb
    ]


# Every ``(la, lb, out_len)`` the tree hands ``_conv`` (the fold shapes are
# checked against ``_fold_semi``'s own schedule below), and one below the
# old shear's ``out_len >= lb - 1`` floor.
CONV_SHAPES = [
    pytest.param(20, 20, 41, id="field_product"),
    pytest.param(21, 3, 25, id="fold0_p"),
    pytest.param(4, 3, 21, id="fold1_p"),
    pytest.param(21, 11, 33, id="fold0_n"),
    pytest.param(11, 11, 23, id="fold1_n"),
    pytest.param(11, 11, 11, id="glv_conv_lo"),
    pytest.param(30, 30, 61, id="bls_mul_wide"),
    pytest.param(30, 30, 59, id="bls_redc_m"),
    pytest.param(30, 30, 62, id="bls_redc_u"),
    pytest.param(20, 20, 5, id="truncated_below_lb"),
    pytest.param(1, 20, 20, id="one_limb"),
]


def _patterns(n):
    """Limb vectors at their extremes (a lazy limb may sit AT 2**13) and in
    between: all 2**13, all 0, the two alternations, a ramp, random."""
    top = F.LIMB_MASK + 1
    rng = np.random.default_rng(n)
    alt = np.arange(n) % 2
    return np.stack(
        [
            np.full(n, top),
            np.zeros(n, dtype=np.int64),
            alt * top,
            (1 - alt) * top,
            np.arange(n) * top // n,
            rng.integers(0, top + 1, n),
        ]
    ).astype(np.int32)


def _conv_ints(a, b, out_len):
    """Schoolbook columns in python ints, broadcasting like ``_conv``."""
    batch = np.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = np.broadcast_to(a.astype(object), batch + a.shape[-1:])
    b = np.broadcast_to(b.astype(object), batch + b.shape[-1:])
    out = np.zeros(batch + (out_len,), dtype=object)
    for i in range(a.shape[-1]):
        for j in range(min(b.shape[-1], out_len - i)):
            out[..., i + j] += a[..., i] * b[..., j]
    return out


CONV_OPERANDS = {
    # b batched like a: pattern against rotated pattern, lane for lane
    "b_batched": lambda pa, pb: (pa, np.roll(pb, 1, axis=0)),
    # b a constant with no batch axes (c_fold, BETA, the GLV and BLS constants)
    "b_const_top": lambda pa, pb: (pa, pb[0]),
    "b_const_random": lambda pa, pb: (pa, pb[5]),
    # a with two leading batch axes (the (16, 4) + batch tables), b with one or none
    "a_two_leading": lambda pa, pb: (np.stack([pa, pa[::-1]]), pb),
    "a_two_leading_b_const": lambda pa, pb: (np.stack([pa, pa[::-1]]), pb[3]),
}


@pytest.mark.parametrize("operands", CONV_OPERANDS)
@pytest.mark.parametrize("la,lb,out_len", CONV_SHAPES)
def test_conv_columns_exact(la, lb, out_len, operands):
    """``_conv`` against python integers, limbs on the last axis going in
    and coming out, whatever the batch axes of either operand."""
    a, b = CONV_OPERANDS[operands](_patterns(la), _patterns(lb))
    out = np.asarray(jax.jit(lambda x, y: F._conv(x, y, out_len))(a, b))
    want = _conv_ints(a, b, out_len)
    assert out.dtype == np.int32 and out.shape == want.shape
    assert (out.astype(object) == want).all()


def test_conv_truncated_value_mod_2_143():
    """The truncating mode (out_len < la+lb-1) keeps the VALUE of the low
    columns — the GLV mod-2**143 combinations depend on it."""
    rng = random.Random(11)
    a_int = [rng.randrange(2**143) for _ in range(5)]
    b_int = [rng.randrange(2**143) for _ in range(5)]
    a = jnp.asarray(F.to_limbs(a_int, 11))
    b = jnp.asarray(F.to_limbs(b_int, 11))
    out = jax.jit(lambda x, y: F._conv(x, y, 11))(a, b)
    got = F.from_limbs(np.asarray(F._exact_carry(out)) & F.LIMB_MASK)
    # _exact_carry drops the final carry out of limb 10; compare mod 2**143
    assert [g % 2**143 for g in got] == [
        (x * y) % 2**143 for x, y in zip(a_int, b_int)
    ]


@pytest.mark.parametrize("p", MODULI)
def test_conv_shapes_of_mul_are_the_tested_ones(p, monkeypatch):
    """The shapes ``mul`` and ``_fold_semi`` derive for each secp256k1
    modulus are cases of ``test_conv_columns_exact``."""
    seen = []
    real = F._conv

    def spy(a, b, out_len):
        seen.append((a.shape[-1], b.shape[-1], out_len))
        return real(a, b, out_len)

    monkeypatch.setattr(F, "_conv", spy)
    m = F.Modulus(p)
    x = jax.ShapeDtypeStruct((3, m.nlimbs), jnp.int32)
    jax.eval_shape(partial(F.mul, m), x, x)
    tested = {tuple(c.values) for c in CONV_SHAPES}
    assert len(seen) == 3 and seen[0] == (20, 20, 41) and set(seen) <= tested


def test_mul_reshapes_keep_the_batch_behind_the_product_axes():
    """From the outer product to the column sum no array carries the
    flattened ``la*w`` axis BEHIND the batch axes: the TPU keeps the batch
    lanes minor, so such a reshape costs a transposing copy of the padded
    product (a third of the recover program until PR 25).  In every
    reshape of the lowered ``mul`` the batch axes trail, but for the limb
    axis going in or out."""
    m = _ops(P_SECP)["m"]
    x = jax.ShapeDtypeStruct((4, 8, m.nlimbs), jnp.int32)
    text = jax.jit(partial(F.mul, m)).lower(x, x).as_text()
    reshapes = re.findall(
        r"stablehlo\.reshape .*?\(tensor<([0-9x]+)xi32>\) -> tensor<([0-9x]+)xi32>", text
    )
    assert reshapes, "the shear is a reshape; none found in the lowered text"
    flattened = 0
    for types in reshapes:
        for shape in types:
            dims = [int(d) for d in shape.split("x")]
            at = [i for i in range(len(dims) - 1) if dims[i : i + 2] == [4, 8]]
            if not at:  # a constant operand: no batch axes
                continue
            behind = dims[at[-1] + 2 :]
            # behind the batch: nothing, or the one limb axis going in or out
            assert len(behind) <= 1 and all(d <= 2 * m.nlimbs + 2 for d in behind), (
                f"product axis behind the batch axes: tensor<{shape}>"
            )
            flattened += any(d >= m.nlimbs**2 for d in dims)
    assert flattened >= 1  # the field product's 20 x 39 rows, merged


# ---- worst cases of the carry schedule (PR 46): an op carries only as far as
# its limb bounds ask, so its operands are held at those bounds' extremes.

WORST_OPS = {
    "mul": (2, lambda x, y: x * y),
    "sqr": (1, lambda x, y: x * x),
    "add": (2, lambda x, y: x + y),
    "sub": (2, lambda x, y: x - y),
}
MULI_KS = (1, 2, 3, 8, 16)


def _worst_rows(p):
    """``(limbs, values)`` of: the edge values of the semi-reduced range; the
    fattest semi-reduced vector (every limb 2**13 below a top limb that keeps
    the value under 2p); and, last, EVERY limb at 2**13, whose value (~2**260)
    is beyond 2p: what ``sub``'s fat K*p is sized for, and a limb-bound stress
    the other ops' value slack takes."""
    m = _ops(p)["m"]
    top = F.LIMB_MASK + 1
    every = np.full(m.nlimbs, top, dtype=np.int32)
    fattest = every.copy()
    low = F.from_limbs(every[:-1])[0]
    fattest[-1] = (2 * p - 1 - low) >> (F.LIMB_BITS * (m.nlimbs - 1))
    rows = np.concatenate(
        [F.to_limbs([0, 1, p - 1, p, p + 1, 2 * p - 1], m.nlimbs), [fattest, every]]
    )
    vals = F.from_limbs(rows)
    assert vals[-2] < 2 * p <= vals[-1]
    return rows, vals


def _worst_pairs(p):
    """Every row of :func:`_worst_rows` against every row, itself among them."""
    rows, vals = _worst_rows(p)
    n = len(rows)
    i, j = np.repeat(np.arange(n), n), np.tile(np.arange(n), n)
    return rows[i], rows[j], [vals[k] for k in i], [vals[k] for k in j]


def _assert_edge_invariant(out, p, want):
    arr = np.asarray(out)
    assert arr.min() >= 0 and arr.max() <= 1 << F.LIMB_BITS
    got = F.from_limbs(arr)
    assert all(v < 2 * p for v in got)
    assert [v % p for v in got] == [w % p for w in want]


@pytest.mark.parametrize("p", MODULI)
@pytest.mark.parametrize("op", WORST_OPS)
def test_worst_case_operands(p, op):
    nargs, ref = WORST_OPS[op]
    a, b, a_int, b_int = _worst_pairs(p)
    out = _ops(p)[op](*(jnp.asarray(a), jnp.asarray(b))[:nargs])
    _assert_edge_invariant(out, p, [ref(x, y) for x, y in zip(a_int, b_int)])


@pytest.mark.parametrize("p", MODULI)
@pytest.mark.parametrize("k", MULI_KS)
def test_worst_case_operands_muli(p, k):
    rows, vals = _worst_rows(p)
    out = _ops(p)["muli"][k](jnp.asarray(rows))
    _assert_edge_invariant(out, p, [x * k for x in vals])


@pytest.mark.parametrize("p", MODULI)
def test_chain_of_fifty_mixed_ops(p):
    """50 ops, each fed by the ones before it: every op's output is the next
    one's operand, so an output off the edge invariant shows downstream."""
    m = _ops(p)["m"]
    rng = random.Random(46)
    rows, vals = _worst_rows(p)
    # without the all-2**13 row: a chain is held to the contract (< 2p)
    a_int = vals[:-1] + [rng.randrange(2 * p) for _ in range(16)]
    a = np.concatenate([rows[:-1], F.to_limbs(a_int[len(rows) - 1 :], m.nlimbs)])
    ops = ["mul", "sqr", "add", "sub", "muli"]
    plan = [(rng.choice(ops), rng.choice(MULI_KS)) for _ in range(50)]
    assert set(ops) == {op for op, _ in plan}

    @jax.jit
    def chain(x):
        acc, prev, outs = x, x, []
        for op, k in plan:
            nxt = {
                "mul": lambda: F.mul(m, acc, prev),
                "sqr": lambda: F.sqr(m, acc),
                "add": lambda: F.add(m, acc, prev),
                "sub": lambda: F.sub(m, prev, acc),
                "muli": lambda: F.muli(m, acc, k),
            }[op]()
            acc, prev = nxt, acc
            outs.append(nxt)
        return jnp.stack(outs)

    outs = np.asarray(chain(jnp.asarray(a)))
    acc_int, prev_int = a_int, a_int
    for (op, k), out in zip(plan, outs):
        nxt = [
            {"mul": x * y, "sqr": x * x, "add": x + y, "sub": y - x, "muli": x * k}[op] % p
            for x, y in zip(acc_int, prev_int)
        ]
        acc_int, prev_int = nxt, acc_int
        _assert_edge_invariant(out, p, nxt)


# The carry schedule has no constant of P or N in it: for any modulus that
# ``Modulus`` accepts it is derived from that modulus' own fold constants.
OTHER_MODULI = {
    "2**61-1": 2**61 - 1,
    "goldilocks": 2**64 - 2**32 + 1,
    "2**127-1": 2**127 - 1,
    "p192": 2**192 - 2**64 - 1,
    "p224": 2**224 - 2**96 + 1,
    "25519": 2**255 - 19,
    "p384": 2**384 - 2**128 - 2**96 + 2**32 - 1,
}


@pytest.mark.parametrize("name", OTHER_MODULI)
def test_schedule_derives_for_other_moduli(name):
    p = OTHER_MODULI[name]
    m = F.Modulus(p)
    rng = random.Random(p & 0xFFFF)
    a_int = [0, 1, p - 1, p, p + 1, 2 * p - 1] + [rng.randrange(2 * p) for _ in range(26)]
    b_int = a_int[::-1]
    a = jnp.asarray(F.to_limbs(a_int, m.nlimbs))
    b = jnp.asarray(F.to_limbs(b_int, m.nlimbs))
    for fn, ref in (
        (lambda x, y: F.mul(m, x, y), lambda x, y: x * y),
        (lambda x, y: F.add(m, x, y), lambda x, y: x + y),
        (lambda x, y: F.sub(m, x, y), lambda x, y: x - y),
        (lambda x, y: F.muli(m, x, 16), lambda x, y: 16 * x),
    ):
        _assert_edge_invariant(jax.jit(fn)(a, b), p, [ref(x, y) for x, y in zip(a_int, b_int)])
