"""The one-kernel ladder (``ops/pallas_ladder.py``), on the CPU and quick.

The kernel's body is plain ``jnp`` on limb-leading slabs, so its field ops
run as they are against Python integers and against ``ops/fields.py`` (limb
for limb: same schedule, same passes); two ladder steps run through the real
``pallas_call`` in interpret mode against the scan's body; the dispatch rule
and the SET-UP (what refused PR 47: the seconds every process pays to trace
and lower the body) are read off a lowering for the TPU platform, which needs
no chip.  The whole kernel against the whole scan is the chip tier's
(``tests/test_secp256k1.py``, ``GO_IBFT_TPU_TESTS=1``).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_ibft_tpu.crypto import ecdsa as host
from go_ibft_tpu.ops import fields as F
from go_ibft_tpu.ops import pallas_ladder as pk
from go_ibft_tpu.ops import secp256k1 as sec

import pallas_cases as pc

P, L = sec.P, sec.FIELD.nlimbs
ROWS, LANES = 8, 128
B = ROWS * LANES  # elements a tile


def _loose(v: int, rng) -> np.ndarray:
    """Limbs of ``v`` with some limbs at the edge's inclusive 2**13 (a lazy
    carry's leftover): limb i + 2**13, limb i+1 - 1, where that stays >= 0."""
    limbs = F.to_limbs([v], L)[0].astype(np.int64)
    for i in range(L - 1):
        if limbs[i] == 0 and limbs[i + 1] > 0 and rng.random() < 0.7:
            limbs[i], limbs[i + 1] = 1 << 13, limbs[i + 1] - 1
    return limbs.astype(np.int32)


# Every limb but the top at 2**13, the value just under 2p.
_ALL_EDGE = sum((1 << 13) << (13 * i) for i in range(L - 1)) + (1022 << (13 * (L - 1)))
EDGES = [0, 1, 2, P - 1, P, P + 1, 2 * P - 1, 2 * P - 2, (1 << 256) - 1, 1 << 255, 1 << 247, _ALL_EDGE]
assert _ALL_EDGE < 2 * P


def _operands(seed):
    rng = random.Random(seed)
    vals = [EDGES[i % len(EDGES)] if i < 4 * len(EDGES) else rng.randrange(2 * P) for i in range(B)]
    rng.shuffle(vals)
    limbs = np.stack([_loose(v, rng) for v in vals])  # (B, L)
    assert F.from_limbs(limbs) == vals and limbs.max() == 1 << 13
    return vals, jnp.asarray(limbs), jnp.asarray(np.moveaxis(limbs.reshape(ROWS, LANES, L), -1, 0))


@pytest.fixture(scope="module")
def slabs():
    return pk.SlabField(sec.FIELD), _operands(1), _operands(2)


FIELD_OPS = {
    "mul": (lambda f, a, b: f.mul(a, b), lambda a, b: F.mul(sec.FIELD, a, b), lambda x, y: x * y),
    "sqr": (lambda f, a, b: f.sqr(a), lambda a, b: F.sqr(sec.FIELD, a), lambda x, y: x * x),
    "add": (lambda f, a, b: f.add(a, b), lambda a, b: F.add(sec.FIELD, a, b), lambda x, y: x + y),
    "sub": (lambda f, a, b: f.sub(a, b), lambda a, b: F.sub(sec.FIELD, a, b), lambda x, y: x - y),
    "muli2": (lambda f, a, b: f.muli(a, 2), lambda a, b: F.muli(sec.FIELD, a, 2), lambda x, y: 2 * x),
    "muli3": (lambda f, a, b: f.muli(a, 3), lambda a, b: F.muli(sec.FIELD, a, 3), lambda x, y: 3 * x),
    "muli8": (lambda f, a, b: f.muli(a, 8), lambda a, b: F.muli(sec.FIELD, a, 8), lambda x, y: 8 * x),
}


@pytest.mark.parametrize("op", FIELD_OPS)
def test_slab_field_op_against_integers_and_against_fields(slabs, op):
    f, (av, al, a), (bv, bl, b) = slabs
    slab_op, limb_op, int_op = FIELD_OPS[op]
    got = np.moveaxis(np.asarray(slab_op(f, a, b)), 0, -1).reshape(B, L)
    assert got.min() >= 0 and got.max() <= 1 << 13  # the edge invariant
    ints = F.from_limbs(got)
    assert all(g < 2 * P and g % P == int_op(x, y) % P for g, x, y in zip(ints, av, bv))
    assert np.array_equal(got, np.asarray(limb_op(al, bl)))  # same schedule: same limbs


def test_slab_product_of_a_stack_is_the_products_of_its_elements(slabs):
    f, (_, _, a), (_, _, b) = slabs
    both = f.mul(jnp.stack([a, b, a]), jnp.stack([b, b, a]))
    for got, want in zip(both, (f.mul(a, b), f.sqr(b), f.sqr(a))):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_slab_zero_test_is_fields_zero_test(slabs):
    f, (av, al, a), _ = slabs
    got = np.asarray(f.is_zero(a)).reshape(B)
    assert np.array_equal(got, np.asarray(F.is_zero_fast(sec.FIELD, al)))
    assert got.tolist() == [v % P == 0 for v in av] and 0 < got.sum() < B


# ---- one tile through the real pallas_call (interpret mode), two steps ----

STEPS = 2


@pytest.fixture(scope="module")
def tile():
    """Per-lane tables ``T[d] = d*B`` of four base points in Jacobian form
    with random ``z`` (``T[0]`` infinity), digits heavy on 0 and 15, and a
    sign a lane.  Step 1 starts from infinity in every lane; step 2 finds
    it there still where step 1's digit was 0."""
    rng = random.Random(48)
    bases = [host.scalar_mul(rng.randrange(1, sec.N), (sec.GX, sec.GY)) for _ in range(4)]
    tabs = []
    for q in bases:
        pts = [None]
        for _ in range(15):
            pts.append(host._add(pts[-1], q))
        tabs.append(pts)
    base_of = [rng.randrange(4) for _ in range(B)]

    def jac(pt):
        if pt is None:
            return 1, 1, 0
        z = rng.randrange(1, P)
        return pt[0] * z * z % P, pt[1] * z**3 % P, z

    table = np.zeros((3, 16, B, L), np.int32)
    for d in range(16):
        coords = [jac(tabs[base_of[i]][d]) for i in range(B)]
        for c in range(3):
            table[c, d] = F.to_limbs([xyz[c] for xyz in coords], L)
    digits = np.array(
        [[rng.choice((0, 15, rng.randrange(16))) for _ in range(B)] for _ in range(STEPS)], np.int32
    )
    digits[0, : B // 8] = 0
    neg = np.array([rng.randrange(2) for _ in range(B)], np.int32)
    want = []
    for i in range(B):
        acc = None
        for s in range(STEPS):
            t = tabs[base_of[i]][digits[s, i]]
            if t is not None and neg[i]:
                t = (t[0], P - t[1])
            acc = host._add(host.scalar_mul(16, acc) if acc else None, t)
        want.append(acc)
    folded = tuple(jnp.asarray(table[c].reshape(16, ROWS, LANES, L)) for c in range(3))
    return (
        jnp.asarray(digits.reshape(STEPS, ROWS, LANES)),
        jnp.asarray(neg.reshape(ROWS, LANES) != 0),
        folded,
        want,
    )


@pytest.fixture(scope="module")
def stepped(tile):
    digits, neg, (tx, ty, tz), _ = tile
    limbs_first = tuple(jnp.moveaxis(t, -1, 1) for t in (tx, ty, tz))
    acc = pk.ladder_call(digits, neg.astype(jnp.int32), *limbs_first, field=sec.FIELD, interpret=True)
    return [np.moveaxis(np.asarray(acc[c]), 0, -1) for c in range(3)]


def test_two_kernel_steps_equal_the_scan_body_limb_for_limb(tile, stepped):
    digits, neg, (tx, ty, tz), _ = tile

    def body(acc, d):  # ecmul2_base's, on this tile
        acc = sec._double4(acc)
        y = sec._one_hot_select(d, ty)
        y = F.select(neg, F.sub(sec.FIELD, jnp.zeros_like(y), y), y)
        addend = sec.JacobianPoint(sec._one_hot_select(d, tx), y, sec._one_hot_select(d, tz))
        return sec._point_add_core(acc, addend), None

    want, _ = jax.jit(lambda d: jax.lax.scan(body, sec.point_infinity((ROWS, LANES)), d))(digits)
    for got, ref in zip(stepped, want):
        assert np.array_equal(got, np.asarray(ref))


def test_two_kernel_steps_against_integers(tile, stepped):
    digits, _, _, want = tile
    x, y, z = (F.from_limbs(c.reshape(B, L)) for c in stepped)
    d = np.asarray(digits).reshape(STEPS, B)
    seen = set()
    for i in range(B):
        if want[i] is None:
            assert z[i] % P == 0
        else:
            zi = pow(z[i], -1, P)
            assert (x[i] * zi * zi % P, y[i] * zi**3 % P) == want[i]
        seen.add((int(d[0, i]) == 0, int(d[1, i]) in (0, 15)))
    assert len(seen) == 4 and want.count(None) > 0  # infinity met, and left


# ---- the fixed-exponent powers (``lift_x_pow``, ``to_affine_inv``) -----------
#
# The body keeps its window table in a VMEM scratch it indexes by the public
# digit, so "plain jax" is the real ``pallas_call`` in interpret mode: the
# same jaxpr on XLA:CPU, a whole tile at the three real exponents.

N = sec.N
SQRT, RINV, ZINV = (sec.FIELD, sec._SQRT_EXP), (sec.ORDER, N - 2), (sec.FIELD, P - 2)


def _elements(m: F.Modulus, count: int, seed: int):
    """Semi-reduced elements mod ``m`` as integers and ``(count, L)`` limbs,
    loose limbs among them: the modulus's edges, then random ones."""
    rng = random.Random(seed)
    edges = [0, 1, 2, m.p - 1, m.p, m.p + 1, 2 * m.p - 1, (1 << 256) - 1]
    vals = (edges + [rng.randrange(2 * m.p) for _ in range(count)])[:count]
    return vals, jnp.asarray(np.stack([_loose(v, rng) for v in vals]))


@pytest.fixture
def interpreted(monkeypatch):
    """``fixed_powers`` through the real ``pallas_call``, interpreted."""
    import functools

    monkeypatch.setattr(pk, "power_call", functools.partial(pk.power_call, interpret=True))


POWER_CASES = {
    "sqrt": ("lift_x_pow", (SQRT,)),
    "r_inverse": ("lift_x_pow", (RINV,)),
    "z_inverse": ("to_affine_inv", (ZINV,)),
    "sqrt_and_r_inverse": ("lift_x_pow", (SQRT, RINV)),
}


@pytest.mark.parametrize("case", POWER_CASES)
def test_power_kernel_at_the_real_exponents_against_integers_and_the_scan(interpreted, case):
    """A whole tile, every chain's accumulator limb for limb what
    ``fields.pow_fixed`` (one chain) / ``pow_fixed2`` (two) leaves."""
    name, chains = POWER_CASES[case]
    vals, limbs = zip(*[_elements(m, B, seed) for seed, (m, _) in enumerate(chains)])
    got = pk.fixed_powers(name, *[(m, a, e) for (m, e), a in zip(chains, limbs)])
    if len(chains) == 1:
        want = (jax.jit(lambda a: F.pow_fixed(chains[0][0], a, chains[0][1]))(*limbs),)
    else:
        (m1, e1), (m2, e2) = chains
        want = jax.jit(lambda a, b: F.pow_fixed2(m1, a, e1, m2, b, e2))(*limbs)
    for (m, e), g, w, v in zip(chains, got, want, vals):
        g = np.asarray(g)
        assert g.min() >= 0 and g.max() <= 1 << 13
        assert np.array_equal(g, np.asarray(w))
        assert [x % m.p for x in F.from_limbs(g)] == [pow(x, e, m.p) for x in v]


SHORT = 0xA07  # three windows, a zero digit among them


@pytest.mark.parametrize("shape", [(300,), (2, 640), (1,), (1024,)], ids=str)
def test_fixed_powers_pads_any_batch_to_whole_tiles_and_slices_it_back(interpreted, shape):
    lanes = int(np.prod(shape))
    (av, a), (bv, b) = _elements(sec.FIELD, lanes, 3), _elements(sec.ORDER, lanes, 4)
    got = pk.fixed_powers(
        "lift_x_pow", (sec.FIELD, a.reshape(shape + (L,)), SHORT), (sec.ORDER, b.reshape(shape + (L,)), SHORT + 1)
    )
    want = F.pow_fixed2(sec.FIELD, a, SHORT, sec.ORDER, b, SHORT + 1)
    for g, w in zip(got, want):
        assert g.shape == shape + (L,)
        assert np.array_equal(np.asarray(g).reshape(lanes, L), np.asarray(w))


def test_zero_goes_to_zero_and_a_padded_tile_holds_zeros_in_its_pad():
    """One tile and a half of lanes, as ``fixed_powers`` pads them: the pad
    lanes are zeros going in, and ``0 ** e`` is the canonical zero coming out
    (which is how ``to_affine`` sends infinity to ``(0, 0)``)."""
    lanes = B + B // 2
    vals, limbs = _elements(sec.FIELD, lanes, 5)
    a = jnp.pad(limbs, ((0, 2 * B - lanes), (0, 0)))
    a = jnp.moveaxis(a.reshape(1, 2 * ROWS, LANES, L), -1, 1)
    out = pk.power_call(a, moduli=(sec.FIELD,), exponents=(SHORT,), name="to_affine_inv", interpret=True)
    got = np.moveaxis(np.asarray(out), 1, -1).reshape(2 * B, L)
    assert not got[lanes:].any() and not got[0].any() and vals[0] == 0
    assert [g % P for g in F.from_limbs(got[:lanes])] == [pow(v, SHORT, P) for v in vals]


@pytest.mark.parametrize("lanes", (256, 1024))
def test_per_lane_inversion_is_the_product_trees_after_canon(interpreted, lanes):
    """``to_affine``'s two inversions, the kernel's in every lane and
    ``fields.batch_inv``'s tree: the same canonical limbs, ``inv(0) == 0``."""
    vals, z = _elements(sec.FIELD, lanes, 6)
    (got,) = pk.fixed_powers("to_affine_inv", (sec.FIELD, z, P - 2))
    canon = jax.jit(lambda a: F.canon(sec.FIELD, a))
    got, want = np.asarray(canon(got)), np.asarray(canon(F.batch_inv(sec.FIELD, z)))
    assert np.array_equal(got, want)
    assert F.from_limbs(got) == [pow(v, P - 2, P) for v in vals] and vals.count(0) == 1


# ---- the dispatch rule ------------------------------------------------------


@pytest.mark.parametrize("lanes", (8, 32, 128, 256, 512, 1024, 2048))
def test_every_program_keeps_the_scan_off_the_tpu(lanes):
    assert pk.kind(lanes) == pk.SCAN


@pytest.mark.parametrize(
    "lanes,want",
    [(8, pk.SCAN), (32, pk.SCAN), (128, pk.SCAN), (256, pk.PALLAS), (512, pk.PALLAS), (1024, pk.PALLAS), (2048, pk.PALLAS), (384, pk.SCAN)],
)
def test_on_a_tpu_the_folded_programs_take_the_kernel(monkeypatch, lanes, want):
    monkeypatch.setattr(pk, "supported", lambda: True)
    assert pk.kind(lanes) == want


def test_under_a_mapped_axis_the_scan_stays(monkeypatch):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec

    monkeypatch.setattr(pk, "supported", lambda: True)
    seen = []
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

    def per_shard(x):
        seen.append(pk.kind(512))
        return x

    spec = PartitionSpec("dp")
    jax.eval_shape(
        shard_map(per_shard, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False),
        jax.ShapeDtypeStruct((1024,), jnp.int32),
    )
    assert seen == [pk.SCAN] and pk.kind(512) == pk.PALLAS


def test_importing_the_package_imports_no_pallas():
    import subprocess
    import sys

    code = (
        "import sys, go_ibft_tpu.verify.batch, go_ibft_tpu.ops.pallas_ladder;"
        "assert not [m for m in sys.modules if m.startswith('jax.experimental.pallas')]"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# ---- the folded program, lowered for the TPU platform: the set-up ------------


@pytest.fixture(scope="module")
def folded():
    with pc.traced_for_tpu():
        return pc.Lowered(256)


@pytest.mark.parametrize("lanes", (8, 32, 128))
def test_unfolded_programs_lower_with_no_custom_call(lanes):
    with pc.traced_for_tpu():
        assert "tpu_custom_call" not in pc.Lowered(lanes).text


KERNEL_SCOPES = {
    "lift_x_pow": "recover.lift_x/lift_x_pow",
    "glv_ladder": "recover.glv_ladder/glv_ladder",
    "to_affine_inv": "recover.to_affine/to_affine_inv",
}


@pytest.mark.parametrize("kernel", KERNEL_SCOPES)
def test_three_custom_calls_each_under_its_own_scope(folded, kernel):
    calls = [line for line in folded.text.splitlines() if "@tpu_custom_call" in line]
    assert len(calls) == 3 == len(folded.kernels)
    (call,) = [line for line in calls if f'kernel_name = "{kernel}"' in line]
    (loc,) = [line for line in folded.text.splitlines() if line.startswith(call.rsplit("loc(", 1)[1][:-1] + " ")]
    assert f"{kernel}/pallas_call" in loc
    # The name stack down to the call, through ``to_affine``'s nested jit: the
    # ``op_name`` XLA gives the custom call, which ``stage_reduce`` reads.
    assert folded.kernel_scopes[kernel] == KERNEL_SCOPES[kernel]


def test_no_ladder_steps_and_no_power_windows_outside_the_kernels(folded):
    """The 33 steps and the chains' 63 windows are in the kernels and nowhere
    else: the XLA scans are gone from the folded program."""
    inside = sum((pc.scans(k) for k in folded.kernels.values()), [])
    by_length = lambda es, n: [e for e in es if e.params["length"] == n]  # noqa: E731
    for length, kernels in ((sec._GLV_NWIN, 1), (63, 2)):
        assert len(by_length(pc.scans(folded.jaxpr), length)) == len(by_length(inside, length)) == kernels


@pytest.mark.parametrize("kernel,chains", [("lift_x_pow", 2), ("to_affine_inv", 1)])
def test_power_kernel_is_329_products_a_chain_in_its_own_terms(folded, kernel, chains):
    """14 to build the table, 63 windows of 4 squarings and one multiply:
    ``pow_fixed2``'s count; three product CALLS a chain are traced."""
    body = folded.kernels[kernel]
    assert pc.slab_products(body) == chains * (14 + 63 * 5)
    assert pc.slab_products(body, sites=True) == chains * 3


def test_kernel_step_is_44_products_in_its_own_terms(folded):
    """A product call is one outer-product multiply, ``k`` products where
    ``k`` operands are stacked: 4 doublings x (3 + 3 + 1) + one add's
    2 + 5 + 2 + 3 + 2 + 2."""
    (step,) = [e for e in pc.scans(folded.kernels["glv_ladder"]) if e.params["length"] == sec._GLV_NWIN]
    body = step.params["jaxpr"].jaxpr
    assert pc.slab_products(body) == 4 * 7 + 16
    sites = pc.slab_products(body, sites=True)
    assert sites == 3 + 6, sites  # product CALLS traced: what set-up pays for


# PR 48's readings on this host (jax 0.9.0): 1,504 equations, a 2,014,482-byte
# module (the parent's scan program: 1,970,876), of which the kernel is one
# line of some 74,000.  Ceilings ~1.3x.  The same body written as twenty slab
# multiply-adds a product on ``jnp`` read 3,034 equations; PR 47's
# register-by-register body, at 400 Python operations a product, would read
# some 40,000.  PR 52's readings: ``lift_x_pow`` 897 equations and a line of
# 40,127, ``to_affine_inv`` 309 and 19,711, and the module SHRINKS to
# 1,661,676 bytes (two 63-window scans of multi-fusion bodies and the
# product tree's two scans left it): the module's ceiling stays where it was.
KERNEL_EQNS = {"glv_ladder": (1_000, 2_000), "lift_x_pow": (600, 1_200), "to_affine_inv": (200, 400)}
KERNEL_LINE_CEILING = 100_000
MODULE_BYTES_CEILING = 2_650_000


@pytest.mark.parametrize("kernel", KERNEL_EQNS)
def test_set_up_stays_under_its_ceilings(folded, kernel):
    eqns = pc.count_eqns(folded.kernels[kernel])
    (call,) = [
        line for line in folded.text.splitlines()
        if "@tpu_custom_call" in line and f'kernel_name = "{kernel}"' in line
    ]
    floor, ceiling = KERNEL_EQNS[kernel]
    assert floor < eqns <= ceiling, eqns
    assert len(call) <= KERNEL_LINE_CEILING, len(call)
    assert len(folded.bare_text) <= MODULE_BYTES_CEILING, len(folded.bare_text)


# ---- the chip's compiler, with no chip ---------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e (the TPU's compiler is installed here; nothing runs).
    Made inside a fixture: only one process may hold libtpu's lock."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or its lock is another process's
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_for(one_chip, call, *shapes):
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        operands = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in shapes]
        return jax.jit(call).trace(*operands).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def test_mosaic_compiles_the_kernel_for_a_v5e(one_chip):
    """What interpret mode cannot show: that Mosaic takes every op of the
    body (the merging reshapes, the sum over a leading axis, ``pad``) and
    that eight row tiles' worth (2,048 lanes) fits the VMEM it is given."""
    rows = 64
    compiled = _compile_for(
        one_chip,
        lambda *a: pk.ladder_call(*a, field=sec.FIELD),
        (33, rows, LANES), (rows, LANES), *((16, L, rows, LANES),) * 3,
    )  # fmt: skip
    assert compiled.memory_analysis().output_size_in_bytes == 3 * L * rows * LANES * 4


@pytest.mark.parametrize("tiles", (1, 2))
@pytest.mark.parametrize("kernel", ("lift_x_pow", "to_affine_inv"))
def test_mosaic_compiles_the_power_kernels_for_a_v5e(one_chip, kernel, tiles):
    """And of the powers: the table entry read and written at a dynamic
    index on the scratch's leading axis, the digits from SMEM, a table a
    chain (1.3 MB) in VMEM; one tile (up to 1,024 lanes) and two (2,048)."""
    chains = {"lift_x_pow": (SQRT, RINV), "to_affine_inv": (ZINV,)}[kernel]
    moduli, exponents = zip(*chains)
    shape = (len(chains), L, tiles * ROWS, LANES)
    compiled = _compile_for(
        one_chip, lambda a: pk.power_call(a, moduli=moduli, exponents=exponents, name=kernel), shape
    )
    assert compiled.memory_analysis().output_size_in_bytes == int(np.prod(shape)) * 4
