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


def test_one_custom_call_under_the_ladder_scope(folded):
    calls = [line for line in folded.text.splitlines() if "@tpu_custom_call" in line]
    assert len(calls) == 1 and 'kernel_name = "glv_ladder"' in calls[0]
    (loc,) = [line for line in folded.text.splitlines() if line.startswith(calls[0].rsplit("loc(", 1)[1][:-1] + " ")]
    assert "recover.glv_ladder/glv_ladder/pallas_call" in loc
    assert len(folded.kernels) == 1
    # The 33 steps are in the kernel and nowhere else: the XLA scan is gone
    # from the folded program.
    steps = lambda j: [e for e in pc.scans(j) if e.params["length"] == sec._GLV_NWIN]  # noqa: E731
    assert len(steps(folded.jaxpr)) == len(steps(folded.kernels[0])) == 1


def test_kernel_step_is_44_products_in_its_own_terms(folded):
    """A product call is one outer-product multiply, ``k`` products where
    ``k`` operands are stacked: 4 doublings x (3 + 3 + 1) + one add's
    2 + 5 + 2 + 3 + 2 + 2."""
    (step,) = [e for e in pc.scans(folded.kernels[0]) if e.params["length"] == sec._GLV_NWIN]
    body = step.params["jaxpr"].jaxpr
    assert pc.slab_products(body) == 4 * 7 + 16
    sites = pc.slab_products(body, sites=True)
    assert sites == 3 + 6, sites  # product CALLS traced: what set-up pays for


# PR 48's readings on this host (jax 0.9.0): 1,504 equations, a 2,014,482-byte
# module (the parent's scan program: 1,970,876), of which the kernel is one
# line of some 74,000.  Ceilings ~1.3x.  The same body written as twenty slab
# multiply-adds a product on ``jnp`` read 3,034 equations; PR 47's
# register-by-register body, at 400 Python operations a product, would read
# some 40,000.
KERNEL_EQNS_CEILING = 2_000
KERNEL_LINE_CEILING = 100_000
MODULE_BYTES_CEILING = 2_650_000


def test_set_up_stays_under_its_ceilings(folded):
    eqns = pc.count_eqns(folded.kernels[0])
    (call,) = [line for line in folded.text.splitlines() if "@tpu_custom_call" in line]
    assert 1_000 < eqns <= KERNEL_EQNS_CEILING, eqns
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


def test_mosaic_compiles_the_kernel_for_a_v5e(one_chip):
    """What interpret mode cannot show: that Mosaic takes every op of the
    body (the merging reshapes, the sum over a leading axis, ``pad``) and
    that eight row tiles' worth (2,048 lanes) fits the VMEM it is given."""
    rows = 64

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = (
            jax.jit(lambda *a: pk.ladder_call(*a, field=sec.FIELD))
            .trace(s(33, rows, LANES), s(rows, LANES), *(s(16, L, rows, LANES),) * 3)
            .lower(lowering_platforms=("tpu",))
            .compile()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert compiled.memory_analysis().output_size_in_bytes == 3 * L * rows * LANES * 4
