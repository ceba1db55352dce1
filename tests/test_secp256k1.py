"""Device secp256k1 kernels vs the host int oracle.

Every lane of every batched op must match :mod:`go_ibft_tpu.crypto.ecdsa`
bit-for-bit — this is the determinism requirement of SURVEY.md §7 (e):
verification results must agree across CPU/TPU backends.

Kernels compile once per process; tests share fixtures to amortize.
"""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from go_ibft_tpu.crypto import ecdsa as host
from go_ibft_tpu.crypto import keccak256
from go_ibft_tpu.ops import fields
from go_ibft_tpu.ops import secp256k1 as sec

import ladder_cases as lc

# Cold EC-ladder kernel compiles take minutes; slow tier only.
pytestmark = pytest.mark.slow

L = sec.FIELD.nlimbs


def pack(vals):
    return jnp.asarray(fields.to_limbs(list(vals), L))


@pytest.fixture(scope="module")
def points():
    rng = np.random.default_rng(7)
    ks = [int.from_bytes(rng.bytes(32), "big") % host.N for _ in range(4)]
    pts = [host.scalar_mul(k, (host.GX, host.GY)) for k in ks]
    X = pack(p[0] for p in pts)
    Y = pack(p[1] for p in pts)
    one = jnp.broadcast_to(jnp.asarray(sec.FIELD.const(1)), X.shape)
    return pts, sec.JacobianPoint(X, Y, one)


def unpack_affine(j):
    x, y = sec.to_affine(j)
    return list(zip(fields.from_limbs(x), fields.from_limbs(y)))


def test_point_double(points):
    pts, J = points
    assert unpack_affine(sec.point_double(J)) == [host._add(p, p) for p in pts]


def test_point_add_generic(points):
    pts, J = points
    J2 = sec.JacobianPoint(
        jnp.roll(J.x, 1, axis=0), jnp.roll(J.y, 1, axis=0), J.z
    )
    expected = [host._add(pts[i], pts[(i - 1) % 4]) for i in range(4)]
    assert unpack_affine(sec.point_add(J, J2)) == expected


def test_point_add_exceptional_cases(points):
    pts, J = points
    # P + P must fall back to doubling
    assert unpack_affine(sec.point_add(J, J)) == [host._add(p, p) for p in pts]
    # P + (-P) = infinity
    neg = sec.JacobianPoint(J.x, pack(host.P - p[1] for p in pts), J.z)
    assert bool(sec.is_infinity(sec.point_add(J, neg)).all())
    # P + infinity = P, both operand orders
    inf = sec.point_infinity(J.x.shape[:-1])
    assert unpack_affine(sec.point_add(J, inf)) == pts
    assert unpack_affine(sec.point_add(inf, J)) == pts


def test_on_curve(points):
    pts, J = points
    x = pack(p[0] for p in pts)
    good = pack(p[1] for p in pts)
    bad = pack((p[1] + 1) % host.P for p in pts)
    assert bool(sec.on_curve(x, good).all())
    assert not bool(sec.on_curve(x, bad).any())


def test_ecmul2_base(points):
    pts, J = points
    rng = np.random.default_rng(8)
    k1 = [int.from_bytes(rng.bytes(32), "big") % host.N for _ in range(4)]
    k2 = [int.from_bytes(rng.bytes(32), "big") % host.N for _ in range(4)]
    got = unpack_affine(sec.ecmul2_base(pack(k1), pack(k2), J.x, J.y))
    expected = [
        host._add(host.scalar_mul(a, (host.GX, host.GY)), host.scalar_mul(b, p))
        for a, b, p in zip(k1, k2, pts)
    ]
    assert got == expected


def test_ecmul2_window_scaling_regression(points):
    """The round-1 comb bug: G-table entries pre-scaled by 16^j ALSO rode
    the ladder's per-step doublings, so ecmul2_base(16, 0, G) returned
    256*G.  Scalars touching exactly one non-zero window above window 0
    pin the single-scaling invariant."""
    pts, J = points
    ks = [16, 1 << 8, 1 << 252, 0]
    got = unpack_affine(
        sec.ecmul2_base(pack(ks), pack([0, 0, 0, 1]), J.x, J.y)
    )
    expected = [
        host.scalar_mul(16, (host.GX, host.GY)),
        host.scalar_mul(1 << 8, (host.GX, host.GY)),
        host.scalar_mul(1 << 252, (host.GX, host.GY)),
        pts[3],
    ]
    assert got == expected


def test_ecmul2_zero_scalars(points):
    pts, J = points
    zeros = pack([0] * 4)
    assert bool(sec.is_infinity(sec.ecmul2_base(zeros, zeros, J.x, J.y)).all())
    # 0*G + 1*Q == Q
    ones = pack([1] * 4)
    assert unpack_affine(sec.ecmul2_base(zeros, ones, J.x, J.y)) == pts


@pytest.fixture(scope="module")
def signatures():
    keys = [host.PrivateKey.from_seed(f"key-{i}".encode()) for i in range(6)]
    digests = [keccak256(f"payload-{i}".encode()) for i in range(6)]
    sigs = [host.sign(k, d) for k, d in zip(keys, digests)]
    return keys, digests, sigs


def test_ecdsa_verify_mask(signatures):
    keys, digests, sigs = signatures
    zs = [host.digest_to_scalar(d) for d in digests]
    rs = [s[0] for s in sigs]
    ss = [s[1] for s in sigs]
    # corrupt: lane 3 wrong digest, lane 4 r=0, lane 5 s=N (out of range)
    zs[3] = (zs[3] + 1) % host.N
    rs[4] = 0
    ss[5] = host.N
    # lanes 6, 7: lane 0's good signature under a key off the curve (y + 1;
    # x + 1 keeping y), which ecmul2_base's ladder is not specified for
    qxs = [k.pubkey[0] for k in keys] + [keys[0].pubkey[0], keys[0].pubkey[0] + 1]
    qys = [k.pubkey[1] for k in keys] + [keys[0].pubkey[1] + 1, keys[0].pubkey[1]]
    assert not host.on_curve(qxs[6], qys[6]) and not host.on_curve(qxs[7], qys[7])
    ok = sec.ecdsa_verify(
        pack(qxs),
        pack(qys),
        pack(zs + [zs[0]] * 2),
        pack(rs + [rs[0]] * 2),
        pack(ss + [ss[0]] * 2),
    )
    assert list(np.asarray(ok)) == [True, True, True, False, False, False, False, False]


def test_ecdsa_recover_roundtrip(signatures):
    keys, digests, sigs = signatures
    qx, qy, ok = sec.ecdsa_recover(
        pack(host.digest_to_scalar(d) for d in digests),
        pack(s[0] for s in sigs),
        pack(s[1] for s in sigs),
        jnp.asarray([s[2] for s in sigs]),
    )
    assert bool(np.asarray(ok).all())
    got = list(zip(fields.from_limbs(qx), fields.from_limbs(qy)))
    assert got == [k.pubkey for k in keys]
    # device recovery agrees with the host recover oracle too
    for d, (r, s, v), k in zip(digests, sigs, keys):
        assert host.recover(d, r, s, v) == k.pubkey


def test_ecdsa_recover_invalid_lanes(signatures):
    keys, digests, sigs = signatures
    rs = [s[0] for s in sigs]
    ss = [s[1] for s in sigs]
    vs = [s[2] for s in sigs]
    rs[0] = 0  # out of range
    ss[1] = host.N  # out of range
    vs[2] = 5  # bad recovery id
    _, _, ok = sec.ecdsa_recover(
        pack(host.digest_to_scalar(d) for d in digests),
        pack(rs),
        pack(ss),
        jnp.asarray(vs),
    )
    assert list(np.asarray(ok)) == [False, False, False, True, True, True]


def test_keccak_vectors():
    assert (
        keccak256(b"").hex()
        == "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
    )
    assert (
        keccak256(b"abc").hex()
        == "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"
    )
    # multi-block absorb (> 136-byte rate)
    assert (
        keccak256(b"a" * 200).hex()
        == keccak256(b"a" * 100 + b"a" * 100).hex()
    )


def test_host_sign_verify_negative():
    k = host.PrivateKey.from_seed(b"seed")
    d = keccak256(b"msg")
    r, s, _v = host.sign(k, d)
    x, y = k.pubkey
    assert host.verify(x, y, d, r, s)
    assert not host.verify(x, y, keccak256(b"other"), r, s)
    assert not host.verify(x, y, d, (r + 1) % host.N, s)


# -- GLV decomposition + ladder (round 4) ------------------------------------


def test_glv_constants():
    """The endomorphism constants satisfy their defining identities, and
    (LAMBDA, BETA) is the matched pair: phi(G) = (BETA*Gx, Gy) equals
    LAMBDA*G on the curve (a swapped pair — LAMBDA vs LAMBDA^2 — passes
    the cube-root identities but breaks this)."""
    assert pow(sec._LAMBDA, 3, sec.N) == 1 and sec._LAMBDA != 1
    assert pow(sec._BETA, 3, sec.P) == 1 and sec._BETA != 1
    assert (sec._GLV_A1 + sec._GLV_B1 * sec._LAMBDA) % sec.N == 0
    assert (sec._GLV_A2 + sec._GLV_B2 * sec._LAMBDA) % sec.N == 0
    lam_g = host.scalar_mul(sec._LAMBDA, (host.GX, host.GY))
    assert lam_g == ((sec._BETA * host.GX) % sec.P, host.GY)


def test_glv_split_parity():
    """Device decomposition == the exact host rounding formula, and the
    recomposition identity k == k1 + k2*LAMBDA (mod N) holds with the
    half-scalars under 2**129."""
    rng = np.random.default_rng(11)
    ks = [int.from_bytes(rng.bytes(32), "big") % sec.N for _ in range(6)]
    ks += [1, sec.N - 1, sec._LAMBDA, (sec.N - sec._LAMBDA) % sec.N]
    a1, n1, a2, n2 = sec.glv_split(pack(ks))
    a1v, a2v = fields.from_limbs(a1), fields.from_limbs(a2)
    n1v, n2v = np.asarray(n1), np.asarray(n2)
    for i, k in enumerate(ks):
        k1, k2 = lc.glv_split_int(k)
        got1 = -a1v[i] if n1v[i] else a1v[i]
        got2 = -a2v[i] if n2v[i] else a2v[i]
        assert (got1, got2) == (k1, k2), hex(k)
        assert (got1 + got2 * sec._LAMBDA) % sec.N == k
        assert abs(got1) < 1 << 129 and abs(got2) < 1 << 129


def test_glv_ladder_matches_shamir_oracle(points):
    """The GLV ladder and the pre-GLV Shamir ladder (independent code
    paths: no shared decomposition) agree lane-for-lane on random double
    scalars."""
    pts, J = points
    rng = np.random.default_rng(12)
    k1 = [int.from_bytes(rng.bytes(32), "big") % host.N for _ in range(4)]
    k2 = [int.from_bytes(rng.bytes(32), "big") % host.N for _ in range(4)]
    glv = unpack_affine(sec.ecmul2_base(pack(k1), pack(k2), J.x, J.y))
    shamir = unpack_affine(sec._ecmul2_base_shamir(pack(k1), pack(k2), J.x, J.y))
    assert glv == shamir


def test_glv_ladder_negative_half_scalar_edges(points):
    """Scalars engineered so one or both half-scalars come out negative
    (LAMBDA and N-LAMBDA decompose to (0, +-1)-shaped splits) exercise the
    gather-time point negation."""
    pts, J = points
    ks = [sec._LAMBDA, (sec.N - sec._LAMBDA) % sec.N, sec.N - 1, 2]
    got = unpack_affine(sec.ecmul2_base(pack(ks), pack([0, 0, 0, 0]), J.x, J.y))
    expected = [host.scalar_mul(k, (host.GX, host.GY)) for k in ks]
    assert got == expected
    got_q = unpack_affine(sec.ecmul2_base(pack([0] * 4), pack(ks), J.x, J.y))
    expected_q = [host.scalar_mul(k, p) for k, p in zip(ks, pts)]
    assert got_q == expected_q


# -- the incomplete ladder add (PR 28) ----------------------------------------
#
# ecmul2_base's scan body adds without the P == Q fallback
# (tests/test_ladder_structure.py has the count and the invariant).  Here the
# COMPILED ladder meets Python integers and the Shamir ladder (one
# accumulator, complete adds, no decomposition shared) on the scalars and
# points of ladder_cases.py, and ecdsa_recover meets the host oracle lane for
# lane.  With GO_IBFT_TPU_TESTS=1 on a TPU the same lanes also run tiled to
# the sync path's 2,048: exactness on XLA:CPU is not exactness on the chip
# (PERF.md section 6, PR 25).

ECMUL2_CASES = lc.ecmul2_cases()
# ecmul2_base folds its (4, lanes) ladder batch into (lanes // 32, 128) where
# lanes % 256 == 0 (PR 30): 128 is the PREPARE flood's shape and is not
# folded, 512 is the smallest lane bucket that is.
LADDER_LANES = (128, 512)
# On the chip only, tile by tile: the joint COMMIT dispatch's widths at 100
# and 300 validators (256: one full (8, 128) tile; 1,024: PR 32) and the
# sync path's 2,048.
CHIP_LANES = (256, 1024, 2048)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _tile(values, lanes):
    values = list(values)
    return [values[i % len(values)] for i in range(lanes)]


def _ecmul2_affine(fn, lanes):
    """ECMUL2_CASES, tiled up to whole dispatches of ``lanes``, through
    ``fn``: lane ``i`` holds case ``i % len(ECMUL2_CASES)`` as an affine
    point or None."""
    cases = _tile(ECMUL2_CASES, -(-len(ECMUL2_CASES) // lanes) * lanes)
    out = []
    for at in range(0, len(cases), lanes):
        k1, k2, q = zip(*[(c[1], c[2], c[3]) for c in cases[at : at + lanes]])
        j = fn(pack(k1), pack(k2), pack(p[0] for p in q), pack(p[1] for p in q))
        inf = list(np.asarray(sec.is_infinity(j)))
        out += [None if i else xy for i, xy in zip(inf, unpack_affine(j))]
    return out


@pytest.fixture(scope="module", params=LADDER_LANES, ids=lambda n: f"{n}l")
def glv_lanes(request):
    return _ecmul2_affine(sec.ecmul2_base, request.param)


@pytest.fixture(scope="module")
def shamir_lanes():
    return _ecmul2_affine(sec._ecmul2_base_shamir, 256)


@pytest.mark.parametrize(
    "lane", range(len(ECMUL2_CASES)), ids=[c[0] for c in ECMUL2_CASES]
)
def test_glv_ladder_edge_case(glv_lanes, shamir_lanes, lane):
    _, k1, k2, q = ECMUL2_CASES[lane]
    want = lc.expected_point(k1, k2, q)
    for got in (glv_lanes, shamir_lanes):  # every tiled copy of the case
        copies = got[lane :: len(ECMUL2_CASES)]
        assert copies == [want] * len(copies)


def test_glv_ladder_edge_cases_cover_what_they_name():
    ids = [c[0] for c in ECMUL2_CASES]
    assert len(set(ids)) == len(ids)
    wants = {c[0]: lc.expected_point(*c[1:]) for c in ECMUL2_CASES if ":" in c[0]}
    assert all(w is None for n, w in wants.items() if n.startswith("inf:"))
    assert all(w is not None for n, w in wants.items() if n.startswith("dbl:"))
    for n, (_, k1, k2, q) in zip(ids, ECMUL2_CASES):
        if n.startswith("dbl:"):  # the G half and the Q half are one point
            assert host.scalar_mul(k1, lc.G) == host.scalar_mul(k2, q)


def _ladder_operands(lanes):
    """ECMUL2_CASES tiled to ``lanes``, then random scalars on random points
    where the tiling would only repeat itself."""
    rng = random.Random(lanes)
    cases = [(c[1], c[2], c[3]) for c in ECMUL2_CASES[:lanes]]
    while len(cases) < lanes:
        q = host.scalar_mul(rng.randrange(1, sec.N), lc.G)
        cases.append((rng.randrange(sec.N), rng.randrange(sec.N), q))
    k1, k2, q = zip(*cases)
    return pack(k1), pack(k2), pack(p[0] for p in q), pack(p[1] for p in q)


@pytest.mark.parametrize("lanes", (256, 512, 1024, 2048), ids=lambda n: f"{n}l")
def test_pallas_ladder_equals_the_scan_lane_by_lane_on_the_chip(lanes, monkeypatch):
    """The whole kernel (``ops/pallas_ladder.py``: the path every folded
    program takes on a TPU) against the scan it replaces, on the same
    operands: the Jacobian accumulator after ``recover.combine``, limb for
    limb, in every lane."""
    if not _on_tpu():
        pytest.skip(f"{lanes} lanes: on the chip only (GO_IBFT_TPU_TESTS=1)")
    from go_ibft_tpu.ops import pallas_ladder

    args = _ladder_operands(lanes)
    assert pallas_ladder.kind(lanes) == pallas_ladder.PALLAS
    text = sec.ecmul2_base.lower(*args).as_text()
    assert text.count("tpu_custom_call") == 1
    got = [np.asarray(c) for c in sec.ecmul2_base(*args)]
    try:
        monkeypatch.setattr(pallas_ladder, "supported", lambda: False)
        jax.clear_caches()  # the traces above chose their ladder
        assert "tpu_custom_call" not in sec.ecmul2_base.lower(*args).as_text()
        want = [np.asarray(c) for c in sec.ecmul2_base(*args)]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert int(np.asarray(sec.is_infinity(sec.JacobianPoint(*map(jnp.asarray, got)))).sum()) < lanes // 4


def _power_operands(lanes, modulus):
    """Semi-reduced elements mod ``modulus``: its edges, then random ones."""
    rng = random.Random(lanes + modulus % 97)
    edges = [0, 1, 2, modulus - 1, modulus, modulus + 1, 2 * modulus - 1, (1 << 256) - 1]
    return (edges + [rng.randrange(2 * modulus) for _ in range(lanes)])[:lanes]


@pytest.mark.parametrize("lanes", (256, 512, 1024, 2048), ids=lambda n: f"{n}l")
def test_pallas_powers_equal_the_scans_lane_by_lane_on_the_chip(lanes, monkeypatch):
    """The two power kernels (``pallas_ladder.fixed_powers``: the path every
    folded program takes on a TPU) against the scans they replace: the two
    accumulators of ``recover.lift_x`` limb for limb what
    ``fields.pow_fixed2`` gives and the powers Python's, in every lane;
    ``to_affine`` bit for bit the product tree's, infinity to ``(0, 0)``."""
    if not _on_tpu():
        pytest.skip(f"{lanes} lanes: on the chip only (GO_IBFT_TPU_TESTS=1)")
    from go_ibft_tpu.ops import pallas_ladder

    assert pallas_ladder.kind(lanes) == pallas_ladder.PALLAS
    f, o = sec.FIELD, sec.ORDER
    av, bv = _power_operands(lanes, sec.P), _power_operands(lanes, sec.N)
    a, b = pack(av), pack(bv)
    chains = ((f, sec._SQRT_EXP), (o, sec.N - 2))
    got = jax.jit(
        lambda a, b: pallas_ladder.fixed_powers("lift_x_pow", (f, a, chains[0][1]), (o, b, chains[1][1]))
    )(a, b)
    want = jax.jit(lambda a, b: fields.pow_fixed2(f, a, chains[0][1], o, b, chains[1][1]))(a, b)
    for g, w, vals, (m, e) in zip(got, want, (av, bv), chains):
        assert np.array_equal(np.asarray(g), np.asarray(w))
        assert [v % m.p for v in fields.from_limbs(np.asarray(g))] == [pow(v, e, m.p) for v in vals]

    rng = random.Random(lanes)
    pts = [host.scalar_mul(rng.randrange(1, sec.N), lc.G) for _ in range(16)]
    zs = [0 if i % 7 == 3 else rng.randrange(1, sec.P) for i in range(lanes)]
    jac = sec.JacobianPoint(
        pack(pts[i % 16][0] * z * z % sec.P for i, z in enumerate(zs)),
        pack(pts[i % 16][1] * z**3 % sec.P for i, z in enumerate(zs)),
        pack(zs),
    )
    assert sec.to_affine.lower(jac).as_text().count("tpu_custom_call") == 1
    got = [np.asarray(c) for c in sec.to_affine(jac)]
    try:
        monkeypatch.setattr(pallas_ladder, "supported", lambda: False)
        jax.clear_caches()  # the trace above chose its inversion
        assert "tpu_custom_call" not in sec.to_affine.lower(jac).as_text()
        want = [np.asarray(c) for c in sec.to_affine(jac)]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    xy = list(zip(fields.from_limbs(got[0]), fields.from_limbs(got[1])))
    assert xy == [(0, 0) if z == 0 else pts[i % 16] for i, z in enumerate(zs)]


@pytest.mark.parametrize("lanes", CHIP_LANES, ids=lambda n: f"{n}l")
def test_glv_ladder_edge_cases_at_wide_lanes_on_the_chip(lanes):
    if not _on_tpu():
        pytest.skip(f"{lanes} lanes: on the chip only (GO_IBFT_TPU_TESTS=1)")
    glv = _ecmul2_affine(sec.ecmul2_base, lanes)
    want = [lc.expected_point(*c[1:]) for c in ECMUL2_CASES]
    assert glv == _tile(want, len(glv))


def _non_residue_x():
    x = 2
    while pow((x**3 + 7) % host.P, (host.P - 1) // 2, host.P) == 1:
        x += 1
    return x


def _recover_lanes():
    """``(category, digest, r, s, v)`` for 128 lanes."""
    rng = random.Random(2828)
    keys = [host.PrivateKey.from_seed(f"pr28-{i}".encode()) for i in range(16)]
    lanes = []

    def signed(i):
        d = keccak256(f"pr28-payload-{i}".encode())
        return (d,) + tuple(host.sign(keys[i % len(keys)], d))

    for i in range(72):
        lanes.append(("valid",) + signed(i))
    for i in range(72, 80):  # recovers, to another key than the signer's
        d, r, s, v = signed(i)
        lanes.append(("wrong_digest", keccak256(d), r, s, v))
    for i in range(80, 84):
        d, r, s, v = signed(i)
        lanes.append(("wrong_parity", d, r, s, 1 - v))
    for i in range(84, 88):  # high-s twin: (r, N - s, 1 - v) recovers the same key
        d, r, s, v = signed(i)
        lanes.append(("high_s", d, r, host.N - s, 1 - v))
    gx = host.GX
    lanes += [
        ("handmade", bytes(32), gx, gx, 0),  # z = 0: Q = (s/r) * R = R
        ("handmade", bytes(32), gx, gx, 1),
        ("handmade", (1).to_bytes(32, "big"), gx, 1, 0),
        ("handmade", (1).to_bytes(32, "big"), gx, 1, 1),
        ("handmade", bytes(32), gx, host.N - 1, 0),
        ("handmade", (host.N - 1).to_bytes(32, "big"), gx, 2, 1),
    ]
    # R = +-cG with z = +-s*c: u1*G + u2*R is infinity for one parity
    for c in (1, 2, 15, rng.randrange(2, host.N)):
        rx = host.scalar_mul(c, lc.G)[0]
        if rx >= host.N:
            continue
        s = rng.randrange(1, host.N)
        for v in (0, 1):
            lanes.append(("infinity", (s * c % host.N).to_bytes(32, "big"), rx, s, v))
    d, r, s, v = signed(90)
    bad_x = _non_residue_x()
    lanes += [
        ("bad_r", d, 0, s, v),
        ("bad_r", d, host.N, s, v),
        ("bad_r", d, host.P - 1, s, v),
        ("bad_r", d, (1 << 256) - 1, s, v),
        ("bad_s", d, r, 0, v),
        ("bad_s", d, r, host.N, v),
        ("bad_s", d, r, (1 << 256) - 1, v),
        ("bad_v", d, r, s, 2),
        ("bad_v", d, r, s, 3),
        ("bad_v", d, r, s, 27),
        ("bad_v", d, r, s, -1),
        ("off_curve_lift", d, bad_x, s, 0),
        ("off_curve_lift", d, bad_x, s, 1),
        ("off_curve_lift", bytes(32), bad_x, bad_x, 0),
    ]
    i = 100
    while len(lanes) < 128:
        lanes.append(("valid",) + signed(i))
        i += 1
    assert len(lanes) == 128
    return lanes


RECOVER_CATEGORIES = (
    "valid",
    "wrong_digest",
    "wrong_parity",
    "high_s",
    "handmade",
    "infinity",
    "bad_r",
    "bad_s",
    "bad_v",
    "off_curve_lift",
)


@pytest.fixture(scope="module")
def recover_lanes():
    # Built on first use, not at import: 128 pure-Python signatures.
    lanes = _recover_lanes()
    assert {lane[0] for lane in lanes} == set(RECOVER_CATEGORIES)
    return lanes


def _recover_on_device(recover_lanes, lanes):
    cases = _tile(recover_lanes, lanes)
    qx, qy, ok = sec.ecdsa_recover(
        pack(host.digest_to_scalar(c[1]) for c in cases),
        pack(c[2] for c in cases),
        pack(c[3] for c in cases),
        jnp.asarray([c[4] for c in cases], dtype=jnp.int32),
    )
    xy = zip(fields.from_limbs(qx), fields.from_limbs(qy))
    return [q if o else None for o, q in zip(np.asarray(ok), xy)]


@pytest.fixture(scope="module")
def recover_oracle(recover_lanes):
    return [host.recover_pure(*lane[1:]) for lane in recover_lanes]


@pytest.fixture(scope="module", params=LADDER_LANES, ids=lambda n: f"{n}l")
def recover_device(request, recover_lanes):
    return _recover_on_device(recover_lanes, request.param)


@pytest.mark.parametrize("category", RECOVER_CATEGORIES)
def test_ecdsa_recover_matches_host_oracle(
    recover_lanes, recover_device, recover_oracle, category
):
    """Bit-identical key on every lane the oracle recovers, the identical
    mask on every lane it refuses, in every tile of the dispatch."""
    idx = [i for i, lane in enumerate(recover_lanes) if lane[0] == category]
    assert idx
    want = [recover_oracle[i] for i in idx]
    for tile in range(0, len(recover_device), len(recover_lanes)):
        assert [recover_device[tile + i] for i in idx] == want
    refused = [w is None for w in want]
    if category in ("bad_r", "bad_s", "bad_v", "off_curve_lift"):
        assert all(refused)
    elif category in ("infinity", "handmade"):  # one parity of R cancels
        assert any(refused) and not all(refused)
    else:
        assert not any(refused)


@pytest.mark.parametrize("lanes", CHIP_LANES, ids=lambda n: f"{n}l")
def test_ecdsa_recover_at_wide_lanes_on_the_chip(
    recover_lanes, recover_oracle, lanes
):
    if not _on_tpu():
        pytest.skip(f"{lanes} lanes: on the chip only (GO_IBFT_TPU_TESTS=1)")
    got = _recover_on_device(recover_lanes, lanes)
    assert got == _tile(recover_oracle, lanes)
